"""Native (C++) runtime tier: wire-format codec.

The compute path is JAX/XLA (ops/); this package holds the host runtime
pieces where native code pays. `codec.cpp` decodes JSON change lists (the
sync wire format) straight into the engine's columnar batch arrays
(measured on cpu: 3.5x the per-op Python decoder - JSON lexing dominates
both - and the run-detection walker 18x the numpy path).

The library builds lazily with g++ (no pybind11 — plain ctypes over an
extern-C API) and caches next to the source, keyed on a hash of the
source plus the build flags: a `build/` copied from another tree (the
chip machine gets a copy of the working tree) is rebuilt unless it came
from exactly this source. Every entry point degrades to
the pure-Python decoder when the toolchain or the .so is unavailable, or
when the batch contains shapes the native scope excludes (rich values,
non-list objects) — correctness never depends on the native tier.
"""

from __future__ import annotations

import ctypes
import hashlib
import json
import os
import subprocess
import threading

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_SO = os.path.join(_HERE, "build", "libamtpu_codec.so")
_SRC = os.path.join(_HERE, "codec.cpp")

_lock = threading.Lock()
_lib = None
_lib_failed = False


def _host_supports_avx2() -> bool:
    """True iff THIS machine's CPU runs AVX2. g++ happily compiles
    -march=x86-64-v3 on an AVX2-less x86 host (the compiler never checks
    the host CPU), and the resulting .so dies with SIGILL at the first
    vectorized call — a hard process kill no except-clause can catch, so
    the gate must be the runtime capability, not compile success."""
    try:
        with open("/proc/cpuinfo") as fh:
            return "avx2" in fh.read()
    except OSError:          # non-Linux: stay on baseline codegen
        return False


def _build_flags() -> list:
    # x86-64-v3 (AVX2/FMA baseline) lets gcc vectorize the columnar
    # predicate loops in detect_runs (measured 77 -> 47.5 ms at 10M ops);
    # NOT -march=native, so the .so stays valid on any AVX2-capable host
    flags = ["-O3", "-shared", "-fPIC", "-std=c++17", "-pthread"]
    if _host_supports_avx2():
        flags.insert(0, "-march=x86-64-v3")
    return flags


_KEY_STAMP = os.path.join(_HERE, "build", "build_key.txt")


def _build_key(flags: list) -> str:
    """sha256 of the codec source plus the flags: the cache key of the
    built library (mtimes say nothing about where a copied .so came
    from)."""
    h = hashlib.sha256()
    with open(_SRC, "rb") as fh:
        h.update(fh.read())
    h.update(" ".join(flags).encode())
    return h.hexdigest()


def _load():
    """Build (if stale) and load the codec library; None if unavailable."""
    global _lib, _lib_failed
    if _lib is not None or _lib_failed:
        return _lib
    with _lock:
        if _lib is not None or _lib_failed:
            return _lib
        try:
            flags = _build_flags()
            key = _build_key(flags)
            try:
                with open(_KEY_STAMP) as fh:
                    stamp_current = fh.read() == key
            except OSError:
                stamp_current = False
            if not os.path.exists(_SO) or not stamp_current:
                os.makedirs(os.path.dirname(_SO), exist_ok=True)
                # build beside the target and rename into place: several
                # processes (test workers) may build at once
                tmp = f"{_SO}.{os.getpid()}.tmp"
                subprocess.run(
                    ["g++", *flags, _SRC, "-o", tmp],
                    check=True, capture_output=True, timeout=120)
                os.replace(tmp, _SO)
                with open(f"{_KEY_STAMP}.{os.getpid()}.tmp", "w") as fh:
                    fh.write(key)
                os.replace(f"{_KEY_STAMP}.{os.getpid()}.tmp", _KEY_STAMP)
            lib = ctypes.CDLL(_SO)
            lib.amtpu_parse.restype = ctypes.c_void_p
            lib.amtpu_parse.argtypes = [ctypes.c_char_p, ctypes.c_long,
                                        ctypes.c_char_p]
            lib.amtpu_error.restype = ctypes.c_char_p
            lib.amtpu_error.argtypes = [ctypes.c_void_p]
            for name in ("amtpu_unsupported", "amtpu_n_changes",
                         "amtpu_n_ops", "amtpu_n_actors"):
                fn = getattr(lib, name)
                fn.restype = ctypes.c_long
                fn.argtypes = [ctypes.c_void_p]
            lib.amtpu_fill_ops.argtypes = [ctypes.c_void_p] + \
                [np.ctypeslib.ndpointer(dt, flags="C_CONTIGUOUS")
                 for dt in (np.int32, np.int8, np.int32, np.int32,
                            np.int32, np.int32, np.int64)]
            lib.amtpu_fill_seqs.argtypes = [
                ctypes.c_void_p,
                np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")]
            for name in ("amtpu_actors", "amtpu_actor_table", "amtpu_deps",
                         "amtpu_messages"):
                fn = getattr(lib, name)
                fn.restype = ctypes.c_char_p
                fn.argtypes = [ctypes.c_void_p]
            lib.amtpu_free.argtypes = [ctypes.c_void_p]
            i64p = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
            lib.amtpu_detect_runs.restype = ctypes.c_void_p
            lib.amtpu_detect_runs.argtypes = [
                ctypes.c_int64,
                np.ctypeslib.ndpointer(np.int8, flags="C_CONTIGUOUS"),
                np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
                np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
                np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
                np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
                np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
                np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
                ctypes.c_int64]
            for name in ("amtpu_plan_n_runs", "amtpu_plan_n_pairs",
                         "amtpu_plan_n_res", "amtpu_plan_n_ins"):
                fn = getattr(lib, name)
                fn.restype = ctypes.c_int64
                fn.argtypes = [ctypes.c_void_p]
            lib.amtpu_plan_blob_lt.restype = ctypes.c_int
            lib.amtpu_plan_blob_lt.argtypes = [ctypes.c_void_p,
                                               ctypes.c_int]
            lib.amtpu_plan_fill.argtypes = [
                ctypes.c_void_p, i64p, i64p, i64p, i64p, i64p,
                np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")]
            lib.amtpu_plan_free.argtypes = [ctypes.c_void_p]
            _lib = lib
        except Exception:
            _lib_failed = True
    return _lib


def available() -> bool:
    return _load() is not None


def detect_runs_native(kind, ta, tc, pa, pc, val64, op_row,
                       base_elems: int):
    """Single-pass C++ typing-run detection over op columns.

    Returns (hpos, run_len, head_slot, rpos, res_new_slot, blob, n_ins,
    blob_lt_128, blob_lt_256) or None when the native tier is unavailable.
    Bit-identical to the numpy detection (engine/runs.py) — pinned by
    tests/test_native_codec."""
    lib = _load()
    if lib is None:
        return None
    n = len(kind)
    h = lib.amtpu_detect_runs(
        n, np.ascontiguousarray(kind, np.int8),
        np.ascontiguousarray(ta, np.int32),
        np.ascontiguousarray(tc, np.int32),
        np.ascontiguousarray(pa, np.int32),
        np.ascontiguousarray(pc, np.int32),
        np.ascontiguousarray(val64, np.int64),
        np.ascontiguousarray(op_row, np.int32), base_elems)
    try:
        n_runs = lib.amtpu_plan_n_runs(h)
        n_pairs = lib.amtpu_plan_n_pairs(h)
        n_res = lib.amtpu_plan_n_res(h)
        hpos = np.empty(n_runs, np.int64)
        run_len = np.empty(n_runs, np.int64)
        head_slot = np.empty(n_runs, np.int64)
        rpos = np.empty(n_res, np.int64)
        res_new_slot = np.empty(n_res, np.int64)
        blob = np.empty(n_pairs, np.int32)
        lib.amtpu_plan_fill(h, hpos, run_len, head_slot, rpos,
                            res_new_slot, blob)
        return (hpos, run_len, head_slot, rpos, res_new_slot, blob,
                lib.amtpu_plan_n_ins(h),
                bool(lib.amtpu_plan_blob_lt(h, 128)),
                bool(lib.amtpu_plan_blob_lt(h, 256)))
    finally:
        lib.amtpu_plan_free(h)


def decode_text_changes(data, obj_id: str):
    """JSON change list (str/bytes) -> TextChangeBatch via the native codec.

    Returns None when the native tier is unavailable or the payload is out
    of its scope; the caller falls back to the Python decoder."""
    lib = _load()
    if lib is None:
        return None
    if isinstance(data, str):
        data = data.encode("utf-8")
    h = lib.amtpu_parse(data, len(data), obj_id.encode("utf-8"))
    try:
        if lib.amtpu_unsupported(h):
            return None
        n_changes = lib.amtpu_n_changes(h)
        n_ops = lib.amtpu_n_ops(h)
        op_change = np.empty(n_ops, np.int32)
        op_kind = np.empty(n_ops, np.int8)
        ta = np.empty(n_ops, np.int32)
        tc = np.empty(n_ops, np.int32)
        pa = np.empty(n_ops, np.int32)
        pc = np.empty(n_ops, np.int32)
        val = np.empty(n_ops, np.int64)
        if n_ops:
            lib.amtpu_fill_ops(h, op_change, op_kind, ta, tc, pa, pc, val)
        seqs = np.empty(n_changes, np.int32)
        if n_changes:
            lib.amtpu_fill_seqs(h, seqs)

        def split(raw):
            s = raw.decode("utf-8")
            return s.split("\n") if s else []

        from ..engine.columnar import intern_deps
        actors = split(lib.amtpu_actors(h))
        actor_table = split(lib.amtpu_actor_table(h))
        deps = intern_deps([json.loads(d) for d in split(lib.amtpu_deps(h))])
        raw_msgs = lib.amtpu_messages(h).decode("utf-8")
        messages = []
        if n_changes:
            for part in raw_msgs.split("\x1f"):
                messages.append(part[1:] if part[:1] == "1" else None)
        if not (len(actors) == len(deps) == len(messages) == n_changes):
            return None  # defensive: malformed joins -> python path

        from ..engine.columnar import TextChangeBatch
        return TextChangeBatch(
            obj_id=obj_id, actors=actors, seqs=seqs, deps=deps,
            messages=messages, op_change=op_change, op_kind=op_kind,
            op_target_actor=ta, op_target_ctr=tc, op_parent_actor=pa,
            op_parent_ctr=pc, op_value=val, actor_table=actor_table,
            value_pool=[])
    finally:
        lib.amtpu_free(h)
