"""Reduce a ``jax.profiler`` trace (``.xplane.pb``) to device busy time,
per-op device time and the longest idle gaps, each gap attributed to the
benchmark phase the host was in.

Device operations are the events of the ``XLA Ops`` line of every
``/device:*`` plane (one per chip), each named by its program: the
``hlo_module`` stat where the event has one, else the ``XLA Modules``
event that encloses it. A trace with no device plane (a CPU rehearsal)
falls back to the host events that carry an ``hlo_module`` stat: there
XLA runs its ops on host threads. Busy time is the UNION of the
operation intervals inside the window, so overlapping operations count
once; the idle share is one minus busy over the window.

The window and the phases are ``jax.profiler.TraceAnnotation`` spans the
harness writes from its own files (``window``, ``inject``, ``svc.tick``,
``pump``, ``drain``); a gap is attributed to the innermost phase span
that encloses its midpoint.
"""

from __future__ import annotations

import bisect
import glob
import os

WINDOW = "window"
PHASES = ("inject", "svc.tick", "pump", "drain")


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def union(intervals) -> list:
    """Merge [start, end) intervals; overlapping ones count once."""
    out: list = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def clip(intervals, lo: int, hi: int) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def gaps(busy: list, lo: int, hi: int) -> list:
    """The idle intervals of [lo, hi) between merged busy intervals."""
    out = []
    t = lo
    for s, e in busy:
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


def enclosing(spans, t: int):
    """Name of the innermost span (start, end, name) holding instant t."""
    best = None
    for s, e, name in spans:
        if s <= t < e and (best is None or e - s < best[1] - best[0]):
            best = (s, e, name)
    return best[2] if best else "other"


def read(path: str):
    """(device op events, n devices, host annotation spans) of a trace.
    Events are (start_ns, end_ns, name, program, device index)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops: list = []
    annotations: list = []
    devices = 0
    cpu_ops: list = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            ops.extend(_device_ops(plane, devices))
            devices += 1
            continue
        for line in plane.lines:
            for ev in line.events:
                start = int(ev.start_ns)
                end = start + int(ev.duration_ns)
                if ev.name == WINDOW or ev.name in PHASES:
                    annotations.append((start, end, ev.name))
                    continue
                module = _stat(ev, "hlo_module")
                if module is not None:
                    cpu_ops.append((start, end, _stat(ev, "hlo_op")
                                    or ev.name, module, 0))
    if not devices:
        ops, devices = cpu_ops, 1
    return ops, devices, annotations


def _device_ops(plane, dev: int) -> list:
    modules: list = []
    raw: list = []
    for line in plane.lines:
        if line.name not in ("XLA Ops", "XLA Modules"):
            continue
        for ev in line.events:
            start = int(ev.start_ns)
            item = (start, start + int(ev.duration_ns), ev)
            (raw if line.name == "XLA Ops" else modules).append(item)
    modules.sort(key=lambda m: m[0])
    starts = [m[0] for m in modules]
    out = []
    for start, end, ev in raw:
        module = _stat(ev, "hlo_module")
        if module is None:
            k = bisect.bisect_right(starts, start) - 1
            if k >= 0 and modules[k][1] >= end:
                module = _short_module(modules[k][2].name)
        out.append((start, end, _short(ev.name), module, dev))
    return out


def _short_module(name: str) -> str:
    """``jit_fused_commit(1234)`` -> ``jit_fused_commit``."""
    return name.split("(", 1)[0]


def _short(name: str) -> str:
    """An HLO instruction's name without its text (``%fusion.31 = ...``
    -> ``fusion.31``)."""
    return name.split(" = ", 1)[0].lstrip("%")


def _stat(ev, key: str):
    for name, value in ev.stats:
        if name == key:
            return value
    return None


def reduce(ops, n_devices: int, annotations, top: int = 10) -> dict:
    """Busy time (the union of each device's operation intervals,
    averaged over the devices), device time per op (``program/op``) and
    per program summed over the devices, and the longest gaps of the window in which no
    device ran anything. An empty window or one with no operation gives
    busy 0 and no op times."""
    wins = [(s, e) for s, e, name in annotations if name == WINDOW]
    if not wins:
        raise ValueError("the trace holds no window annotation")
    lo, hi = wins[0]
    phases = [a for a in annotations if a[2] in PHASES]
    op_ns: dict = {}
    module_ns: dict = {}
    for s, e, name, module, _dev in ops:
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        key = name if module is None else f"{module}/{name}"
        op_ns[key] = op_ns.get(key, 0) + (e - s)
        if module is not None:
            module_ns[module] = module_ns.get(module, 0) + (e - s)
    per_dev = [sum(e - s for s, e in union(clip(
        [(s, e) for s, e, _n, _m, d in ops if d == dev], lo, hi)))
        for dev in range(n_devices)]
    busy_ns = sum(per_dev) / n_devices
    any_busy = union(clip([(s, e) for s, e, _n, _m, _d in ops], lo, hi))
    idle = sorted(gaps(any_busy, lo, hi),
                  key=lambda g: g[0] - g[1])[:top]
    return {
        "window_ns": hi - lo,
        "busy_ns": busy_ns,
        "n_devices": n_devices,
        "op_ns": op_ns,
        "module_ns": module_ns,
        "device_ops": sorted(op_ns.items(), key=lambda kv: -kv[1])[:top],
        "idle_gaps": [(enclosing(phases, (s + e) // 2), e - s)
                      for s, e in idle],
    }


def reduce_dir(log_dir: str, top: int = 10) -> dict:
    return reduce(*read(find_xplane(log_dir)), top=top)
