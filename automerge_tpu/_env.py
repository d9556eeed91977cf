"""Process environment recipes: the virtual CPU device mesh and the one
place the persistent compile cache is set.

XLA reads its flags once, when the first backend initializes, so a
process that needs an N-device virtual CPU platform (tests, the driver's
multichip dryrun) sets ``virtual_cpu_env`` before anything touches a JAX
backend — in practice, before it imports the engine. This module imports
JAX only inside ``setup_compile_cache``.
"""

from __future__ import annotations

import os

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def virtual_cpu_env(n_devices: int, base: dict | None = None) -> dict:
    """A copy of ``base`` (default: os.environ) rewritten so that a fresh
    interpreter lands on an ``n_devices``-device virtual CPU platform:
    JAX_PLATFORMS is forced to cpu and any existing
    --xla_force_host_platform_device_count is replaced."""
    env = dict(os.environ if base is None else base)
    env["JAX_PLATFORMS"] = "cpu"
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f]
    flags.append(f"--xla_force_host_platform_device_count={n_devices}")
    env["XLA_FLAGS"] = " ".join(flags)
    return env


def compile_cache_dir(env: dict | None = None) -> str:
    """Where the persistent compile cache lives: ``JAX_COMPILATION_CACHE_DIR``
    when set (the deployment places it), else ``<repo>/.jax_cache`` —
    resolved from this file, never from the working directory, so every
    entry point and every working directory shares one cache."""
    e = os.environ if env is None else env
    return e.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        _REPO, ".jax_cache")


def setup_compile_cache() -> str:
    """Point JAX's persistent compile cache at ``compile_cache_dir()``.
    The only place in the repo that sets the cache; every entry point
    (bench.py, profile_bench.py, benchmarks/, chip_smoke.py, the test
    suite) calls it before its first compile. Returns the directory."""
    import jax

    path = compile_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def compile_cache_state(env: dict | None = None) -> dict:
    """The persistent-compile-cache placement as observable state (ISSUE
    15): the directory this process resolves, and what is on disk right
    now. jax-free — safe from `metrics_snapshot()` and the bench record
    path in any process. Session-level first-compile vs cache-served
    counts live next to this in
    ``obs.device_truth.compile_cache_snapshot()``."""
    cache_dir = compile_cache_dir(env)
    entries = 0
    exists = False
    try:
        names = os.listdir(cache_dir)
        exists = True
        # the cache writes one `-cache` payload per executable plus
        # an `-atime` sidecar; count payloads only
        entries = sum(1 for n in names if not n.endswith("-atime"))
    except OSError:
        pass
    return {"dir": cache_dir, "exists": exists, "entries": entries}
