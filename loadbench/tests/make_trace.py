"""Record the small CPU profiler trace that test_trace_reduce.py reads.

    JAX_PLATFORMS=cpu python3 loadbench/tests/make_trace.py

Inside a ``window`` annotation: two threads run jitted matrix products at
once (overlapping device operations), then the host sleeps inside a
``pump`` annotation while nothing runs (an idle gap), then one more
product runs inside ``svc.tick``. Writes tests/data/cpu_window.xplane.pb.
"""

import glob
import os
import shutil
import tempfile
import threading
import time

import jax
import jax.numpy as jnp

OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                   "cpu_window.xplane.pb")


def main():
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((384, 384), jnp.float32)
    f(x).block_until_ready()
    tmp = tempfile.mkdtemp()
    try:
        jax.profiler.start_trace(tmp)
        with jax.profiler.TraceAnnotation("window"):
            with jax.profiler.TraceAnnotation("inject"):
                threads = [threading.Thread(
                    target=lambda: [f(x).block_until_ready()
                                    for _ in range(3)])
                    for _ in range(2)]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join()
            with jax.profiler.TraceAnnotation("pump"):
                time.sleep(0.05)
            with jax.profiler.TraceAnnotation("svc.tick"):
                f(x).block_until_ready()
        jax.profiler.stop_trace()
        path = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                         recursive=True)[0]
        shutil.copyfile(path, OUT)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    print(OUT, os.path.getsize(OUT))


if __name__ == "__main__":
    main()
