"""The benchmark's own tests run on the CPU (the rehearsal sizes of the
cells), never on a chip: JAX is pinned to the CPU before it loads."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("AMTPU_FUSED_MODE", "interpret")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
