"""The driver's entry points must stay green: a red dryrun zeroes out the
multichip-correctness axis regardless of how good the mesh unit tests are
(round-1 lesson).
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import __graft_entry__


def test_entry_compiles_and_runs():
    import jax

    fn, args = __graft_entry__.entry()
    pos, out, n_vis = jax.jit(fn)(*args)
    assert pos.shape == args[0].shape
    assert n_vis.shape[0] == args[0].shape[0]


def test_dryrun_multichip_8():
    # The dryrun itself spawns a virtual-CPU subprocess, so this is safe to
    # run inside pytest regardless of which platform the suite runs on.
    __graft_entry__.dryrun_multichip(8)

