"""int32-envelope capacity guards (ISSUE 4 satellite).

The device tier packs elemId keys as (actor_rank << 32 | ctr) int64 and
stores every column int32; actor ranks stand in for the reference's
string ordering (op_set.js:432-436). A counter, seq, or rank past
2^31-1 — or negative — would therefore WRAP into wrong ordering
silently. These tests pin that every packing/encoding site fails loudly
(OverflowError) instead.
"""

import numpy as np
import pytest

from automerge_tpu._common import INT32_MAX, check_int32_envelope
from automerge_tpu.engine import TextChangeBatch
from automerge_tpu.engine.columnar import MapChangeBatch
from automerge_tpu.engine.host_index import pack_keys


def test_check_int32_envelope_bounds():
    check_int32_envelope("x", np.asarray([0, 1, INT32_MAX]))
    with pytest.raises(OverflowError, match="envelope"):
        check_int32_envelope("x", np.asarray([INT32_MAX + 1]))
    with pytest.raises(OverflowError, match="envelope"):
        check_int32_envelope("x", np.asarray([-1]))
    check_int32_envelope("x", np.empty(0, np.int64))     # empty: no-op


def test_pack_keys_rejects_overflowing_ctr():
    ok = pack_keys(np.asarray([1, 2]), np.asarray([5, INT32_MAX]))
    assert ok.dtype == np.int64
    with pytest.raises(OverflowError, match="elemId counter"):
        pack_keys(np.asarray([1]), np.asarray([INT32_MAX + 1]))
    with pytest.raises(OverflowError, match="elemId counter"):
        pack_keys(np.asarray([1]), np.asarray([-7]))
    with pytest.raises(OverflowError, match="actor rank"):
        pack_keys(np.asarray([-2]), np.asarray([1]))


def test_pack_keys_boundary_does_not_collide():
    """Adjacent in-envelope keys stay distinct and ordered — the property
    a silent wrap would destroy."""
    keys = pack_keys(np.asarray([0, 0, 1]),
                     np.asarray([INT32_MAX - 1, INT32_MAX, 0]))
    assert len(set(keys.tolist())) == 3
    assert (np.diff(keys) > 0).all()


def test_text_batch_rejects_overflowing_elem_counter():
    """Wire changes minting an elemId counter past the envelope fail at
    batch construction — before anything reaches a device column."""
    big = INT32_MAX + 1
    changes = [{"actor": "a", "seq": 1, "deps": {}, "ops": [
        {"action": "ins", "obj": "t", "key": "_head", "elem": big}]}]
    with pytest.raises(OverflowError, match="elemId counter"):
        TextChangeBatch.from_changes(changes, "t")
    # a parent reference overflowing is caught by the same gate
    changes = [{"actor": "a", "seq": 1, "deps": {}, "ops": [
        {"action": "ins", "obj": "t", "key": f"b:{big}", "elem": 1}]}]
    with pytest.raises(OverflowError, match="counter"):
        TextChangeBatch.from_changes(changes, "t")


def test_batches_reject_overflowing_seq():
    changes = [{"actor": "a", "seq": INT32_MAX + 1, "deps": {}, "ops": [
        {"action": "ins", "obj": "t", "key": "_head", "elem": 1}]}]
    with pytest.raises(OverflowError, match="seq"):
        TextChangeBatch.from_changes(changes, "t")
    mchanges = [{"actor": "a", "seq": INT32_MAX + 1, "deps": {}, "ops": [
        {"action": "set", "obj": "m", "key": "k", "value": 1}]}]
    with pytest.raises(OverflowError, match="seq"):
        MapChangeBatch.from_changes(mchanges, "m")
    # seq 0 / negative is equally outside the envelope (lo=1)
    zchanges = [{"actor": "a", "seq": 0, "deps": {}, "ops": [
        {"action": "ins", "obj": "t", "key": "_head", "elem": 1}]}]
    with pytest.raises(OverflowError, match="seq"):
        TextChangeBatch.from_changes(zchanges, "t")


def test_in_envelope_batch_still_round_trips():
    """The guard must not reject legitimate large-but-legal counters."""
    from automerge_tpu.engine import DeviceTextDoc

    changes = [{"actor": "a", "seq": 1, "deps": {}, "ops": [
        {"action": "ins", "obj": "t", "key": "_head",
         "elem": INT32_MAX},
        {"action": "set", "obj": "t", "key": f"a:{INT32_MAX}",
         "value": "z"}]}]
    doc = DeviceTextDoc("t")
    doc.apply_batch(TextChangeBatch.from_changes(changes, "t"))
    assert doc.text() == "z"
    assert doc.elem_ids() == [f"a:{INT32_MAX}"]
