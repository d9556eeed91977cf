"""Whole rehearsal runs (CPU, the cells' tiny ``rehearse`` sizes, past
the harness's look for a chip) with the timed path broken underneath:
each fault must turn ``correct`` false, and the sound run must not.

Faults, switched on when the window opens:

- ``engine_unchanged``: the backend admits the changes (its clock
  advances) but the device engine's state is left as it was;
- ``service_unchanged``: the service's per-(room, doc) delivery step
  returns without delivering;
- ``half``: every second change of the delivered batches is left out;
- ``altered``: the server's fan-out alters the first value of every
  change it sends.

A one-chip cell has no exchange between chips, so that fault has no
case here.
"""

import json

import pytest

from loadbench import run
from loadbench.harness import Harness

CELLS = {"rooms-typing": 3}
FAULTS = ("engine_unchanged", "service_unchanged", "half", "altered")


def _alter_payload(payload):
    from automerge_tpu.engine.wire_format import as_frame, split_outgoing
    payload = dict(payload)
    if payload.get("changes"):
        payload["changes"] = [_alter(c) for c in payload["changes"]]
    if payload.get("wire") is not None:
        changes = [_alter(c) for c in as_frame(payload["wire"]).changes()]
        payload["wire"] = split_outgoing(changes, min_ops=1)[1]
    return payload


def _alter(change):
    ops = [dict(op) for op in change["ops"]]
    for op in ops:
        if op["action"] == "set":
            op["value"] = "#"
            break
    return dict(change, ops=ops)


def _install(monkeypatch, fault):
    from automerge_tpu.backend import device
    from automerge_tpu.engine.wire_format import as_frame, split_outgoing
    from automerge_tpu.resilience.channel import ResilientChannel
    from automerge_tpu.service import server

    on = {"v": False}
    counters = Harness.counters

    def arm(self):
        out = counters(self)
        on["v"] = True          # first call: the window opens
        return out
    monkeypatch.setattr(Harness, "counters", arm)

    if fault == "engine_unchanged":
        dist, dist_frame = (device._DeviceCore._distribute,
                            device._DeviceCore._distribute_frame)
        monkeypatch.setattr(
            device._DeviceCore, "_distribute",
            lambda self, applied, creations, routed=None:
            (set(), []) if on["v"]
            else dist(self, applied, creations, routed=routed))
        monkeypatch.setattr(
            device._DeviceCore, "_distribute_frame",
            lambda self, applied, frame:
            ({frame.obj_id}, []) if on["v"]
            else dist_frame(self, applied, frame))
    elif fault in ("service_unchanged", "half"):
        deliver = server.SyncService._deliver_one_group
        state = {"n": 0}

        def broken(self, key, room, payload):
            if not on["v"]:
                return deliver(self, key, room, payload)
            if fault == "service_unchanged":
                return None
            changes, senders, frames = payload
            keep = []
            for c, s in zip(changes, senders):
                state["n"] += 1
                if state["n"] % 2:
                    keep.append((c, s))
            halved = []
            for frame, sender in frames:
                ch = as_frame(frame).changes()
                halved.append((split_outgoing(ch[: len(ch) // 2] or ch[:1],
                                              min_ops=1)[1], sender))
            return deliver(self, key, room,
                           ([c for c, _ in keep], [s for _, s in keep],
                            halved))
        monkeypatch.setattr(server.SyncService, "_deliver_one_group",
                            broken)
    elif fault == "altered":
        send = ResilientChannel.send

        def altered_send(self, payload):
            if on["v"] and self.label is not None \
                    and isinstance(payload, dict):
                payload = _alter_payload(payload)
            return send(self, payload)
        monkeypatch.setattr(ResilientChannel, "send", altered_send)


def _run(capsys, workload, seed):
    rc = run.main(["--workload", workload, "--seed", str(seed),
                   "--seconds", str(CELLS[workload]), "--trace", "0",
                   "--rehearse"])
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    return json.loads(out[-1])


@pytest.mark.parametrize("workload", sorted(CELLS))
def test_sound_run_is_correct(capsys, workload):
    res = _run(capsys, workload, 2**31 + 17)
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("fault", FAULTS)
@pytest.mark.parametrize("workload", sorted(CELLS))
def test_fault_makes_correct_false(capsys, monkeypatch, workload, fault):
    _install(monkeypatch, fault)
    res = _run(capsys, workload, 2**31 + 29)
    assert res["correct"] is False, (fault, res["checks"])
