"""chip_smoke.py, the proof that the main path runs on the chip: its CPU
rehearsal passes end to end, and without --rehearse it refuses any
platform but a TPU."""

import json
import os
import subprocess
import sys

import pytest

from automerge_tpu._env import virtual_cpu_env

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(*args, timeout=600):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *args],
        capture_output=True, text=True, env=virtual_cpu_env(1), cwd=REPO,
        timeout=timeout)


@pytest.mark.parametrize("chips", ["1", "4"])
def test_rehearsal_passes(chips):
    out = _run("--rehearse", "--chips", chips)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-3000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last == {"ok": True, "device": {
        "platform": "cpu", "kind": "cpu", "count": int(chips)}}


def test_refuses_the_cpu_without_rehearse():
    out = _run(timeout=120)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert "no TPU" in out.stderr
