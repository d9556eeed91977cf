"""Bench harness contracts (benchmarks/common.py): a measurement runs on
the TPU unless the CPU was asked for, and the cfg5 CPU floor reads the
newest committed record row."""

import json

import pytest


def test_bench_platform_refuses_cpu_unasked(monkeypatch):
    """A JAX that found no chip and fell back to the CPU on its own must
    not produce a measurement: bench_platform raises."""
    from benchmarks import common
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(RuntimeError, match="no TPU"):
        common.bench_platform()


def test_bench_platform_cpu_when_asked(monkeypatch):
    from benchmarks import common
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert common.bench_platform() == "cpu"


def test_prior_committed_value_newest_wins(tmp_path):
    """The cpu-floor baseline is the NEWEST committed record row for the
    (metric, platform) pair — by numeric round, so r100 outranks r99 —
    and torn lines / other platforms skip."""
    import benchmarks.common as C
    root = str(tmp_path)
    (tmp_path / "BENCH_CONFIGS_r01.json").write_text(
        json.dumps({"metric": "cfg5_x", "platform": "cpu",
                    "value": 100.0}) + "\n")
    (tmp_path / "BENCH_CONFIGS_r02.json").write_text(
        "not json\n"
        + json.dumps({"metric": "cfg5_x", "platform": "tpu",
                      "value": 999.0}) + "\n"
        + json.dumps({"metric": "cfg5_x", "platform": "cpu",
                      "value": 200.0}) + "\n")
    assert C.prior_committed_value("cfg5_x", "cpu", root=root) == 200.0
    assert C.prior_committed_value("cfg5_x", "tpu", root=root) == 999.0
    assert C.prior_committed_value("missing", "cpu", root=root) is None
    # numeric round ordering: lexicographically "r99" > "r100", but the
    # newest round must still win
    (tmp_path / "BENCH_CONFIGS_r99.json").write_text(
        json.dumps({"metric": "cfg5_x", "platform": "cpu",
                    "value": 300.0}) + "\n")
    (tmp_path / "BENCH_CONFIGS_r100.json").write_text(
        json.dumps({"metric": "cfg5_x", "platform": "cpu",
                    "value": 400.0}) + "\n")
    assert C.prior_committed_value("cfg5_x", "cpu", root=root) == 400.0


def test_headline_cpu_floor_machine_check(tmp_path, capsys):
    """cfg5/headline cpu rows carry a machine-checked floor against the
    latest committed cpu row: met -> threshold_met True; a regression
    records False AND prints loudly; chip rows are untouched (floor_met
    covers them); no committed prior seeds instead of checking."""
    import benchmarks.common as C
    root = str(tmp_path)
    (tmp_path / "BENCH_CONFIGS_r05.json").write_text(
        json.dumps({"metric": "cfg5_y", "platform": "cpu",
                    "value": 1000.0}) + "\n")

    ok = {"metric": "y", "value": 900.0, "unit": "ops/s",
          "platform": "cpu", "threshold": "base"}
    C.headline_cpu_floor(ok, "cfg5_y", root=root)
    assert ok["threshold_met"] is True
    assert "machine-checked" in ok["threshold"]

    bad = {"metric": "y", "value": 700.0, "unit": "ops/s",
           "platform": "cpu", "threshold": "base"}
    C.headline_cpu_floor(bad, "cfg5_y", root=root)
    assert bad["threshold_met"] is False
    assert "HEADLINE CPU FLOOR MISS" in capsys.readouterr().err

    chip = {"metric": "y", "value": 1.0, "unit": "ops/s",
            "platform": "tpu", "threshold": "base"}
    C.headline_cpu_floor(chip, "cfg5_y", root=root)
    assert "threshold_met" not in chip

    fresh = {"metric": "z", "value": 1.0, "unit": "ops/s",
             "platform": "cpu", "threshold": "base"}
    C.headline_cpu_floor(fresh, "cfg5_z", root=root)
    assert "threshold_met" not in fresh and "seeds it" in fresh["threshold"]
