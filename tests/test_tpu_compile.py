"""Compile the main path's kernels for a described TPU v5e, no chip attached.

What the TPU compiler refuses here (an unaligned slice, more VMEM than a
kernel may use, a program that does not fit the device) costs no chip
time. Shapes are the real widths the chip runs:

- config 5 (bench.py / chip_smoke.py phase b): a 1M-char base
  (1,572,864-slot tables) merged with 10,000 x 1,000-op changes — 10,000
  runs (R = 12,288), 5M value pairs (N = 6,291,456), out_cap 8,388,608,
  live window L = 6,291,456, S = 16,384 segments (the shapes the chip
  compiled in PR 22's chip_smoke.py run);
- cfg12 serving lane (chip_smoke.py phase c): 640 text docs x 512 slots
  per lane, and the 640 x 2,048-slot map population of bench.py
  --sharded.

Every test but the combined scatter (which holds no Pallas kernel)
asserts the Mosaic kernel (`tpu_custom_call`) is in the compiled HLO.
The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from automerge_tpu.ops import fused_round as F
from automerge_tpu.ops.scan_pallas import fused_segment_scans, multi_scan

I32, BOOL, U8 = jnp.int32, jnp.bool_, jnp.uint8
# the 9 element tables every commit kernel leads with
TABLE_DTYPES = (I32, I32, I32, I32, BOOL, I32, I32, BOOL, BOOL)
# the 5 register tables of a map doc
REG_DTYPES = (I32, BOOL, I32, I32, BOOL)

# config 5
CAP5_IN, CAP5_OUT = 1_572_864, 8_388_608
R5, N5, L5, S5 = 12_288, 6_291_456, 6_291_456, 16_384
# cfg12 lanes
D12, TEXT_CAP12, MAP_CAP12 = 640, 512, 2_048


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler, or the library is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_hlo(fn, args, kwargs=None):
    return fn.lower(*args, **(kwargs or {})).compile().as_text()


@pytest.mark.parametrize("n", [1_024, 1_048_576, N5])
def test_multi_scan(one_chip, n):
    hlo = _compiled_hlo(multi_scan, [_sds(one_chip, (6, n), I32)])
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("c", [1_024, 1_048_576, CAP5_OUT])
def test_fused_segment_scans(one_chip, c):
    hlo = _compiled_hlo(
        fused_segment_scans,
        [_sds(one_chip, (c,), BOOL), _sds(one_chip, (c,), BOOL),
         _sds(one_chip, (), I32)])
    assert "tpu_custom_call" in hlo


def _tables(sh, lead, cap):
    return [_sds(sh, lead + (cap,), dt) for dt in TABLE_DTYPES]


def test_fused_commit_round_config5(one_chip):
    args = _tables(one_chip, (), CAP5_IN) + [
        _sds(one_chip, (9, R5), I32), _sds(one_chip, (N5,), U8)]
    hlo = _compiled_hlo(F.fused_commit_round, args, dict(
        out_cap=CAP5_OUT, S=S5, as_u8=True, L=L5, mode="pallas"))
    assert "tpu_custom_call" in hlo


def test_fused_commit_round_planned_config5(one_chip):
    args = _tables(one_chip, (), CAP5_IN) + [
        _sds(one_chip, (9, R5), I32), _sds(one_chip, (N5,), U8),
        _sds(one_chip, (4, S5), I32)]
    hlo = _compiled_hlo(F.fused_commit_round_planned, args, dict(
        out_cap=CAP5_OUT, S=S5, as_u8=True, L=L5, mode="pallas"))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("cap_in,cap_out,r,n", [
    (CAP5_IN, CAP5_OUT, R5, N5),          # config 5, solo round
    (TEXT_CAP12, TEXT_CAP12, 64, 256),    # one cfg12 doc
])
def test_fused_mixed_round(one_chip, cap_in, cap_out, r, n):
    args = _tables(one_chip, (), cap_in) + [
        _sds(one_chip, (9, r), I32), _sds(one_chip, (n,), I32),
        _sds(one_chip, (8, 128), I32), _sds(one_chip, (64,), I32),
        _sds(one_chip, (3, 64), I32)]
    hlo = _compiled_hlo(F.fused_mixed_round, args,
                        dict(out_cap=cap_out, mode="pallas"))
    assert "tpu_custom_call" in hlo


def _absent(sh, n):
    return [_sds(sh, (1, 1), I32)] * n


def _map_lane(sh):
    return ([_sds(sh, (D12, MAP_CAP12), dt) for dt in REG_DTYPES]
            + [_sds(sh, (D12, 5, 128), I32), _sds(sh, (D12, 64), I32)])


def _text_lane(sh):
    return _tables(sh, (D12,), TEXT_CAP12) + [
        _sds(sh, (D12, 9, 64), I32), _sds(sh, (D12, 256), I32),
        _sds(sh, (D12, 8, 128), I32), _sds(sh, (D12, 64), I32),
        _sds(sh, (D12, 3, 64), I32)]


@pytest.mark.parametrize("lanes", ["text", "map+text"])
def test_fused_stacked_round_cfg12(one_chip, lanes):
    with_map = lanes == "map+text"
    args = ((_map_lane(one_chip) if with_map else _absent(one_chip, 7))
            + _text_lane(one_chip))
    hlo = _compiled_hlo(F.fused_stacked_round, args, dict(
        map_cap=MAP_CAP12 if with_map else 1, text_cap=TEXT_CAP12,
        with_map=with_map, with_text=True, mode="pallas"))
    assert "tpu_custom_call" in hlo


def test_fused_scatter_registers_cfg12(one_chip):
    # the combined writeback carries no Pallas kernel: it must compile
    wb = _sds(one_chip, (D12, 6, 64), I32)
    regs = [_sds(one_chip, (D12, MAP_CAP12), dt) for dt in REG_DTYPES]
    text_regs = [_sds(one_chip, (D12, TEXT_CAP12), dt)
                 for dt in TABLE_DTYPES[3:8]]
    hlo = _compiled_hlo(F.fused_scatter_registers, regs + [wb]
                        + text_regs + [wb],
                        dict(with_map=True, with_text=True))
    assert "scatter" in hlo
