"""Roofline-derived latency model (Appendix B/D adaptation)."""
import types

import pytest

from repro.configs import get_config
from repro.core import (A100_4X, TPU_V5E, TPU_V5E_POD, LatencyModel,
                        hardware_for_device)


CFG = get_config("opt-66b")


def test_latency_linear_in_batch():
    """Paper Appendix B: iteration latency ~ a + b*B (memory-bound slope)."""
    lat = LatencyModel(CFG, A100_4X)
    l1 = lat.iter_latency(10, 10 * 500)
    l2 = lat.iter_latency(110, 110 * 500)
    l3 = lat.iter_latency(210, 210 * 500)
    assert l2 > l1 and l3 > l2
    slope1 = (l2 - l1) / 100
    slope2 = (l3 - l2) / 100
    assert slope1 == pytest.approx(slope2, rel=0.05)


def test_generation_speed_matches_paper():
    """Fig 3b: ~6.6-9 tok/s per request at operating batch on 4xA100."""
    lat = LatencyModel(CFG, A100_4X)
    rate = lat.token_rate(100, 100 * 550)
    assert 5.0 < rate < 10.0


def test_decode_memory_bound_prefill_compute_bound():
    lat = LatencyModel(CFG, A100_4X)
    # decode: memory term dominates
    b = 50
    flops_t = 2 * CFG.param_count() * b / lat._agg_flops
    mem_t = lat.param_bytes / lat._agg_bw
    assert mem_t > flops_t
    # prefill at long prompts: compute term dominates
    p = 2048
    flops_p = 2 * CFG.param_count() * p / lat._agg_flops
    assert flops_p > mem_t


def test_swap_cheaper_than_recompute_for_long_ctx():
    """Appendix D: swap ~ one iteration; recompute grows with context."""
    lat = LatencyModel(CFG, A100_4X)
    assert lat.swap_latency(500) < lat.recompute_latency(2000)


def test_max_batch_from_latency_monotone():
    lat = LatencyModel(CFG, A100_4X)
    b_fast = lat.max_batch_from_latency(1 / 8.0)    # stringent TDS
    b_slow = lat.max_batch_from_latency(1 / 3.0)    # lenient TDS
    assert b_slow >= b_fast >= 1


def test_ssm_state_weight():
    mamba = get_config("falcon-mamba-7b")
    assert mamba.kv_bytes_per_token() == 0
    assert mamba.ssm_state_bytes() > 0
    lat = LatencyModel(mamba, TPU_V5E_POD)
    # context length barely affects SSM decode latency
    l_small = lat.iter_latency(32, 32 * 100)
    l_big = lat.iter_latency(32, 32 * 100_000)
    assert l_big == pytest.approx(l_small, rel=1e-6)


def test_hardware_lookup_by_device_kind():
    """Wall-clock pacing uses the profile of the chip JAX reports; the
    CPU runs the virtual clock's TPU_V5E; an unknown chip is an error."""
    dev = types.SimpleNamespace
    assert hardware_for_device(dev(platform="cpu", device_kind="cpu")) \
        is TPU_V5E
    assert hardware_for_device(
        dev(platform="tpu", device_kind="TPU v5 lite")) is TPU_V5E
    with pytest.raises(ValueError, match="TPU v9"):
        hardware_for_device(dev(platform="tpu", device_kind="TPU v9"))
