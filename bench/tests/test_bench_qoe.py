"""The benchmark's copy of the QoE arithmetic agrees with the program's."""
import numpy as np
import pytest

from bench import qoe
from repro.core import qoe as program_qoe


def timeline(seed):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(1, 300))
    gaps = rng.exponential(rng.uniform(0.005, 0.5), n)
    gaps[0] += rng.uniform(0.0, 3.0)          # time to first token
    return np.cumsum(gaps), float(rng.uniform(0.5, 2.0)), float(
        rng.uniform(2.0, 12.0)), n + int(rng.integers(0, 50))


@pytest.mark.parametrize("seed", range(12))
def test_qoe_exact_matches_program(seed):
    emit, ttft, tds, length = timeline(seed)
    arrival = 0.37
    emit = emit + arrival
    want = program_qoe.qoe_exact(emit, arrival,
                                 program_qoe.QoESpec(ttft=ttft, tds=tds),
                                 response_len=length)
    got = qoe.qoe_exact(emit, arrival, ttft, tds, response_len=length)
    assert got == want
    np.testing.assert_array_equal(qoe.pace_delivery(emit, tds),
                                  program_qoe.pace_delivery(emit, tds))


@pytest.mark.parametrize("seed", range(4))
def test_window_qoe_of_finished_stream_is_exact(seed):
    emit, ttft, tds, length = timeline(seed)
    assert qoe.window_qoe(emit, 0.0, ttft, tds, len(emit), 1e9, True) == \
        qoe.qoe_exact(emit, 0.0, ttft, tds, response_len=len(emit))


def test_window_qoe_censors_open_streams():
    # no token yet: 1 before the expected curve starts, 0 after
    assert qoe.window_qoe([], 0.0, 1.0, 5.0, 100, 0.5, False) == 1.0
    assert qoe.window_qoe([], 0.0, 1.0, 5.0, 100, 3.0, False) == 0.0
    # tokens at exactly the expected pace from t = ttft score 1 at any cut
    recv = 1.0 + np.arange(40) / 5.0
    assert qoe.window_qoe(recv, 0.0, 1.0, 5.0, 100, 6.0, False) == \
        pytest.approx(1.0, abs=0.03)
    # tokens after the cut do not count
    late = qoe.window_qoe(list(recv) + [50.0], 0.0, 1.0, 5.0, 100, 6.0,
                          False)
    assert late == qoe.window_qoe(recv, 0.0, 1.0, 5.0, 100, 6.0, False)
    # half the pace scores about half
    slow = 1.0 + np.arange(20) / 2.5
    assert 0.4 < qoe.window_qoe(slow, 0.0, 1.0, 5.0, 100, 9.0, False) < 0.6
