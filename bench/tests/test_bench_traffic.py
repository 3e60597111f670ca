"""The benchmark's traffic generator against the program's workload/."""
import json
import pathlib

import numpy as np
import pytest

from bench import traffic
from repro.core.qoe import QoESpec
from repro.workload import arrivals, qoe_traces, sharegpt

MIXES = pathlib.Path(__file__).resolve().parents[1] / "traffic"


@pytest.mark.parametrize("seed", [0, 1, 2 ** 31 + 7])
def test_lengths_match_program(seed):
    a = traffic.sample_lengths(500, np.random.default_rng(seed))
    b = sharegpt.sample_lengths(500, np.random.default_rng(seed))
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)


@pytest.mark.parametrize("seed", [0, 5])
def test_arrivals_match_program(seed):
    np.testing.assert_array_equal(
        traffic.poisson_arrivals(2.5, 300, np.random.default_rng(seed)),
        arrivals.poisson_arrivals(2.5, 300, np.random.default_rng(seed)))
    np.testing.assert_array_equal(
        traffic.gamma_arrivals(4.0, 300, np.random.default_rng(seed), cv=3),
        arrivals.gamma_arrivals(4.0, 300, np.random.default_rng(seed), cv=3))


@pytest.mark.parametrize("mix,trace", [(traffic.READING_WPM,
                                        qoe_traces.reading_qoe_trace),
                                       (traffic.SPEAKING_WPM,
                                        qoe_traces.voice_qoe_trace)])
def test_specs_match_program(mix, trace):
    got = traffic.tds_trace(mix, 400, np.random.default_rng(3))
    want = trace(400, np.random.default_rng(3))
    assert [QoESpec(ttft=1.0, tds=float(t)) for t in got] == want


def test_clip_to_context():
    p = np.array([10, 600, 1020, 1024, 3])
    o = np.array([10, 600, 50, 900, 1024])
    cp, co = traffic.clip_to_context(p, o, 1024, 4)
    assert np.all(cp + co <= 1024)
    assert list(cp) == [10, 600, 1020, 1020, 3]
    assert list(co) == [10, 424, 4, 4, 1021]
    assert np.all(co >= 4)


@pytest.mark.parametrize("name", ["chat", "surge", "first_token"])
def test_every_seed_gets_the_same_work(name):
    mix = json.loads((MIXES / f"{name}.json").read_text())
    runs = [traffic.generate(mix, rate=3.0, seconds=45.0, seed=s,
                             vocab=1000, max_seq=1024)
            for s in (1, 2, 2 ** 31 + 11)]
    key = lambda reqs: [(r.due, len(r.prompt), r.output_len, r.tds)
                        for r in reqs]
    assert key(runs[0]) == key(runs[1]) == key(runs[2])
    assert runs[0][0].prompt.tolist() != runs[1][0].prompt.tolist()
    for reqs in runs:
        assert all(0 < r.due < 45.0 for r in reqs)
        assert all(len(r.prompt) + r.output_len <= 1024 for r in reqs)
    again = traffic.generate(mix, rate=3.0, seconds=45.0, seed=2,
                             vocab=1000, max_seq=1024)
    assert [(r.due, r.prompt.tolist()) for r in again] == \
        [(r.due, r.prompt.tolist()) for r in runs[1]]


def test_fixed_output_keeps_the_pool():
    """A mix with `output_tokens` has the same arrivals and prompts as the
    mix without it, and every output that long."""
    chat = json.loads((MIXES / "chat.json").read_text())
    first = json.loads((MIXES / "first_token.json").read_text())
    a, b = (traffic.generate(m, rate=20.0, seconds=45.0, seed=9,
                             vocab=1000, max_seq=1024) for m in (chat, first))
    assert [(r.due, r.prompt.tolist()) for r in a] == \
        [(r.due, r.prompt.tolist()) for r in b]
    assert {r.output_len for r in b} == {1}
