"""Tolerance harness + wall-clock engine differential (ISSUE 9 tentpole).

Unit layer: compare_requests gate mechanics on synthetic populations
(identical pass, perturbed fail, token mismatch, missing rid, cancelled
skip). Integration layer: a real smoke-model ServingEngine run twice on
the same trace — virtual clock vs clock="wall" — must deliver identical
token text and pass the timing gates; plus cancel() semantics on both
clocks and the simulator.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import LatencyModel, QoESpec, TPU_V5E, make_scheduler
from repro.core.request import Request, ReqState
from repro.models import Model
from repro.serving import (ServingEngine, ServingSimulator, SimConfig,
                           Tolerance, ToleranceSpec, compare_requests)

SPEC = QoESpec(ttft=1.0, tds=4.8)


def served(rid, arrival, emits, tokens, cancelled=False):
    r = Request(rid=rid, arrival=arrival, prompt_len=8, output_len=len(emits),
                spec=SPEC)
    r.emit_times = list(emits)
    r.output_tokens = list(tokens)
    r.generated = len(emits)
    r.state = ReqState.FINISHED
    r.cancelled = cancelled
    return r


def population(n=12, seed=0, skew=0.0, jitter=0.0):
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        arr = i * 0.2
        e = arr + 0.4 + np.arange(10) * 0.12
        e = e + skew + (rng.uniform(0, jitter, 10) if jitter else 0.0)
        toks = list(100 * i + np.arange(10))
        out.append(served(i, arr, e, toks))
    return out


# ---------------------------------------------------------------------------
# gate mechanics
# ---------------------------------------------------------------------------

def test_identical_populations_pass():
    ref = population()
    rep = compare_requests(ref, population())
    assert rep.ok and rep.n_pairs == 12
    assert not rep.token_mismatches and not rep.missing_rids
    assert "OK" in rep.summary()
    rep.assert_ok()


def test_small_jitter_passes_large_skew_fails():
    ref = population()
    assert compare_requests(ref, population(jitter=0.004)).ok
    rep = compare_requests(ref, population(skew=1.0))
    assert not rep.ok
    failed = {g.name for g in rep.gates if not g.passed}
    assert "ttft_mean_diff" in failed
    with pytest.raises(AssertionError, match="FAIL"):
        rep.assert_ok()


def test_token_mismatch_is_a_hard_gate():
    ref = population()
    cand = population()
    cand[3].output_tokens[5] = -999
    rep = compare_requests(ref, cand)
    assert rep.token_mismatches == [3] and not rep.ok
    # ...unless identity is explicitly waived
    waived = compare_requests(
        ref, cand, dataclasses.replace(ToleranceSpec(),
                                       require_token_identity=False))
    assert waived.ok


def test_length_mismatch_counts_unless_cancelled():
    ref = population()
    cand = population()
    cand[2].output_tokens = cand[2].output_tokens[:4]  # truncated, same text
    assert compare_requests(ref, cand).token_mismatches == [2]
    # a cancelled request legitimately has a shorter (prefix) output
    cand[2].cancelled = True
    rep = compare_requests(ref, cand)
    assert not rep.token_mismatches
    assert 2 in rep.skipped_rids and rep.n_pairs == 11


def test_missing_rid_fails():
    ref = population()
    rep = compare_requests(ref, ref[:-1])
    assert rep.missing_rids == [11] and not rep.ok


def test_tolerance_relative_part():
    t = Tolerance(abs_tol=0.1, rel_tol=0.1)
    assert t.ok(10.0, 10.9)          # 0.9 <= 0.1 + 1.0
    assert not t.ok(10.0, 11.2)
    assert t.ok(float("nan"), float("nan"))


# ---------------------------------------------------------------------------
# wall-clock engine differential (the new verification spine)
# ---------------------------------------------------------------------------

def _mk_engine(clock):
    cfg = get_smoke_config("llama3-8b")
    m = Model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    lat = LatencyModel(cfg, TPU_V5E)
    sched = make_scheduler("andes", 4 * 64, lat)
    return cfg, ServingEngine(m, params, sched, lat, num_slots=4,
                              max_seq=64, clock=clock)


def _trace(cfg, n=6, out_len=10, stagger=0.03, seed=2):
    rng = np.random.default_rng(seed)
    wl = []
    for i in range(n):
        plen = int(rng.integers(5, 16))
        wl.append(Request(rid=i, arrival=i * stagger, prompt_len=plen,
                          output_len=out_len, spec=SPEC,
                          prompt_tokens=rng.integers(0, cfg.vocab_size,
                                                     plen)))
    return wl


@pytest.mark.slow
def test_wall_vs_virtual_engine_tolerance():
    """The acceptance-criteria differential, in-process: same trace through
    a virtual and a wall engine; token text identical, timing within the
    (CI-generous) gates. Exercised per-PR over a real socket by the server
    smoke job; marked slow here because the wall run takes real seconds."""
    cfg, eng_v = _mk_engine("virtual")
    ref = eng_v.run(_trace(cfg), max_iterations=2000)
    cfg, eng_w = _mk_engine("wall")
    # warmup: jit compilation would otherwise land in the first requests'
    # wall TTFTs (run() resets serving state but keeps the compile caches —
    # exactly what a real server's warmup request does)
    eng_w.run(_trace(cfg, n=2, out_len=4), max_iterations=200)
    cand = eng_w.run(_trace(cfg), max_iterations=2000)
    # paced wall clock never runs ahead of schedule by construction, and a
    # smoke-model virtual run finishes in a few wall seconds
    spec = ToleranceSpec(
        ttft_mean_diff=Tolerance(abs_tol=0.5),
        ttft_p95_diff=Tolerance(abs_tol=1.0),
        ttft_max_diff=Tolerance(abs_tol=2.0),
        tds_mean_diff=Tolerance(abs_tol=2.0, rel_tol=0.5),
        qoe_mean_diff=Tolerance(abs_tol=0.30),
        qoe_max_diff=Tolerance(abs_tol=0.60),
        qoe_mean_of=Tolerance(abs_tol=0.30),
    )
    rep = compare_requests(ref, cand, spec)
    assert not rep.token_mismatches, rep.summary()
    assert not rep.missing_rids
    rep.assert_ok()
    # wall timestamps are real monotonic readings: never behind virtual's
    # deterministic schedule by more than scheduling noise, and the run's
    # makespan is real elapsed time (> 0)
    assert eng_w.result().makespan > 0


def test_wall_clock_pacing_unit():
    """_tick pacing invariant without a model: deadlines accumulate, and
    the clock never runs ahead of the schedule."""
    import time

    class Eng:
        _tick = ServingEngine._tick
        wall_now = ServingEngine.wall_now

        def __init__(self):
            self.clock = "wall"
            self.now = 0.0
            self.pace_seconds = 0.0
            self._wall0 = time.monotonic()

    e = Eng()
    for _ in range(5):
        e._tick(0.01, "decode")
    assert e.now >= 0.05 - 1e-6          # paced: slept the modeled time
    assert e.wall_now() >= e.now - 1e-6
    assert 0 < e.pace_seconds <= e.now   # ...and counted the sleeps
    v = Eng(); v.clock = "virtual"
    v._tick(0.25, "decode")
    assert v.now == 0.25 and v.wall_now() == 0.25
    assert v.pace_seconds == 0.0


# ---------------------------------------------------------------------------
# cancellation
# ---------------------------------------------------------------------------

def _sim():
    cfg = get_smoke_config("llama3-8b")
    lat = LatencyModel(cfg, TPU_V5E)
    sched = make_scheduler("andes", 256, lat)
    return ServingSimulator(sched, lat, SimConfig(kv_capacity_tokens=256))


def test_simulator_cancel_pending_live_finished():
    sim = _sim()
    reqs = [Request(rid=i, arrival=i * 10.0, prompt_len=8, output_len=20,
                    spec=SPEC) for i in range(3)]
    for r in reqs:
        sim.submit(r)
    # live cancel: step until rid 0 has a few tokens
    while reqs[0].generated < 3:
        sim.step()
    assert sim.cancel(0)
    assert reqs[0].cancelled and reqs[0].state == ReqState.FINISHED
    assert reqs[0].generated == 3
    # pending cancel: rid 2 hasn't arrived yet
    assert sim.cancel(2)
    assert reqs[2].cancelled and not reqs[2].emit_times
    # unknown + already-finished cancels are no-ops
    assert not sim.cancel(99)
    assert not sim.cancel(0)
    # the remaining request still completes
    while sim.step():
        pass
    assert reqs[1].generated == 20 and not reqs[1].cancelled


def test_engine_cancel_running(llama_engine=None):
    cfg, eng = _mk_engine("virtual")
    wl = _trace(cfg, n=3, out_len=30, stagger=0.0)
    for r in wl:
        eng.submit(r)
    while wl[0].generated < 4:
        eng.step()
    slots_before = eng.kv.slots_in_use
    assert eng.cancel(0)
    gen_at_cancel = wl[0].generated   # multi-step may batch several tokens
    assert wl[0].cancelled and wl[0].state == ReqState.FINISHED
    assert eng.kv.slots_in_use == slots_before - 1   # slot freed
    assert not eng.cancel(0)
    while eng.step():
        pass
    # survivors finish with full token counts; cancelled kept its prefix
    assert wl[0].generated == gen_at_cancel >= 4
    assert all(r.generated == 30 for r in wl[1:])


def test_engine_cancel_tokens_unchanged_for_survivors():
    """Cancelling one stream must not change any other stream's text
    (row independence — the same argument behind wall-clock identity)."""
    cfg, ref_eng = _mk_engine("virtual")
    ref = ref_eng.run(_trace(cfg, n=3, out_len=12, stagger=0.0),
                      max_iterations=1000)
    cfg, eng = _mk_engine("virtual")
    wl = _trace(cfg, n=3, out_len=12, stagger=0.0)
    for r in wl:
        eng.submit(r)
    while wl[1].generated < 2:
        eng.step()
    eng.cancel(1)
    while eng.step():
        pass
    ref_by = {r.rid: r for r in ref}
    for r in (wl[0], wl[2]):
        assert r.output_tokens == ref_by[r.rid].output_tokens
    assert wl[1].output_tokens == ref_by[1].output_tokens[:wl[1].generated]
