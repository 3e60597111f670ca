"""Engine-as-oracle differential tests for the steppable ServingEngine.

PR 1 made the discrete-event simulator steppable so the cluster layer
could drive it; this suite guards the same refactor applied to the real
engine. ``legacy_run`` below is a faithful transcription of the
pre-refactor monolithic ``ServingEngine.run()`` loop (the PR 0 seed),
driving the engine's private helpers directly with loop-local
pending/live lists. The steppable engine — whether driven by the thin
``run()`` wrapper, by manual ``submit()``+``step()``, or cluster-style
(submit each request only once the clock reaches its arrival) — must
reproduce it *bit-for-bit*: identical token ids, identical emission
timestamps (exact float equality: same operations in the same order),
identical preemption events, identical final QoE.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from repro.configs import get_smoke_config
from repro.core import LatencyModel, QoESpec, SchedulerConfig, TPU_V5E, make_scheduler
from repro.cluster import SteppableBackend
from repro.models import Model
from repro.serving import Request, ReqState, ServingEngine
from repro.serving.simulator import ServingSimulator, SimConfig, SimResult


_LLAMA_CACHE = {}


def _llama():
    # module-level cache rather than a fixture: the hypothesis-compat
    # @given wrapper cannot take pytest fixtures as arguments
    if "v" not in _LLAMA_CACHE:
        cfg = get_smoke_config("llama3-8b")
        m = Model(cfg)
        _LLAMA_CACHE["v"] = (cfg, m, m.init(jax.random.PRNGKey(0)))
    return _LLAMA_CACHE["v"]


@pytest.fixture(scope="module")
def llama():
    return _llama()


def mk_wl(cfg, rng, n=8, out_len=10, stagger=0.2, plo=8, phi=24):
    wl = []
    for i in range(n):
        plen = int(rng.integers(plo, phi))
        wl.append(Request(
            rid=i, arrival=i * stagger, prompt_len=plen, output_len=out_len,
            spec=QoESpec(ttft=1.0, tds=4.8),
            prompt_tokens=rng.integers(0, cfg.vocab_size, plen),
        ))
    return wl


def clone(wl):
    return [r.clone() for r in wl]


def mk_engine(m, params, lat, *, sched_name="andes", cap=8 * 64,
              num_slots=8, max_seq=64, mode="swap",
              sched_cfg=None):
    sched = make_scheduler(sched_name, cap, lat,
                           sched_cfg or SchedulerConfig())
    return ServingEngine(m, params, sched, lat, num_slots=num_slots,
                         max_seq=max_seq, capacity_tokens=cap,
                         preemption_mode=mode)


# ---------------------------------------------------------------------------
# the oracle: the pre-refactor monolithic run() loop, verbatim
# ---------------------------------------------------------------------------

def legacy_run(eng: ServingEngine, workload, max_iterations=100_000):
    """Transcription of ServingEngine.run() before the steppable refactor.
    Uses loop-local pending/live exactly as the seed code did; the private
    helpers (_prefill_request/_emit/_preempt/_swap_in/_tick) are shared
    with the refactored engine, so any drift in the step decomposition
    shows up as a diff against this."""
    pending = sorted(workload, key=lambda r: r.arrival)
    live = []

    def admit_arrivals():
        while pending and pending[0].arrival <= eng.now:
            r = pending.pop(0)
            r.fluid_idx = eng.fluid.add(r.arrival, r.spec)
            r.state = ReqState.WAITING
            live.append(r)
            eng.sched.on_request_arrival(r)

    while (pending or live) and eng.iterations < max_iterations:
        if not live and pending:
            eng.now = max(eng.now, pending[0].arrival)
        admit_arrivals()
        if not live:
            continue

        target = eng.sched.schedule(eng.now, live, eng.fluid)
        target_ids = {id(r) for r in target}

        for r in list(eng.slot_req.values()):
            if id(r) not in target_ids and r.state == ReqState.RUNNING:
                eng._preempt(r)
        for r in target:
            if r.state == ReqState.SWAPPED and eng.kv.can_allocate(r):
                eng._swap_in(r)
            elif r.state == ReqState.WAITING and eng.kv.can_allocate(r):
                r.state = ReqState.RUNNING
                r.prefilled = True
                eng._prefill_request(r)

        active = {s: r for s, r in eng.slot_req.items()
                  if r.state == ReqState.RUNNING}
        if active:
            lengths = np.zeros(eng.kv.num_slots, np.int32)
            tokens = np.zeros(eng.kv.num_slots, np.int32)
            for s, r in active.items():
                lengths[s] = r.context_len
                tokens[s] = r.output_tokens[-1] if r.output_tokens else 0
            eng.cache["length"] = jnp.asarray(lengths)
            logits, eng.cache = eng._decode(
                eng.params, jnp.asarray(tokens), eng.cache
            )
            total_ctx = int(lengths.sum())
            eng._tick(eng.lat.iter_latency(len(active), total_ctx),
                      "decode")
            nxt = np.asarray(jnp.argmax(logits, axis=-1))
            for s, r in list(active.items()):
                eng._emit(r, int(nxt[s]))
        else:
            eng._tick(eng.lat.hw.overhead, "overhead")

        eng.iterations += 1
        live = [r for r in live if r.is_live]
        admit_arrivals()

    return workload


def assert_bitforbit(out_a, out_b):
    """Token ids, emission timestamps, preemptions, and final QoE must be
    *identical* — not merely close."""
    assert len(out_a) == len(out_b)
    for a, b in zip(out_a, out_b):
        assert a.rid == b.rid
        assert a.output_tokens == b.output_tokens, a.rid
        assert a.emit_times == b.emit_times, a.rid        # exact floats
        assert a.preemptions == b.preemptions, a.rid
        assert a.generated == b.generated, a.rid
        assert a.final_qoe() == b.final_qoe(), a.rid
        assert (np.isnan(a.finish_time) and np.isnan(b.finish_time)) \
            or a.finish_time == b.finish_time, a.rid


# ---------------------------------------------------------------------------
# protocol conformance
# ---------------------------------------------------------------------------

def test_engine_satisfies_steppable_backend(llama):
    cfg, m, params = llama
    lat = LatencyModel(cfg, TPU_V5E)
    eng = mk_engine(m, params, lat)
    assert isinstance(eng, SteppableBackend)
    sim = ServingSimulator(make_scheduler("andes", 512, lat), lat,
                           SimConfig(kv_capacity_tokens=512))
    assert isinstance(sim, SteppableBackend)
    # the protocol members the cluster layer actually calls
    for member in ("submit", "step", "result", "has_work",
                   "pending", "live", "seen", "now", "sched", "fluid"):
        assert hasattr(eng, member), member
    assert isinstance(eng.result(), SimResult)


# ---------------------------------------------------------------------------
# stepped ≡ legacy, all drive styles
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sched_name", ["fcfs", "andes"])
def test_run_equals_legacy_uncontended(llama, sched_name):
    cfg, m, params = llama
    lat = LatencyModel(cfg, TPU_V5E)
    rng = np.random.default_rng(0)
    wl = mk_wl(cfg, rng)

    ref = mk_engine(m, params, lat, sched_name=sched_name)
    out_ref = legacy_run(ref, clone(wl), max_iterations=2000)

    new = mk_engine(m, params, lat, sched_name=sched_name)
    out_new = new.run(clone(wl), max_iterations=2000)

    assert_bitforbit(out_new, out_ref)
    assert new.now == ref.now
    assert new.iterations == ref.iterations
    assert new.preemptions == ref.preemptions


@pytest.mark.parametrize("mode", [
    "swap",
    pytest.param("recompute", marks=pytest.mark.slow),
])
def test_run_equals_legacy_under_contention(llama, mode):
    """Tight KV budget + 2 slots forces preemption/swap-in traffic; the
    stepped engine must replay the exact same event sequence."""
    cfg, m, params = llama
    lat = LatencyModel(cfg, TPU_V5E)
    rng = np.random.default_rng(1)
    wl = mk_wl(cfg, rng, n=8, out_len=15, stagger=0.01, plo=5, phi=20)
    kw = dict(sched_name="andes", cap=100, num_slots=2, mode=mode,
              sched_cfg=SchedulerConfig(delta_t=5.0))

    ref = mk_engine(m, params, lat, **kw)
    out_ref = legacy_run(ref, clone(wl), max_iterations=2000)
    assert ref.preemptions > 0, "test requires contention"

    new = mk_engine(m, params, lat, **kw)
    out_new = new.run(clone(wl), max_iterations=2000)

    assert_bitforbit(out_new, out_ref)
    assert new.preemptions == ref.preemptions
    assert new.kv.swap_bytes_total == ref.kv.swap_bytes_total


def test_manual_stepping_equals_run(llama):
    cfg, m, params = llama
    lat = LatencyModel(cfg, TPU_V5E)
    rng = np.random.default_rng(2)
    wl = mk_wl(cfg, rng)

    a = mk_engine(m, params, lat)
    out_a = a.run(clone(wl), max_iterations=2000)

    b = mk_engine(m, params, lat)
    wl_b = clone(wl)
    for r in wl_b:
        b.submit(r)
    while b.step():
        pass
    assert_bitforbit(wl_b, out_a)
    assert not b.has_work
    assert not b.step()                      # idempotent once drained


def test_incremental_submit_equals_upfront(llama):
    """Cluster-style drive: step to each arrival, submit, continue. The
    request is admitted at the same iteration boundary as the all-upfront
    run, so the timelines are identical (this is the invariant that makes
    a routed engine replica ≡ a bare engine). Like Replica.advance_to,
    the driver passes `until` so the engine's multi-step fast path — which
    the upfront run bounds by its visible pending queue — never fuses
    past an arrival this driver has not submitted yet."""
    cfg, m, params = llama
    lat = LatencyModel(cfg, TPU_V5E)
    rng = np.random.default_rng(3)
    wl = mk_wl(cfg, rng)

    a = mk_engine(m, params, lat)
    out_a = a.run(clone(wl), max_iterations=2000)

    b = mk_engine(m, params, lat)
    wl_b = clone(wl)
    for r in wl_b:
        # replica.advance_to(r.arrival): run iterations until the clock
        # reaches the arrival (may overshoot — iterations are indivisible)
        while b.has_work and b.now < r.arrival:
            if not b.step(until=r.arrival):
                break
        b.submit(r)
    while b.step():
        pass
    assert_bitforbit(wl_b, out_a)


def test_result_snapshot(llama):
    cfg, m, params = llama
    lat = LatencyModel(cfg, TPU_V5E)
    rng = np.random.default_rng(4)
    wl = mk_wl(cfg, rng, n=5, out_len=8)
    eng = mk_engine(m, params, lat)
    eng.run(clone(wl), max_iterations=1000)
    res = eng.result()
    assert res.makespan == eng.now
    assert res.total_tokens == sum(r.generated for r in res.requests)
    assert res.iterations == eng.iterations
    assert len(res.batch_sizes) == res.iterations
    assert res.preemptions == eng.preemptions
    assert len(res.requests) == 5
    assert res.avg_qoe() > 0.0


def test_reset_gives_fresh_engine(llama):
    cfg, m, params = llama
    lat = LatencyModel(cfg, TPU_V5E)
    rng = np.random.default_rng(5)
    wl = mk_wl(cfg, rng, n=4, out_len=6)

    eng = mk_engine(m, params, lat)
    first = eng.run(clone(wl), max_iterations=1000)
    eng.reset()
    assert eng.now == 0.0 and not eng.seen and not eng.has_work
    second = eng.run(clone(wl), max_iterations=1000)
    assert_bitforbit(second, first)
    # run() itself resets (same batch semantics as ServingSimulator.run),
    # so back-to-back runs need no manual reset
    third = eng.run(clone(wl), max_iterations=1000)
    assert_bitforbit(third, first)
    assert len(eng.result().requests) == len(wl)


def test_stuck_engine_halts_instead_of_spinning(llama):
    """A prompt larger than the KV capacity can never be scheduled; the
    steppable engine must detect the deadlock and stop returning True
    (the legacy loop spun on overhead ticks until max_iterations)."""
    cfg, m, params = llama
    lat = LatencyModel(cfg, TPU_V5E)
    big = Request(rid=0, arrival=0.0, prompt_len=50, output_len=4,
                  spec=QoESpec(ttft=1.0, tds=4.8),
                  prompt_tokens=np.zeros(50, np.int64))
    eng = mk_engine(m, params, lat, cap=20, num_slots=2, max_seq=64)
    eng.submit(big)
    steps = 0
    while eng.step():
        steps += 1
        assert steps < 50, "engine failed to detect deadlock"
    assert eng.stuck
    assert big.generated == 0
    # a feasible later submit clears the flag and serves normally
    ok = Request(rid=1, arrival=eng.now, prompt_len=5, output_len=4,
                 spec=QoESpec(ttft=1.0, tds=4.8),
                 prompt_tokens=np.zeros(5, np.int64))
    eng.submit(ok)
    assert not eng.stuck
    while eng.step():
        pass
    assert ok.generated >= ok.output_len


def test_pending_arrival_unsticks_idle_engine(llama):
    """An unschedulable request idles the batch, but a *pending* feasible
    arrival must still be admitted when the overhead ticks reach its
    arrival time — the deadlock guard may only halt when no admission,
    decode, preemption, or new arrival happened. The served request's
    timeline must match the legacy loop exactly."""
    cfg, m, params = llama
    lat = LatencyModel(cfg, TPU_V5E)

    def wl():
        return [
            Request(rid=0, arrival=0.0, prompt_len=50, output_len=4,
                    spec=QoESpec(ttft=1.0, tds=4.8),
                    prompt_tokens=np.zeros(50, np.int64)),
            Request(rid=1, arrival=0.05, prompt_len=5, output_len=4,
                    spec=QoESpec(ttft=1.0, tds=4.8),
                    prompt_tokens=np.arange(5, dtype=np.int64)),
        ]

    ref = mk_engine(m, params, lat, cap=20, num_slots=2)
    out_ref = legacy_run(ref, wl(), max_iterations=300)

    eng = mk_engine(m, params, lat, cap=20, num_slots=2)
    out = wl()
    for r in out:
        eng.submit(r)
    while eng.step():
        pass
    assert eng.stuck
    assert out[0].generated == 0
    assert out[1].generated >= out[1].output_len
    # the request that did get served matches the legacy loop exactly
    assert out[1].output_tokens == out_ref[1].output_tokens
    assert out[1].emit_times == out_ref[1].emit_times


# ---------------------------------------------------------------------------
# property test: randomized traces and QoE specs
# ---------------------------------------------------------------------------

@given(st.integers(0, 10_000), st.integers(3, 7),
       st.floats(0.3, 2.0), st.floats(2.0, 10.0))
@settings(max_examples=5, deadline=None)
@pytest.mark.slow
def test_property_stepped_equals_legacy(seed, n, ttft, tds):
    """Random arrival traces and QoE specs, tight capacity (so contention
    and preemption paths are exercised): stepped ≡ legacy bit-for-bit."""
    cfg, m, params = _llama()
    lat = LatencyModel(cfg, TPU_V5E)
    rng = np.random.default_rng(seed)
    wl = []
    t = 0.0
    for i in range(n):
        t += float(rng.exponential(0.15))
        plen = int(rng.integers(4, 16))
        wl.append(Request(
            rid=i, arrival=t, prompt_len=plen,
            output_len=int(rng.integers(4, 12)),
            spec=QoESpec(ttft=ttft, tds=tds),
            prompt_tokens=rng.integers(0, cfg.vocab_size, plen),
        ))
    kw = dict(sched_name="andes", cap=70, num_slots=2, max_seq=64,
              sched_cfg=SchedulerConfig(delta_t=5.0))

    ref = mk_engine(m, params, lat, **kw)
    out_ref = legacy_run(ref, clone(wl), max_iterations=1500)
    new = mk_engine(m, params, lat, **kw)
    out_new = new.run(clone(wl), max_iterations=1500)
    assert_bitforbit(out_new, out_ref)
