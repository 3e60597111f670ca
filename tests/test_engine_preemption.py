"""Unit tests for the engine's preemption paths: `_preempt` (swap-to-host
vs recompute) and `_swap_in`.

The integration suite (test_engine.py::test_preemption_exactness) already
proves preempted requests finish with the right tokens end-to-end; these
tests pin the mechanism itself — the KV/state slice that comes back from
host RAM is *bit-identical* to what was parked, the slot/token accounting
balances on both sides, and the recompute path genuinely drops state.

The speculative tests extend the same pins to the draft cache: a preempted
speculative request parks *two* slices (target + draft, same rid, same
slot decision), both must round-trip host RAM bit-identically — including
the stale rejected-proposal entries beyond the committed frontier, which
the length gate makes inert — and the resumed request must continue the
exact token sequence.
"""
import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import (
    LatencyModel,
    QoESpec,
    SpeculativeLatencyModel,
    TPU_V5E,
    make_scheduler,
)
from repro.models import Model
from repro.serving import Request, ReqState, ServingEngine
from repro.serving.engine import _read_slot


@pytest.fixture(scope="module")
def llama():
    cfg = get_smoke_config("llama3-8b")
    m = Model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    return cfg, m, params


def mk_req(cfg, rng, rid=0, out_len=10, plen=12):
    return Request(
        rid=rid, arrival=0.0, prompt_len=plen, output_len=out_len,
        spec=QoESpec(ttft=1.0, tds=4.8),
        prompt_tokens=rng.integers(0, cfg.vocab_size, plen),
    )


def mk_engine(m, params, lat, mode="swap"):
    sched = make_scheduler("fcfs", 10_000, lat)
    return ServingEngine(m, params, sched, lat, num_slots=4, max_seq=64,
                         preemption_mode=mode)


def tree_equal(a, b):
    eq = jax.tree.map(
        lambda x, y: bool(np.array_equal(np.asarray(x), np.asarray(y))), a, b
    )
    return all(jax.tree.leaves(eq))


def start_running(eng, r, steps=2):
    """Submit and step until the request is mid-decode."""
    eng.submit(r)
    for _ in range(steps):
        assert eng.step()
    assert r.state == ReqState.RUNNING and r.generated > 0
    return r.engine_slot


def test_swap_roundtrip_preserves_kv_exactly(llama):
    cfg, m, params = llama
    lat = LatencyModel(cfg, TPU_V5E)
    rng = np.random.default_rng(0)
    eng = mk_engine(m, params, lat, mode="swap")
    r = mk_req(cfg, rng)
    slot = start_running(eng, r)

    before = jax.device_get(_read_slot(eng.cache, slot))
    used_before = eng.kv.tokens_used

    eng._preempt(r)
    assert r.state == ReqState.SWAPPED
    assert r.preemptions == 1 and eng.preemptions == 1
    assert slot in eng.kv.free_slots and slot not in eng.slot_req
    assert eng.kv.tokens_used == used_before - r.context_len
    # the parked host slice is exactly the device slice that was evicted
    parked = eng.kv.host_store[r.rid]
    assert tree_equal(parked, before)
    assert eng.kv.swap_bytes_total > 0

    eng._swap_in(r)
    assert r.state == ReqState.RUNNING
    assert r.rid not in eng.kv.host_store
    assert eng.kv.tokens_used == used_before
    new_slot = r.engine_slot
    assert eng.slot_req[new_slot] is r
    # the restored device slice is bit-identical to the parked one
    after = jax.device_get(_read_slot(eng.cache, new_slot))
    assert tree_equal(after, before)


def test_swapped_request_finishes_like_uncontended(llama):
    """After a forced swap round-trip mid-decode, the remaining tokens
    must be exactly what an undisturbed engine produces."""
    cfg, m, params = llama
    lat = LatencyModel(cfg, TPU_V5E)
    rng = np.random.default_rng(1)

    ref_eng = mk_engine(m, params, lat)
    ref = mk_req(cfg, rng)
    ref_eng.run([ref], max_iterations=100)

    eng = mk_engine(m, params, lat, mode="swap")
    r = Request(rid=ref.rid, arrival=0.0, prompt_len=ref.prompt_len,
                output_len=ref.output_len, spec=ref.spec,
                prompt_tokens=ref.prompt_tokens)
    start_running(eng, r)
    eng._preempt(r)
    while eng.step():            # scheduler swaps it back in and finishes
        pass
    assert r.generated >= r.output_len
    assert r.output_tokens == ref.output_tokens


def test_recompute_preemption_drops_state(llama):
    cfg, m, params = llama
    lat = LatencyModel(cfg, TPU_V5E)
    rng = np.random.default_rng(2)
    eng = mk_engine(m, params, lat, mode="recompute")
    r = mk_req(cfg, rng)
    slot = start_running(eng, r)
    gen_before = r.generated
    used_before = eng.kv.tokens_used

    eng._preempt(r)
    assert r.state == ReqState.WAITING
    assert not r.prefilled                   # must re-prefill from scratch
    assert r.rid not in eng.kv.host_store    # nothing parked
    assert eng.kv.swap_bytes_total == 0
    assert slot in eng.kv.free_slots and slot not in eng.slot_req
    assert eng.kv.tokens_used == used_before - r.context_len
    # generated prefix is kept on the request (recompute replays it)
    assert r.generated == gen_before and len(r.output_tokens) == gen_before


def test_recompute_resumes_token_exact(llama):
    cfg, m, params = llama
    lat = LatencyModel(cfg, TPU_V5E)
    rng = np.random.default_rng(3)

    ref_eng = mk_engine(m, params, lat)
    ref = mk_req(cfg, rng)
    ref_eng.run([ref], max_iterations=100)

    eng = mk_engine(m, params, lat, mode="recompute")
    r = Request(rid=ref.rid, arrival=0.0, prompt_len=ref.prompt_len,
                output_len=ref.output_len, spec=ref.spec,
                prompt_tokens=ref.prompt_tokens)
    start_running(eng, r, steps=3)
    eng._preempt(r)
    while eng.step():            # re-prefills prompt + generated prefix
        pass
    assert r.generated >= r.output_len
    assert r.output_tokens == ref.output_tokens
    assert eng.kv.tokens_used == 0           # everything released


def _decode_logits(eng):
    """rid -> the logits row of each of its decode steps (the engine's
    host-argmax path)."""
    rows, decode, emit, last = {}, eng._decode, eng._emit, {}

    def record_decode(*args):
        last["logits"], cache = decode(*args)
        return last["logits"], cache

    def record_emit(r, tok):
        if "logits" in last:
            rows.setdefault(r.rid, []).append(
                np.asarray(last.pop("logits")[r.engine_slot]))
        emit(r, tok)

    eng._decode, eng._emit = record_decode, record_emit
    return rows


def test_recompute_resumes_logit_exact_hybrid():
    """A recompute resume of the Zamba2 hybrid re-prefills the prompt and
    every generated token but the last, which the next decode step
    consumes: its decode logits are the uncontended run's. The recurrent
    state, unlike K/V, would count a token prefilled and decoded again
    twice. Tolerance 1e-4 of the logit scale: prefill and decode round
    differently in f32 (~1e-6)."""
    from repro.serving import HotpathConfig

    cfg = get_smoke_config("zamba2-7b")
    m = Model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    lat = LatencyModel(cfg, TPU_V5E)
    host = HotpathConfig(fused_sampling=False, multi_step=1)
    rng = np.random.default_rng(3)
    ref = mk_req(cfg, rng)
    ref_eng = ServingEngine(m, params, make_scheduler("fcfs", 10_000, lat),
                            lat, num_slots=4, max_seq=64, hotpath=host)
    want = _decode_logits(ref_eng)
    ref_eng.run([ref], max_iterations=100)

    eng = ServingEngine(m, params, make_scheduler("fcfs", 10_000, lat), lat,
                        num_slots=4, max_seq=64, preemption_mode="recompute",
                        hotpath=host)
    got = _decode_logits(eng)
    r = Request(rid=ref.rid, arrival=0.0, prompt_len=ref.prompt_len,
                output_len=ref.output_len, spec=ref.spec,
                prompt_tokens=ref.prompt_tokens)
    start_running(eng, r, steps=3)
    eng._preempt(r)
    while eng.step():
        pass
    assert r.output_tokens == ref.output_tokens
    assert eng.kv.tokens_used == 0
    a, b = np.stack(got[r.rid]), np.stack(want[ref.rid])
    np.testing.assert_allclose(a, b, rtol=0, atol=1e-4 * np.abs(b).max())


# ---------------------------------------------------------------------------
# Preemption under speculation: both caches round-trip, mid-proposal
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def spec_setup(llama):
    """Target + a perturbed-params draft (partial, context-dependent
    acceptance — so preemption happens with rejected-proposal junk parked
    beyond the committed frontier, the 'mid-proposal' state)."""
    cfg, m, params = llama
    draft_params = jax.tree.map(
        lambda a: a + 1e-3 * jax.random.normal(
            jax.random.PRNGKey(9), a.shape, a.dtype), params
    )
    return cfg, m, params, m, draft_params


def mk_spec_engine(spec_setup, mode="swap", k=2):
    cfg, m, params, dm, dparams = spec_setup
    lat = SpeculativeLatencyModel(cfg, TPU_V5E, dm.cfg, k=k)
    sched = make_scheduler("fcfs", 10_000, lat)
    return ServingEngine(m, params, sched, lat, num_slots=4, max_seq=64,
                         preemption_mode=mode,
                         draft_model=dm, draft_params=dparams, spec_k=k)


def test_spec_swap_roundtrip_preserves_both_kv(spec_setup):
    """Swap out a speculative request mid-proposal: the parked target AND
    draft slices are bit-identical to what was on device — including stale
    rejected-draft entries past the committed length — and both come back
    bit-identical on swap-in."""
    cfg = spec_setup[0]
    rng = np.random.default_rng(10)
    eng = mk_spec_engine(spec_setup, mode="swap")
    r = mk_req(cfg, rng, out_len=20, plen=12)
    slot = start_running(eng, r)
    assert eng.spec_steps > 0            # verify iterations actually ran

    before_t = jax.device_get(_read_slot(eng.cache, slot))
    before_d = eng.draft.park(slot)
    used_before = eng.kv.tokens_used

    eng._preempt(r)
    assert r.state == ReqState.SWAPPED
    assert eng.kv.tokens_used == used_before - r.context_len
    assert tree_equal(eng.kv.host_store[r.rid], before_t)
    assert tree_equal(eng.kv.draft_store[r.rid], before_d)

    eng._swap_in(r)
    assert r.rid not in eng.kv.host_store
    assert r.rid not in eng.kv.draft_store
    assert eng.kv.tokens_used == used_before
    new_slot = r.engine_slot
    assert tree_equal(jax.device_get(_read_slot(eng.cache, new_slot)),
                      before_t)
    assert tree_equal(eng.draft.park(new_slot), before_d)


def test_spec_swapped_resumes_token_exact(spec_setup):
    """After a forced swap round-trip mid-proposal, the speculative engine
    finishes with exactly the tokens an undisturbed baseline produces."""
    cfg, m, params, _, _ = spec_setup
    rng = np.random.default_rng(11)

    ref = mk_req(cfg, rng, out_len=18, plen=12)
    lat = LatencyModel(cfg, TPU_V5E)
    ref_eng = ServingEngine(m, params, make_scheduler("fcfs", 10_000, lat),
                            lat, num_slots=4, max_seq=64)
    ref_eng.run([ref], max_iterations=100)

    eng = mk_spec_engine(spec_setup, mode="swap")
    r = Request(rid=ref.rid, arrival=0.0, prompt_len=ref.prompt_len,
                output_len=ref.output_len, spec=ref.spec,
                prompt_tokens=ref.prompt_tokens)
    start_running(eng, r)
    eng._preempt(r)
    while eng.step():                    # swap back in and finish
        pass
    assert r.generated >= r.output_len
    assert r.output_tokens == ref.output_tokens


def test_spec_recompute_matches_nonspec_recompute(spec_setup):
    """Recompute-mode differential: re-prefill rebuilds the cache in
    prefill layout, whose logits may legitimately flip near-tie argmaxes
    vs the stepwise layout (a pre-existing engine property — see
    test_recompute_resumes_token_exact, which passes only because its
    trace is argmax-robust). The invariant speculation must preserve is
    therefore *equivalence with the non-speculative engine preempted at
    the same point*: same committed prefix dropped and re-prefilled, same
    continuation."""
    cfg, m, params, _, _ = spec_setup
    rng = np.random.default_rng(12)
    proto = mk_req(cfg, rng, out_len=18, plen=12)
    lat = LatencyModel(cfg, TPU_V5E)

    # speculative engine: run to mid-stream, force recompute preemption
    eng = mk_spec_engine(spec_setup, mode="recompute")
    r_spec = Request(rid=proto.rid, arrival=0.0, prompt_len=proto.prompt_len,
                     output_len=proto.output_len, spec=proto.spec,
                     prompt_tokens=proto.prompt_tokens)
    eng.submit(r_spec)
    while r_spec.generated < 6:
        assert eng.step()
    cut = r_spec.generated               # bursts may overshoot 6
    eng._preempt(r_spec)
    assert not r_spec.prefilled and r_spec.rid not in eng.kv.draft_store
    while eng.step():
        pass

    # non-spec engine preempted at the *same* generated count
    ref_eng = ServingEngine(m, params, make_scheduler("fcfs", 10_000, lat),
                            lat, num_slots=4, max_seq=64,
                            preemption_mode="recompute")
    r_ref = Request(rid=proto.rid, arrival=0.0, prompt_len=proto.prompt_len,
                    output_len=proto.output_len, spec=proto.spec,
                    prompt_tokens=proto.prompt_tokens)
    ref_eng.submit(r_ref)
    while r_ref.generated < cut:
        assert ref_eng.step()
    assert r_ref.generated == cut        # 1 token/step: lands exactly
    assert r_ref.output_tokens == r_spec.output_tokens[:cut]
    ref_eng._preempt(r_ref)
    while ref_eng.step():
        pass

    assert r_spec.generated >= r_spec.output_len
    assert r_spec.output_tokens == r_ref.output_tokens


def test_double_swap_roundtrip(llama):
    """Two park/restore cycles in a row must still be exact (regression
    guard for slot-reuse bugs: the second allocate may land on a
    different slot than the first)."""
    cfg, m, params = llama
    lat = LatencyModel(cfg, TPU_V5E)
    rng = np.random.default_rng(4)
    eng = mk_engine(m, params, lat, mode="swap")
    r = mk_req(cfg, rng, out_len=12)
    start_running(eng, r)

    for _ in range(2):
        slot = r.engine_slot
        before = jax.device_get(_read_slot(eng.cache, slot))
        eng._preempt(r)
        eng._swap_in(r)
        after = jax.device_get(_read_slot(eng.cache, r.engine_slot))
        assert tree_equal(after, before)
        assert eng.step()        # decode one more token between cycles
    while eng.step():
        pass
    assert r.generated >= r.output_len
