"""Real serving-engine integration tests: continuous batching, preemption
round-trips, Andes-on-engine, and cross-family serving."""
import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import (
    LatencyModel,
    QoESpec,
    SchedulerConfig,
    TPU_V5E,
    make_scheduler,
)
from repro.models import Model
from repro.serving import HotpathConfig, Request, ServingEngine


def mk_workload(cfg, n, rng, out_len=12, stagger=0.05):
    wl = []
    for i in range(n):
        plen = int(rng.integers(5, 20))
        wl.append(Request(
            rid=i, arrival=i * stagger, prompt_len=plen, output_len=out_len,
            spec=QoESpec(ttft=1.0, tds=4.8),
            prompt_tokens=rng.integers(0, cfg.vocab_size, plen),
        ))
    return wl


def clone(wl):
    return [Request(rid=r.rid, arrival=r.arrival, prompt_len=r.prompt_len,
                    output_len=r.output_len, spec=r.spec,
                    prompt_tokens=r.prompt_tokens) for r in wl]


@pytest.fixture(scope="module")
def llama():
    cfg = get_smoke_config("llama3-8b")
    m = Model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    return cfg, m, params


@pytest.mark.parametrize("arch", [
    "llama3-8b", "falcon-mamba-7b", "zamba2-2.7b", "zamba2-7b",
    "qwen2-moe-a2.7b", "seamless-m4t-medium", "pixtral-12b",
])
@pytest.mark.slow
def test_engine_serves_all_families(arch):
    cfg = get_smoke_config(arch)
    m = Model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    lat = LatencyModel(cfg, TPU_V5E)
    rng = np.random.default_rng(0)
    wl = mk_workload(cfg, 5, rng, out_len=8)
    sched = make_scheduler("andes", 4 * 64, lat)
    eng = ServingEngine(m, params, sched, lat, num_slots=3, max_seq=64)
    out = eng.run(wl, max_iterations=500)
    assert all(r.generated >= r.output_len for r in out)
    assert all(len(r.emit_times) == r.generated for r in out)
    # emissions strictly ordered in time per request
    for r in out:
        assert all(b >= a for a, b in zip(r.emit_times, r.emit_times[1:]))


@pytest.mark.parametrize("mode", ["swap", "recompute"])
@pytest.mark.slow
def test_preemption_exactness(llama, mode):
    """Preempted-and-resumed requests must generate token-for-token the
    same output as an uncontended run (KV/state round-trip fidelity)."""
    cfg, m, params = llama
    lat = LatencyModel(cfg, TPU_V5E)
    rng = np.random.default_rng(1)
    wl = mk_workload(cfg, 8, rng, out_len=15, stagger=0.01)
    sched = make_scheduler("andes", 100, lat, SchedulerConfig(delta_t=5.0))
    eng = ServingEngine(m, params, sched, lat, num_slots=2, max_seq=64,
                        capacity_tokens=100, preemption_mode=mode)
    out = eng.run(wl, max_iterations=2000)
    assert eng.preemptions > 0, "test requires contention"

    ref_eng = ServingEngine(m, params, make_scheduler("fcfs", 10_000, lat),
                            lat, num_slots=8, max_seq=64)
    ref = ref_eng.run(clone(wl), max_iterations=2000)
    for a, b in zip(out, ref):
        assert a.output_tokens == b.output_tokens, a.rid


def test_engine_kv_accounting(llama):
    cfg, m, params = llama
    lat = LatencyModel(cfg, TPU_V5E)
    rng = np.random.default_rng(2)
    wl = mk_workload(cfg, 6, rng, out_len=10)
    sched = make_scheduler("fcfs", 10_000, lat)
    eng = ServingEngine(m, params, sched, lat, num_slots=4, max_seq=64)
    eng.run(wl, max_iterations=500)
    assert eng.kv.tokens_used == 0          # everything released
    assert len(eng.kv.free_slots) == 4
    assert not eng.kv.host_store


def test_engine_respects_capacity(llama):
    cfg, m, params = llama
    lat = LatencyModel(cfg, TPU_V5E)
    rng = np.random.default_rng(3)
    wl = mk_workload(cfg, 10, rng, out_len=10)
    cap = 80
    sched = make_scheduler("andes", cap, lat)
    eng = ServingEngine(m, params, sched, lat, num_slots=3, max_seq=64,
                        capacity_tokens=cap)
    # track peak usage via a wrapper
    peak = 0
    orig_grow = eng.kv.grow

    def grow(req, n=1):
        nonlocal peak
        orig_grow(req, n)
        peak = max(peak, eng.kv.tokens_used)

    eng.kv.grow = grow
    out = eng.run(wl, max_iterations=2000)
    assert all(r.generated >= r.output_len for r in out)
    assert peak <= cap + 1


def _reference_model(arch):
    """A smoke config, its plain f32 reference (bench/reference) and the
    reference's model block, whose weights the program serves."""
    import dataclasses

    from bench import oracle

    cfg = get_smoke_config(arch)
    if arch == "granite-3-2b":
        kind, cfg = "dense", dataclasses.replace(cfg, num_layers=4)
        m = dict(hidden_size=cfg.d_model, num_hidden_layers=cfg.num_layers,
                 num_attention_heads=cfg.num_heads,
                 num_key_value_heads=cfg.num_kv_heads,
                 intermediate_size=cfg.d_ff, vocab_size=cfg.vocab_size,
                 rms_norm_eps=cfg.norm_eps, rope_theta=cfg.rope_theta,
                 tie_word_embeddings=cfg.tie_embeddings)
    else:
        kind = "zamba2"
        m = oracle.reference_module(kind).registry_view(cfg)
    return cfg, kind, m


@pytest.mark.parametrize("hotpath", ["default", "host_argmax"])
@pytest.mark.parametrize("arch", ["granite-3-2b", "zamba2-7b"])
def test_served_stream_matches_reference(arch, hotpath):
    """The engine's served stream — the prefill's token, then 9 decode
    steps through `ServingEngine` — against the plain f32 reference
    (bench/reference) over the prompt and the served tokens. With the
    default hot path (on-device argmax, fused multi-step) every served
    token is the reference's first choice; with host argmax the logits of
    every decode step are the reference's at that position. Tolerance:
    with f32 weights and cache the two differ by reduction order, ~1e-6
    of the logit scale, so 1e-4 of it; a decode step whose cache length is
    one off (it attends a position never written) is 4e-4 or more off."""
    from bench import oracle
    from bench.reference import mm_f32

    cfg, kind, m = _reference_model(arch)
    mod = oracle.reference_module(kind)
    params = jax.jit(lambda k: mod.init(m, k, jax.numpy.float32))(
        jax.random.PRNGKey(5))
    model = Model(cfg)
    lat = LatencyModel(cfg, TPU_V5E)
    rng = np.random.default_rng(4)
    wl = mk_workload(cfg, 3, rng, out_len=10, stagger=0.01)
    eng = ServingEngine(
        model, params, make_scheduler("fcfs", 10_000, lat), lat,
        num_slots=3, max_seq=64,
        hotpath=HotpathConfig() if hotpath == "default"
        else HotpathConfig(fused_sampling=False, multi_step=1))
    served = {}                       # rid -> logits of each decode step
    if hotpath == "host_argmax":
        decode, emit, last = eng._decode, eng._emit, {}

        def record_decode(*args):
            last["logits"], cache = decode(*args)
            return last["logits"], cache

        def record_emit(r, tok):
            served.setdefault(r.rid, []).append(
                np.asarray(last["logits"][r.engine_slot]))
            emit(r, tok)

        eng._decode, eng._emit = record_decode, record_emit
    out = eng.run(wl, max_iterations=500)
    score = oracle.make_scorer(kind, m, mm_f32)
    with jax.default_matmul_precision("highest"):
        for r in out:
            assert r.generated == 10
            seq, targets, lo, hi = oracle.pad_pair(r.prompt_tokens,
                                                   r.output_tokens)
            ref = np.asarray(mod.forward(params, m, jax.numpy.asarray(seq),
                                         mm_f32))
            tol = 1e-4 * np.abs(ref).max()
            if hotpath == "host_argmax":
                got = np.stack(served[r.rid])
                assert len(got) == 9
                np.testing.assert_allclose(got, ref[lo + 1:hi], rtol=0,
                                           atol=tol)
            else:
                gap, _ = score(params, seq, targets)
                assert np.asarray(gap)[lo:hi].max() <= tol, r.rid
