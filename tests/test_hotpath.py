"""Engine hot-path regression suite (PR 5).

Three optimization families (serving/engine.py HotpathConfig — bucketed
batched prefill, fused on-device sampling, multi-step decode) must be
lossless: the differential oracles in test_engine_steppable / test_sim_vs_
engine / test_speculative / test_api already run with them ON by default;
this file pins the *mechanisms* those suites rely on:

  * foundation: fused argmax decode ≡ decode_step + host argmax, and the
    multi-step scan ≡ sequential fused steps, bit-for-bit;
  * bucketed+batched prefill ≡ exact-length batch-1 prefill (argmax-exact,
    logits allclose) for every model family the engine serves;
  * prefill compile count bounded by the bucket grid — not by the number
    of distinct prompt lengths — over a mixed-length trace;
  * multi-step engines reproduce single-step engines bit-for-bit,
    including EOS truncation mid-block;
  * the arrival-queue cursor preserves stable equal-arrival order and
    late submits of past arrivals.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import LatencyModel, QoESpec, SchedulerConfig, TPU_V5E, make_scheduler
from repro.models import Model
from repro.models import cache as cache_lib
from repro.serving import HotpathConfig, Request, ServingEngine
from repro.serving.engine import BucketedPrefill
from repro.serving.simulator import ServingSimulator, SimConfig


_MODELS = {}


def _model(arch):
    if arch not in _MODELS:
        cfg = get_smoke_config(arch)
        m = Model(cfg)
        _MODELS[arch] = (cfg, m, m.init(jax.random.PRNGKey(0)))
    return _MODELS[arch]


def mk_wl(cfg, rng, n=8, out_len=12, stagger=0.2, plo=6, phi=40):
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


def mk_engine(arch="llama3-8b", *, hotpath=None, num_slots=8, max_seq=64,
              cap=None, eos_id=-1, sched_cfg=None):
    cfg, m, params = _model(arch)
    lat = LatencyModel(cfg, TPU_V5E)
    cap = cap if cap is not None else num_slots * max_seq
    sched = make_scheduler("andes", cap, lat, sched_cfg or SchedulerConfig())
    return ServingEngine(m, params, sched, lat, num_slots=num_slots,
                         max_seq=max_seq, capacity_tokens=cap,
                         eos_id=eos_id, hotpath=hotpath)


def assert_bitforbit(out_a, out_b):
    assert len(out_a) == len(out_b)
    for a, b in zip(out_a, out_b):
        assert a.rid == b.rid
        assert a.output_tokens == b.output_tokens, a.rid
        assert a.emit_times == b.emit_times, a.rid        # exact floats
        assert a.preemptions == b.preemptions, a.rid
        assert a.generated == b.generated, a.rid
        assert a.final_qoe() == b.final_qoe(), a.rid


# ---------------------------------------------------------------------------
# foundation: the fused device ops are bit-identical to their host splits
# ---------------------------------------------------------------------------

def test_fused_sampling_foundation():
    """decode_tokens (device argmax) and decode_multi (fused scan) must be
    bit-identical to decode_step + host argmax iterated — the identity
    every hot-path differential guarantee reduces to."""
    cfg, m, params = _model("llama3-8b")
    rng = np.random.default_rng(0)
    B, S = 4, 48
    pre = jax.jit(lambda p, t, l, c: m.prefill(
        p, {"tokens": t, "lengths": l}, c))
    toks = np.zeros((B, 32), np.int32)
    lens = np.array([9, 13, 21, 30], np.int32)
    for i, l in enumerate(lens):
        toks[i, :l] = rng.integers(0, cfg.vocab_size, l)
    cache0 = m.init_cache(B, S, dtype=jnp.float32)
    logits, cache0 = pre(params, jnp.asarray(toks), jnp.asarray(lens), cache0)
    t0 = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    dec = jax.jit(m.decode_step)
    dec_tok = jax.jit(m.decode_tokens)
    dec_multi = jax.jit(m.decode_multi, static_argnames=("j",))

    # sequential reference: host argmax feedback, 6 iterations
    c, tok, ref = dict(cache0), t0, []
    for _ in range(6):
        logits, c = dec(params, tok, c)
        tok = jnp.asarray(np.asarray(jnp.argmax(logits, axis=-1), np.int32))
        ref.append(np.asarray(tok))
    ref = np.stack(ref)

    # fused single-step, iterated
    c1, tok1, out1 = dict(cache0), t0, []
    for _ in range(6):
        tok1, c1 = dec_tok(params, tok1, c1)
        out1.append(np.asarray(tok1))
    assert (np.stack(out1) == ref).all()

    # fused multi-step scan, one dispatch
    out6, c6 = dec_multi(params, t0, dict(cache0), j=6)
    assert (np.asarray(out6) == ref).all()
    for a, b in zip(jax.tree.leaves(c6), jax.tree.leaves(c)):
        assert (np.asarray(a) == np.asarray(b)).all()


# ---------------------------------------------------------------------------
# bucketed + batched prefill ≡ exact-length batch-1 (per model family)
# ---------------------------------------------------------------------------

def _prefill_property(arch, *, batch_rows):
    cfg, m, params = _model(arch)
    rng = np.random.default_rng(1)
    lens = [5, 9, 17, 23]
    toks = [rng.integers(0, cfg.vocab_size, l).astype(np.int32)
            for l in lens]
    enc_seq = 8 if cfg.kind in ("encdec", "audio") else 0
    jit_pre = jax.jit(lambda p, b, c: m.prefill(p, b, c))

    def run_padded(group):
        """Padded-to-bucket-32, lengths-masked, jitted (the hot path)."""
        n = len(group)
        T = np.zeros((n, 32), np.int32)
        L = np.zeros((n,), np.int32)
        for i, t in enumerate(group):
            T[i, : len(t)] = t
            L[i] = len(t)
        batch = {"tokens": jnp.asarray(T), "lengths": jnp.asarray(L)}
        if enc_seq:
            batch["frames"] = jnp.zeros((n, enc_seq, cfg.d_model),
                                        jnp.float32)
        c = m.init_cache(n, 48, enc_seq=enc_seq, dtype=jnp.float32)
        logits, _ = jit_pre(params, batch, c)
        return np.asarray(logits)

    def run_exact(t):
        """Eager exact-length batch-1 (the pre-PR-5 engine path)."""
        batch = {"tokens": jnp.asarray(t)[None]}
        if enc_seq:
            batch["frames"] = jnp.zeros((1, enc_seq, cfg.d_model),
                                        jnp.float32)
        c = m.init_cache(1, 48, enc_seq=enc_seq, dtype=jnp.float32)
        logits, _ = m.prefill(params, batch, c)
        return np.asarray(logits[0])

    exact = [run_exact(t) for t in toks]
    if batch_rows:
        padded = run_padded(toks)
    else:   # MoE: capacity routing couples rows — the engine goes batch-1
        padded = np.stack([run_padded([t])[0] for t in toks])
    for i, l in enumerate(lens):
        np.testing.assert_allclose(padded[i], exact[i], atol=1e-5, rtol=1e-5)
        assert int(np.argmax(padded[i])) == int(np.argmax(exact[i])), l


@pytest.mark.parametrize("arch", ["llama3-8b", "falcon-mamba-7b",
                                  "zamba2-7b"])
def test_bucketed_prefill_matches_exact(arch):
    _prefill_property(arch, batch_rows=True)


@pytest.mark.slow
@pytest.mark.parametrize("arch", [
    "zamba2-2.7b",          # hybrid (mamba2 + shared attention)
    "seamless-m4t-medium",  # encdec (frames path)
    "pixtral-12b",          # vlm (dense prefill, no patches)
])
def test_bucketed_prefill_matches_exact_all_kinds(arch):
    _prefill_property(arch, batch_rows=True)


@pytest.mark.parametrize("arch,chunk", [("zamba2-7b", 256),
                                        ("granite-3-2b", 0)])
def test_prefill_reports_ssd_chunk(arch, chunk, monkeypatch):
    """Each `prefill.group` span and `hotpath_stats()["ssd_chunks"]` give
    the chunk length the flush's Mamba-2 layers ran with: the configured
    256 for one row of the 1024 bucket, 0 for a model without them."""
    from repro.serving import engine as engine_mod
    groups, span = [], engine_mod.span

    def record(name, **args):
        if name == "prefill.group":
            groups.append((args["rows"], args["bucket"], args["ssd_chunk"]))
        return span(name, **args)

    monkeypatch.setattr(engine_mod, "span", record)
    eng = mk_engine(arch, num_slots=1, max_seq=1024)
    wl = mk_wl(eng.model.cfg, np.random.default_rng(0), n=1, out_len=1,
               plo=600, phi=601)
    eng.run(wl, max_iterations=100)
    assert groups == [(1, 1024, chunk)]
    assert eng.hotpath_stats()["ssd_chunks"] == {(1, 1024): chunk}


def test_moe_prefill_stays_exact_length():
    """MoE is the one family bucketed prefill CANNOT serve exactly: expert
    capacity is proportional to the forward's total token count (padding
    included — moe.py), so a padded prompt sees a different capacity gate
    and can drop different tokens. The engine must fall back to the eager
    exact-length path — prefill compiles then track distinct lengths, and
    the differential oracles stay exact by construction."""
    cfg, m, params = _model("qwen2-moe-a2.7b")
    lat = LatencyModel(cfg, TPU_V5E)
    sched = make_scheduler("andes", 4 * 64, lat, SchedulerConfig())
    eng = ServingEngine(m, params, sched, lat, num_slots=3, max_seq=64,
                        capacity_tokens=4 * 64)
    assert eng.hotpath.prefill_buckets          # hot path is on...
    assert not eng._prefill_bucketable          # ...but MoE is excluded
    rng = np.random.default_rng(9)
    out = eng.run(mk_wl(cfg, rng, n=3, out_len=4, plo=6, phi=20),
                  max_iterations=500)
    assert all(r.generated >= r.output_len for r in out)
    # exact-length signatures, not buckets
    lens = {(1, r.prompt_len) for r in out}
    assert set(eng.hotpath_stats()["prefill_shapes"]) == lens


def test_batched_rows_bitwise_equal_batch1():
    """Row independence — the property that makes the engine's batched
    admission flush bit-identical to the legacy oracle's sequential
    prefills: a request's row in an N-row padded call equals its own
    1-row padded call EXACTLY (same bucket, so same per-row shapes)."""
    cfg, m, params = _model("llama3-8b")
    rng = np.random.default_rng(2)
    bp = BucketedPrefill(m, 64, jnp.float32, max_seq=64, bucket_min=16)
    toks = [rng.integers(0, cfg.vocab_size, l).astype(np.int32)
            for l in (7, 12, 15)]
    firstN, srcN = bp.run(params, toks)
    firstN = np.asarray(firstN)
    for i, t in enumerate(toks):
        f1, s1 = bp.run(params, [t])
        assert int(np.asarray(f1)[0]) == int(firstN[i])
        for a, b in zip(jax.tree.leaves(s1), jax.tree.leaves(srcN)):
            a, b = np.asarray(a), np.asarray(b)
            ax = 0 if a.ndim == 1 else 1
            assert (np.take(a, 0, ax) == np.take(b, i, ax)).all()


# ---------------------------------------------------------------------------
# compile-count regression: buckets, not distinct lengths
# ---------------------------------------------------------------------------

def test_prefill_compile_count_bounded_by_buckets():
    """50-request mixed-length trace: the optimized engine's prefill
    compile count must be bounded by the bucket grid (#length-buckets x
    #row-buckets) — NOT by the number of distinct prompt lengths, which is
    what the eager baseline pays. The engine's jit entry point doubles as
    the counting cache: its signature set is checked against jax's own
    compile-cache size so the bookkeeping cannot drift from reality."""
    cfg, m, params = _model("llama3-8b")
    rng = np.random.default_rng(3)
    wl = mk_wl(cfg, rng, n=50, out_len=6, stagger=0.05, plo=6, phi=60)
    n_lengths = len({r.prompt_len for r in wl})
    eng = mk_engine()
    eng.run(clone(wl), max_iterations=20_000)
    stats = eng.hotpath_stats()
    n_len_buckets = len(stats["prefill_bucket_grid"])
    n_row_buckets = len({s[0] for s in stats["prefill_shapes"]})
    bound = n_len_buckets * n_row_buckets
    assert stats["prefill_compiles"] <= bound, stats
    assert n_lengths > bound, (
        "trace too narrow to demonstrate the compile-count win")
    # the jit cache itself (when introspectable) must agree with the
    # signature bookkeeping the benchmark gates on
    cache_size = getattr(eng._prefill._jit, "_cache_size", None)
    if callable(cache_size):
        assert cache_size() <= stats["prefill_compiles"]


# ---------------------------------------------------------------------------
# multi-step decode ≡ single-step, bit-for-bit
# ---------------------------------------------------------------------------

def _run_pair(wl, hp_multi, hp_single, **kw):
    a = mk_engine(hotpath=hp_multi, **kw)
    out_a = a.run(clone(wl), max_iterations=20_000)
    b = mk_engine(hotpath=hp_single, **kw)
    out_b = b.run(clone(wl), max_iterations=20_000)
    assert_bitforbit(out_a, out_b)
    assert a.now == b.now
    assert a.iterations == b.iterations
    assert len(a.batch_sizes) == len(b.batch_sizes)
    assert a.sched.iteration == b.sched.iteration
    return a, b


def test_multi_step_equals_single_step():
    cfg, _, _ = _model("llama3-8b")
    rng = np.random.default_rng(4)
    wl = mk_wl(cfg, rng, n=8, out_len=24, stagger=0.15)
    multi, single = _run_pair(
        wl, HotpathConfig(multi_step=8), HotpathConfig(multi_step=1))
    assert multi.multi_step_blocks > 0, "fast path never engaged"
    assert multi.host_syncs < single.host_syncs


def test_multi_step_respects_pending_arrivals():
    """A late stiff arrival mid-drain: the block must stop at the same
    iteration boundary single-stepping admits it at."""
    cfg, _, _ = _model("llama3-8b")
    rng = np.random.default_rng(5)
    wl = mk_wl(cfg, rng, n=6, out_len=30, stagger=0.01)
    wl.append(Request(
        rid=99, arrival=0.35, prompt_len=10, output_len=12,
        spec=QoESpec(ttft=0.3, tds=8.0),
        prompt_tokens=rng.integers(0, cfg.vocab_size, 10)))
    _run_pair(wl, HotpathConfig(multi_step=8), HotpathConfig(multi_step=1))


def test_multi_step_with_eos_truncation():
    """EOS is unpredictable, so a multi-step block may overshoot it; the
    commit must stop exactly where single-stepping stops and the
    length-gate rollback must leave no trace in later tokens."""
    cfg, _, _ = _model("llama3-8b")
    rng = np.random.default_rng(6)
    wl = mk_wl(cfg, rng, n=6, out_len=24, stagger=0.1)
    # find a token that actually occurs mid-stream, then rerun with it as
    # EOS so blocks really do truncate
    probe = mk_engine(hotpath=HotpathConfig(multi_step=1))
    out = probe.run(clone(wl), max_iterations=20_000)
    mid_tokens = [t for r in out for t in r.output_tokens[2:-2]]
    assert mid_tokens, "probe trace too short"
    eos = int(np.bincount(np.asarray(mid_tokens)).argmax())
    multi, single = _run_pair(
        wl, HotpathConfig(multi_step=8), HotpathConfig(multi_step=1),
        eos_id=eos)
    assert any(r.output_tokens and r.output_tokens[-1] == eos
               and r.generated < r.output_len
               for r in single.seen), "EOS never fired — test is vacuous"
    assert multi.multi_step_blocks > 0, "fast path never engaged"


def test_multi_step_incremental_until_equals_upfront():
    """Replica.advance_to's `until` bound: stepping incrementally toward
    each arrival with step(until=arrival) must replay the all-upfront
    engine bit-for-bit even when multi-step blocks are active."""
    cfg, _, _ = _model("llama3-8b")
    rng = np.random.default_rng(7)
    wl = mk_wl(cfg, rng, n=8, out_len=20, stagger=0.12)

    a = mk_engine()
    out_a = a.run(clone(wl), max_iterations=20_000)

    b = mk_engine()
    wl_b = clone(wl)
    for r in wl_b:
        while b.has_work and b.now < r.arrival:
            if not b.step(until=r.arrival):
                break
        b.submit(r)
    while b.step():
        pass
    assert_bitforbit(wl_b, out_a)
    assert a.multi_step_blocks > 0


# ---------------------------------------------------------------------------
# arrival-queue cursor: stable order, late submits, protocol view
# ---------------------------------------------------------------------------

def test_arrival_queue_equal_arrival_stability():
    """Equal-arrival requests must be admitted in submit order (the
    bisect_right insert above the cursor ≡ the old insort semantics)."""
    lat = LatencyModel(get_smoke_config("llama3-8b"), TPU_V5E)
    sim = ServingSimulator(make_scheduler("fcfs", 4096, lat), lat,
                           SimConfig(kv_capacity_tokens=4096))
    for rid in (3, 1, 4, 1 + 4, 9, 2, 6):
        sim.submit(Request(rid=rid, arrival=1.0, prompt_len=8, output_len=2,
                           spec=QoESpec(ttft=1.0, tds=4.8)))
    sim._admit_arrivals(2.0)
    assert [r.rid for r in sim.live] == [3, 1, 4, 5, 9, 2, 6]
    assert sim.pending == []


def test_arrival_queue_late_submit_of_past_arrival():
    """A request submitted with an arrival earlier than already-admitted
    ones must still be admitted (the cursor clamps the insert position —
    it can never land inside the consumed prefix)."""
    lat = LatencyModel(get_smoke_config("llama3-8b"), TPU_V5E)
    sim = ServingSimulator(make_scheduler("fcfs", 4096, lat), lat,
                           SimConfig(kv_capacity_tokens=4096))
    for rid, arr in ((0, 0.0), (1, 0.5), (2, 1.0)):
        sim.submit(Request(rid=rid, arrival=arr, prompt_len=8, output_len=4,
                           spec=QoESpec(ttft=1.0, tds=4.8)))
    sim._admit_arrivals(2.0)          # consume everything
    assert len(sim.live) == 3
    sim.submit(Request(rid=9, arrival=0.25, prompt_len=8, output_len=4,
                       spec=QoESpec(ttft=1.0, tds=4.8)))
    assert [r.rid for r in sim.pending] == [9]
    sim._admit_arrivals(2.0)
    assert [r.rid for r in sim.live] == [0, 1, 2, 9]
    assert sim.has_work


def test_queue_cursor_drain_is_linear():
    """Admitting a deep queue must not re-shift the list per request: the
    compaction counter stays O(n) total (regression guard for the old
    pop(0) O(n²) drain). Checked behaviorally: a 5k-request drain through
    _admit_arrivals completes with the cursor consuming every entry."""
    lat = LatencyModel(get_smoke_config("llama3-8b"), TPU_V5E)
    sim = ServingSimulator(make_scheduler("fcfs", 1 << 22, lat), lat,
                           SimConfig(kv_capacity_tokens=1 << 22))
    n = 5000
    for i in range(n):
        sim.submit(Request(rid=i, arrival=i * 1e-4, prompt_len=4,
                           output_len=1, spec=QoESpec(ttft=1.0, tds=4.8)))
    sim._admit_arrivals(1.0)
    assert len(sim.live) == n
    assert not sim.pending
    assert sim._pending_pos == 0      # compacted


# ---------------------------------------------------------------------------
# pricing grid ≡ per-candidate scalar pricing
# ---------------------------------------------------------------------------

def test_predict_qoe_grid_rows_match_scalar():
    from repro.core.qoe import FluidQoE
    rng = np.random.default_rng(8)
    fl = FluidQoE()
    for i in range(6):
        fl.add(float(i) * 0.3, QoESpec(ttft=1.0, tds=4.8))
    fl.emit(np.arange(4), 2.0, 1)
    fl.emit(np.arange(2), 2.5, 3)
    rates = np.array([0.0, 1.3, 4.8, 7.7, 50.0])
    delay = rng.uniform(0, 2, 6)
    exp_len = rng.uniform(8, 64, 6)
    grid = fl.predict_qoe_grid(3.0, 50.0, rates, delay, exp_len)
    for i, r in enumerate(rates):
        row = fl.predict_qoe(3.0, 50.0, r, delay, exp_len)
        assert (grid[i] == row).all(), i
