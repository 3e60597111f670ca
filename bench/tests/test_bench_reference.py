"""The plain references compute what the program computes: at the smoke
sizes in float32, with the benchmark's own weights, the program's
prefill and its decode through the KV cache and recurrent state give the
reference's logits."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import oracle
from bench.reference import mm_f32
from repro.configs import get_smoke_config
from repro.models import Model


def model_block(c):
    m = dict(hidden_size=c.d_model, num_hidden_layers=c.num_layers,
             num_attention_heads=c.num_heads, num_key_value_heads=c.num_kv_heads,
             intermediate_size=c.d_ff, vocab_size=c.vocab_size,
             rms_norm_eps=c.norm_eps, rope_theta=c.rope_theta,
             tie_word_embeddings=c.tie_embeddings)
    if c.ssm:
        m.update(mamba_d_state=c.ssm.d_state, mamba_d_conv=c.ssm.d_conv,
                 mamba_expand=c.ssm.expand, mamba_headdim=c.ssm.headdim,
                 attn_every=c.hybrid_attn_every)
    return m


@pytest.mark.parametrize("arch,kind", [("granite-3-2b", "dense"),
                                       ("zamba2-2.7b", "hybrid")])
def test_reference_matches_program_prefill_and_decode(arch, kind):
    c = get_smoke_config(arch)
    m = model_block(c)
    mod = oracle.reference_module(kind)
    model = Model(c, param_dtype=jnp.float32)
    params = jax.jit(lambda k: mod.init(m, k, jnp.float32))(
        jax.random.PRNGKey(3))
    want = model.abstract_params()
    assert jax.tree.structure(want) == jax.tree.structure(params)
    toks = jnp.asarray(np.random.default_rng(0).integers(0, c.vocab_size, 40),
                       jnp.int32)
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(mod.forward(params, m, toks, mm_f32))
        cache = model.init_cache(1, 64, dtype=jnp.float32)
        logits, cache = model.prefill(params, {"tokens": toks[None, :30]},
                                      cache)
        got = [np.asarray(logits[0])]
        for i in range(30, 39):
            logits, cache = model.decode_step(params, toks[i:i + 1], cache)
            got.append(np.asarray(logits[0]))
    scale = np.abs(ref).max()
    np.testing.assert_allclose(np.stack(got), ref[29:39], rtol=0,
                               atol=1e-4 * scale)


def test_scorer_gap_is_zero_for_its_own_choices():
    c = get_smoke_config("granite-3-2b")
    m = model_block(c)
    params = jax.jit(lambda k: oracle.reference_module("dense").init(
        m, k, jnp.float32))(jax.random.PRNGKey(0))
    scorer = oracle.make_scorer("dense", m, mm_f32)
    prompt = np.arange(1, 20, dtype=np.int32)
    seq, targets, lo, hi = oracle.pad_pair(prompt, [0])
    _, best = scorer(params, seq, targets)
    served = np.asarray(best)[lo:hi]
    gaps = oracle.served_gaps(scorer, params, [(prompt, served)], c.vocab_size)
    assert gaps[0].shape == (1,) and gaps[0][0] == 0.0
    worse = (served + 1) % c.vocab_size
    assert oracle.served_gaps(scorer, params, [(prompt, worse)],
                              c.vocab_size)[0][0] > 0
    assert oracle.out_of_vocab([(prompt, [c.vocab_size, -1, 0])],
                               c.vocab_size) == 2


def test_pad_pair_positions():
    seq, targets, lo, hi = oracle.pad_pair(np.array([5, 6, 7]), [8, 9])
    assert list(seq[:4]) == [5, 6, 7, 8] and len(seq) == oracle.MIN_BUCKET
    assert list(targets[lo:hi]) == [8, 9] and (lo, hi) == (2, 4)


def test_sample_takes_preempted_and_longest():
    finished = {i: 10 + i for i in range(50)}
    chosen = oracle.sample(finished, [3, 7, 999], np.random.default_rng(1))
    assert chosen[:2] in ([3, 7], [7, 3]) and 49 in chosen
    assert sum(finished[r] for r in chosen) >= min(
        oracle.WANT_TOKENS, sum(finished.values()))
    assert oracle.sample({}, [], np.random.default_rng(1)) == []


def test_sample_of_one_token_answers_takes_the_longest_prompt():
    """Where every answer is one token, the longest request is the one
    with the longest prompt, and the sample stops at MAX_REQUESTS."""
    n = oracle.MAX_REQUESTS + 40
    finished = {i: 1 for i in range(n)}
    prompts = {i: 10 + (i * 37) % 500 for i in range(n)}
    chosen = oracle.sample(finished, [], np.random.default_rng(2), prompts)
    assert chosen[0] == max(prompts, key=lambda r: (prompts[r], -r))
    assert len(chosen) == oracle.MAX_REQUESTS == len(set(chosen))


def test_wire_mismatches():
    assert oracle.wire_mismatches({1: [1, 2], 2: []}, {1: [1, 2, 3], 2: [4]}) == 0
    assert oracle.wire_mismatches({1: [1, 3]}, {1: [1, 2, 3]}) == 1
    assert oracle.wire_mismatches({5: [1]}, {}) == 1
    # sent as the window closed: no token read, no engine record
    assert oracle.wire_mismatches({6: []}, {}) == 0


def test_pad_pair_buckets_to_powers_of_two():
    seq, *_ = oracle.pad_pair(np.arange(300), np.arange(300))
    assert len(seq) == 1024
    seq, *_ = oracle.pad_pair(np.arange(100), np.arange(30))
    assert len(seq) == 256
