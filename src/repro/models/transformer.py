"""Architecture assembly: init + forward/prefill/decode for every family.

Layers are *stacked* (leading axis = layer) and traversed with `lax.scan`,
MaxText-style, so the 126-layer Llama-3-405B lowers to a compact HLO while
the per-layer math stays identical to an unrolled loop. Hybrids come in
two wirings. A config with ``hybrid_layer_ids`` is Zamba2 as published
(``_zamba2_stack``): every layer is a Mamba-2 layer, and at each hybrid
id one of ``num_mem_blocks`` shared attention+MLP blocks, taken in turn,
reads concat(hidden, embedding); its output, through that application's
linear, joins the Mamba layer's input. The gaps between hybrid ids are
uneven, so the plain layers between them run as loops whose bounds are
data. The older wiring (``hybrid_attn_every``, zamba2-2.7b) scans over
"rounds": (attn_every − 1) Mamba-2 layers followed by one
*weight-shared* residual attention+MLP block.

All functions are pure; ``impl`` picks the attention/scan implementation
("ref" XLA for dry-run/CPU, "pallas" for TPU kernels, "chunked" for XLA
scan forms).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.models.layers import (
    embed_apply,
    init_embed,
    init_mlp,
    mlp_apply,
    normal,
    rms_norm,
    unembed,
)

# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def init_params(rng, cfg: ModelConfig, dtype=jnp.float32):
    ks = jax.random.split(rng, 8)
    params = {
        "embed": init_embed(ks[0], cfg.vocab_size, cfg.d_model, dtype),
        "final_norm": {"scale": jnp.ones((cfg.d_model,), dtype)},
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = {
            "kernel": normal(ks[1], (cfg.d_model, cfg.vocab_size), dtype=dtype)
        }

    def stack(init_fn, n, rng_):
        return jax.vmap(lambda r: init_fn(r))(jax.random.split(rng_, n))

    L, d = cfg.num_layers, cfg.d_model

    if cfg.kind in ("dense", "vlm", "moe"):
        blocks = {
            "attn_norm_scale": jnp.ones((L, d), dtype),
            "attn": stack(lambda r: attn.init_attn(r, cfg, dtype), L, ks[2]),
            "mlp_norm_scale": jnp.ones((L, d), dtype),
        }
        if cfg.kind == "moe":
            blocks["moe"] = stack(lambda r: moe_lib.init_moe(r, cfg, dtype), L, ks[3])
        else:
            blocks["mlp"] = stack(
                lambda r: init_mlp(r, d, cfg.d_ff, cfg.gated_mlp, dtype), L, ks[3]
            )
        params["blocks"] = blocks
        if cfg.kind == "vlm":
            params["vision_proj"] = {"kernel": normal(ks[4], (d, d), dtype=dtype)}

    elif cfg.kind == "ssm":
        params["blocks"] = {
            "norm_scale": jnp.ones((L, d), dtype),
            "mamba": stack(lambda r: ssm_lib.init_mamba1(r, cfg, dtype), L, ks[2]),
        }

    elif cfg.kind == "hybrid" and cfg.hybrid_layer_ids:
        M, A, r = cfg.num_mem_blocks, len(cfg.hybrid_layer_ids), cfg.adapter_rank
        params["layers"] = {
            "norm_scale": jnp.ones((L, d), dtype),
            "mamba": stack(lambda r_: ssm_lib.init_mamba2(r_, cfg, dtype), L, ks[2]),
        }
        params["shared"] = {
            "attn_norm_scale": jnp.ones((M, cfg.d_attn_in), dtype),
            "attn": stack(lambda r_: attn.init_attn(r_, cfg, dtype), M, ks[3]),
            "mlp_norm_scale": jnp.ones((M, d), dtype),
            "mlp": stack(
                lambda r_: init_mlp(r_, d, cfg.d_ff, True, dtype), M, ks[4]
            ),
        }
        ka, kb, kl = jax.random.split(ks[5], 3)
        params["hybrid"] = {
            "adapter_a": normal(ka, (A, d, r), dtype=dtype),
            "adapter_b": normal(kb, (A, r, 2 * cfg.d_ff), dtype=dtype),
            "linear": normal(kl, (A, d, d), dtype=dtype),
        }

    elif cfg.kind == "hybrid":
        every = cfg.hybrid_attn_every
        assert L % every == 0, (L, every)
        rounds, per_round = L // every, every - 1

        def round_mamba(r):
            return jax.vmap(lambda rr: ssm_lib.init_mamba2(rr, cfg, dtype))(
                jax.random.split(r, per_round)
            )

        params["rounds"] = {
            "norm_scale": jnp.ones((rounds, per_round, d), dtype),
            "mamba": stack(round_mamba, rounds, ks[2]),
        }
        params["shared"] = {
            "attn_norm_scale": jnp.ones((d,), dtype),
            "attn": attn.init_attn(ks[3], cfg, dtype),
            "mlp_norm_scale": jnp.ones((d,), dtype),
            "mlp": init_mlp(ks[4], d, cfg.d_ff, cfg.gated_mlp, dtype),
        }

    elif cfg.kind in ("encdec", "audio"):
        Le = cfg.num_encoder_layers
        params["enc_blocks"] = {
            "attn_norm_scale": jnp.ones((Le, d), dtype),
            "attn": stack(lambda r: attn.init_attn(r, cfg, dtype), Le, ks[2]),
            "mlp_norm_scale": jnp.ones((Le, d), dtype),
            "mlp": stack(
                lambda r: init_mlp(r, d, cfg.d_ff, cfg.gated_mlp, dtype), Le, ks[3]
            ),
        }
        params["enc_norm"] = {"scale": jnp.ones((d,), dtype)}
        params["dec_blocks"] = {
            "self_norm_scale": jnp.ones((L, d), dtype),
            "self_attn": stack(lambda r: attn.init_attn(r, cfg, dtype), L, ks[4]),
            "cross_norm_scale": jnp.ones((L, d), dtype),
            "cross_attn": stack(lambda r: attn.init_attn(r, cfg, dtype), L, ks[5]),
            "mlp_norm_scale": jnp.ones((L, d), dtype),
            "mlp": stack(
                lambda r: init_mlp(r, d, cfg.d_ff, cfg.gated_mlp, dtype), L, ks[6]
            ),
        }
    else:
        raise ValueError(cfg.kind)
    return params


# ---------------------------------------------------------------------------
# Decoder-only (dense / vlm / moe / ssm / hybrid) full-sequence forward
# ---------------------------------------------------------------------------

def _embed_inputs(params, cfg: ModelConfig, batch):
    """Token (+ modality) embedding. Returns h (B, S, d)."""
    h = embed_apply(params["embed"], batch["tokens"])
    if cfg.kind == "vlm" and "patch_embeds" in batch:
        vis = batch["patch_embeds"] @ params["vision_proj"]["kernel"]
        h = jnp.concatenate([vis.astype(h.dtype), h], axis=1)
    return h


def _maybe_remat(fn, remat: bool):
    """Per-layer rematerialization: inside the layer scan, save only the
    residual-stream carry; recompute everything else on the backward pass.
    This is the policy that lets train_4k on the big archs lower with sane
    per-device activation memory (EXPERIMENTS.md §Dry-run)."""
    if not remat:
        return fn
    return jax.checkpoint(fn, policy=jax.checkpoint_policies.nothing_saveable)


def forward(
    params,
    cfg: ModelConfig,
    batch,
    *,
    impl: str = "ref",
    scan_impl: str = "chunked",
    window: Optional[int] = None,
    collect_cache: bool = False,
    lengths=None,
    remat: bool = False,
    return_hidden: bool = False,
    kv_repeat: int = 1,
    moe_seq_chunk: int = 0,
    moe_ep_mesh=None,
):
    """Full-sequence forward for decoder-only archs.

    Returns (logits, aux_loss) or, with collect_cache, (logits, aux, cache_kv)
    where cache_kv holds per-application (k, v [, ssm states]). With
    return_hidden, logits are NOT computed: returns (h, aux) so the caller
    can do chunked cross-entropy (Model.loss).
    """
    if cfg.kind in ("encdec", "audio"):
        return _forward_encdec(
            params, cfg, batch, impl=impl, collect_cache=collect_cache,
            lengths=lengths, remat=remat, return_hidden=return_hidden,
        )

    h = _embed_inputs(params, cfg, batch)
    b, s, d = h.shape
    valid = None
    if lengths is not None:
        valid = jnp.arange(s)[None] < lengths[:, None]

    if cfg.kind in ("dense", "vlm", "moe"):
        def block(h, bp):
            x = rms_norm(h, bp["attn_norm_scale"], cfg.norm_eps)
            if collect_cache:
                a, k, v = attn.attn_prefill(
                    bp["attn"], x, cfg, window=window, lengths=lengths,
                    impl=impl, kv_repeat=kv_repeat,
                )
            else:
                a = attn.attn_train(
                    bp["attn"], x, cfg, window=window, lengths=lengths, impl=impl
                )
                k = v = jnp.zeros((), h.dtype)
            h = h + a
            x = rms_norm(h, bp["mlp_norm_scale"], cfg.norm_eps)
            if cfg.kind == "moe":
                if moe_ep_mesh is not None:
                    from repro.distributed.moe_ep import moe_apply_ep
                    y, aux = moe_apply_ep(
                        bp["moe"], x, cfg, moe_ep_mesh, valid=valid
                    )
                elif moe_seq_chunk:
                    y, aux = moe_lib.moe_apply_chunked(
                        bp["moe"], x, cfg, valid=valid, seq_chunk=moe_seq_chunk
                    )
                else:
                    y, aux = moe_lib.moe_apply(bp["moe"], x, cfg, valid=valid)
            else:
                y, aux = mlp_apply(bp["mlp"], x), jnp.zeros((), jnp.float32)
            return h + y, (aux, k, v)

        h, (auxs, ks, vs) = jax.lax.scan(
            _maybe_remat(block, remat), h, params["blocks"]
        )
        cache_parts = {"k": ks, "v": vs}

    elif cfg.kind == "ssm":
        def block(h, bp):
            x = rms_norm(h, bp["norm_scale"], cfg.norm_eps)
            if collect_cache:
                y, st = ssm_lib.mamba1_prefill(
                    bp["mamba"], x, cfg, lengths, impl=scan_impl
                )
            else:
                y = ssm_lib.mamba1_apply(bp["mamba"], x, cfg, impl=scan_impl)
                st = {"h": jnp.zeros((), jnp.float32), "conv": jnp.zeros((), h.dtype)}
            return h + y, (jnp.zeros((), jnp.float32), st)

        h, (auxs, states) = jax.lax.scan(
            _maybe_remat(block, remat), h, params["blocks"]
        )
        cache_parts = {"ssm_h": states["h"], "ssm_conv": states["conv"]}

    elif cfg.kind == "hybrid" and cfg.hybrid_layer_ids and not collect_cache:
        h = _zamba2_train(params, cfg, h, window=window, lengths=lengths,
                          impl=impl, remat=remat)
        auxs = jnp.zeros((1,), jnp.float32)
        cache_parts = {}

    elif cfg.kind == "hybrid" and cfg.hybrid_layer_ids:
        def mamba(h, t, lp, _st):
            x = rms_norm(h if t is None else h + t, lp["norm_scale"],
                         cfg.norm_eps)
            y, st = ssm_lib.mamba2_prefill(lp["mamba"], x, cfg, lengths)
            return h + y, st

        def attend(p, x, kv, app):
            a, k, v = attn.attn_prefill(p, x, cfg, window=window,
                                        lengths=lengths, impl=impl,
                                        kv_repeat=kv_repeat)
            return a, tuple(
                jax.lax.dynamic_update_index_in_dim(
                    c, new.reshape(b, s, -1).astype(c.dtype), app, 0)
                for c, new in zip(kv, (k, v)))

        states = {"h": jnp.zeros((cfg.num_layers, b) + _ssm_h_shape(cfg),
                                 jnp.float32),
                  "conv": jnp.zeros((cfg.num_layers, b, cfg.ssm.d_conv - 1,
                                     cfg.mamba2_conv_dim), h.dtype)}
        kv = (jnp.zeros((len(cfg.hybrid_layer_ids), b, s,
                         cfg.num_kv_heads * kv_repeat * cfg.head_dim),
                        h.dtype),) * 2
        h, states, (ks, vs) = _zamba2_stack(params, cfg, h, h, mamba,
                                            attend, states, kv)
        auxs = jnp.zeros((1,), jnp.float32)
        cache_parts = {"ssm_h": states["h"], "ssm_conv": states["conv"],
                       "k": ks, "v": vs}

    elif cfg.kind == "hybrid":
        shared = params["shared"]

        def apply_shared(h):
            x = rms_norm(h, shared["attn_norm_scale"], cfg.norm_eps)
            if collect_cache:
                a, k, v = attn.attn_prefill(
                    shared["attn"], x, cfg, window=window, lengths=lengths,
                    impl=impl, kv_repeat=kv_repeat,
                )
            else:
                a = attn.attn_train(
                    shared["attn"], x, cfg, window=window, lengths=lengths, impl=impl
                )
                k = v = jnp.zeros((), h.dtype)
            h = h + a
            x = rms_norm(h, shared["mlp_norm_scale"], cfg.norm_eps)
            return h + mlp_apply(shared["mlp"], x), k, v

        def mamba_layer(h, lp):
            x = rms_norm(h, lp["norm_scale"], cfg.norm_eps)
            if collect_cache:
                y, st = ssm_lib.mamba2_prefill(lp["mamba"], x, cfg, lengths)
            else:
                y = ssm_lib.mamba2_apply(lp["mamba"], x, cfg)
                st = {"h": jnp.zeros((), jnp.float32), "conv": jnp.zeros((), h.dtype)}
            return h + y, st

        def round_fn(h, rp):
            h, states = jax.lax.scan(mamba_layer, h, rp)
            h, k, v = apply_shared(h)
            return h, (states, k, v)

        h, (states, ks, vs) = jax.lax.scan(
            _maybe_remat(round_fn, remat), h, params["rounds"]
        )
        auxs = jnp.zeros((1,), jnp.float32)
        if collect_cache:
            # (R, per_round, ...) -> (R*per_round, ...)
            flat = jax.tree.map(
                lambda t: t.reshape((-1,) + t.shape[2:]), states
            )
            cache_parts = {
                "ssm_h": flat["h"], "ssm_conv": flat["conv"], "k": ks, "v": vs
            }
        else:
            cache_parts = {}
    else:
        raise ValueError(cfg.kind)

    h = rms_norm(h, params["final_norm"]["scale"], cfg.norm_eps)
    if cfg.kind == "vlm" and "patch_embeds" in batch:
        h = h[:, batch["patch_embeds"].shape[1]:]   # logits over text positions
    aux = jnp.sum(auxs)
    if return_hidden:
        return h, aux
    logits = unembed(params, h)
    if collect_cache:
        return logits, aux, cache_parts
    return logits, aux


# ---------------------------------------------------------------------------
# Zamba2: Mamba-2 layers with shared blocks at the hybrid layer ids
# ---------------------------------------------------------------------------

def _ssm_h_shape(cfg: ModelConfig):
    nh = cfg.d_inner // cfg.ssm.headdim
    return (cfg.ssm.headdim, cfg.ssm.d_state, nh)


def _take(tree, i):
    """Entry i (traced) of every leaf's leading axis."""
    return jax.tree.map(
        lambda w: jax.lax.dynamic_index_in_dim(w, i, 0, keepdims=False), tree)


def _shared_block(params, cfg: ModelConfig, k, h, emb, attend, kv):
    """Application k: block k mod num_mem_blocks on concat(h, emb) —
    RMSNorm, attention, RMSNorm, GeGLU MLP with adapter k — then linear k.
    Returns (the Mamba layer's extra input, kv')."""
    blk = _take(params["shared"], k % cfg.num_mem_blocks)
    ap = _take(params["hybrid"], k)
    with jax.named_scope("zamba2.shared"):
        x = rms_norm(jnp.concatenate([h, emb], axis=-1),
                     blk["attn_norm_scale"], cfg.norm_eps)
        a, kv = attend(blk["attn"], x, kv, k)
        x = rms_norm(a, blk["mlp_norm_scale"], cfg.norm_eps)
        y = mlp_apply(blk["mlp"], x, cfg.mlp_act,
                      (ap["adapter_a"], ap["adapter_b"]))
        return y @ ap["linear"], kv


def _zamba2_train(params, cfg: ModelConfig, h, *, window, lengths, impl,
                  remat):
    """The training forward of the Zamba2 wiring: one scan over the
    layers, each carrying its application index (-1 at a plain layer) as
    data, the shared block under a `lax.cond`. It keeps no cache, so it
    needs no carried state; reverse-mode differentiable."""
    emb = h
    app = [-1] * cfg.num_layers
    for k, i in enumerate(cfg.hybrid_layer_ids):
        app[i] = k

    def attend(p, x, kv, _app):
        return attn.attn_train(p, x, cfg, window=window, lengths=lengths,
                               impl=impl), kv

    def layer(h, xs):
        lp, k = xs
        t = jax.lax.cond(
            k >= 0,
            lambda h: _shared_block(params, cfg, jnp.maximum(k, 0), h, emb,
                                    attend, None)[0],
            jnp.zeros_like, h)
        x = rms_norm(h + t, lp["norm_scale"], cfg.norm_eps)
        return h + ssm_lib.mamba2_apply(lp["mamba"], x, cfg,
                                        lengths=lengths), None

    h, _ = jax.lax.scan(_maybe_remat(layer, remat), h,
                        (params["layers"], jnp.asarray(app, jnp.int32)))
    return h


def _zamba2_stack(params, cfg: ModelConfig, h, emb, mamba, attend, states,
                  kv, *, unroll: bool = False):
    """Run the layers in the published Zamba2 wiring.

    `mamba(h, t, layer_params, state) -> (h', state')` is one Mamba-2
    layer with its residual (`t`, the shared block's contribution to its
    input, or None); `attend(attn_params, x, kv, k) -> (a, kv')` is
    application k's attention, `kv` the K/V stacks it writes (leading
    axis = application). `states` ({"h", "conv"}, leading axis = layer)
    and `kv` are carried and rewritten in place. Application k runs the
    plain layers from the previous hybrid id up to hybrid id k (a loop
    with those bounds as data), then `_shared_block` into the Mamba layer
    at the hybrid id. The applications are a scan, or with `unroll`
    straight-line code, where each one's index is static: it then reads
    its block's weights and its K/V in place instead of as dynamic slices
    that the compiler copies. The one-token decode step unrolls; a prefill
    program (one per bucket shape) stays a scan, less than half the size.
    Returns (h, states, kv)."""
    def layer(i, carry, t=None):
        h, st = carry
        h, si = mamba(h, t, _take(params["layers"], i), _take(st, i))
        st = jax.tree.map(
            lambda s, v: jax.lax.dynamic_update_index_in_dim(
                s, v.astype(s.dtype), i, 0), st, si)
        return h, st

    def application(carry, k, hid, start):
        h, st, kv = carry
        h, st = jax.lax.fori_loop(start, hid, layer, (h, st))
        t, kv = _shared_block(params, cfg, k, h, emb, attend, kv)
        return layer(hid, (h, st), t) + (kv,)

    ids = cfg.hybrid_layer_ids
    starts = (0,) + tuple(i + 1 for i in ids[:-1])
    carry = (h, states, kv)
    if unroll:
        for k, (hid, start) in enumerate(zip(ids, starts)):
            carry = application(carry, k, hid, start)
    else:
        carry, _ = jax.lax.scan(
            lambda c, xs: (application(c, *xs), None), carry,
            (jnp.arange(len(ids), dtype=jnp.int32),
             jnp.asarray(ids, jnp.int32), jnp.asarray(starts, jnp.int32)))
    h, states, kv = carry
    if ids[-1] + 1 < cfg.num_layers:
        h, states = jax.lax.fori_loop(ids[-1] + 1, cfg.num_layers, layer,
                                      (h, states))
    return h, states, kv


# ---------------------------------------------------------------------------
# Encoder-decoder
# ---------------------------------------------------------------------------

def encode(params, cfg: ModelConfig, enc_inputs, enc_lengths=None, *, impl="ref",
           remat=False):
    """Encoder stack over frame embeddings (audio stub) — bidirectional."""
    h = enc_inputs

    def block(h, bp):
        x = rms_norm(h, bp["attn_norm_scale"], cfg.norm_eps)
        h = h + attn.attn_train(
            bp["attn"], x, cfg, causal=False, lengths=enc_lengths, impl=impl
        )
        x = rms_norm(h, bp["mlp_norm_scale"], cfg.norm_eps)
        return h + mlp_apply(bp["mlp"], x), None

    h, _ = jax.lax.scan(_maybe_remat(block, remat), h, params["enc_blocks"])
    return rms_norm(h, params["enc_norm"]["scale"], cfg.norm_eps)


def _forward_encdec(
    params, cfg: ModelConfig, batch, *, impl="ref", collect_cache=False,
    lengths=None, remat=False, return_hidden=False,
):
    enc_out = encode(
        params, cfg, batch["frames"], batch.get("enc_lengths"), impl=impl,
        remat=remat,
    )
    h = embed_apply(params["embed"], batch["tokens"])
    enc_lengths = batch.get("enc_lengths")

    def block(h, bp):
        x = rms_norm(h, bp["self_norm_scale"], cfg.norm_eps)
        if collect_cache:
            a, k, v = attn.attn_prefill(
                bp["self_attn"], x, cfg, lengths=lengths, impl=impl
            )
        else:
            a = attn.attn_train(bp["self_attn"], x, cfg, lengths=lengths, impl=impl)
            k = v = jnp.zeros((), h.dtype)
        h = h + a
        x = rms_norm(h, bp["cross_norm_scale"], cfg.norm_eps)
        ck, cv = attn.cross_attn_kv(bp["cross_attn"], enc_out, cfg)
        h = h + attn.cross_attn_apply(
            bp["cross_attn"], x, ck, cv, enc_lengths, cfg, impl=impl
        )
        x = rms_norm(h, bp["mlp_norm_scale"], cfg.norm_eps)
        return h + mlp_apply(bp["mlp"], x), (k, v, ck, cv)

    h, (ks, vs, cks, cvs) = jax.lax.scan(
        _maybe_remat(block, remat), h, params["dec_blocks"]
    )
    h = rms_norm(h, params["final_norm"]["scale"], cfg.norm_eps)
    aux = jnp.zeros((), jnp.float32)
    if return_hidden:
        return h, aux
    logits = unembed(params, h)
    if collect_cache:
        return logits, aux, {"k": ks, "v": vs, "cross_k": cks, "cross_v": cvs}
    return logits, aux


# ---------------------------------------------------------------------------
# Decode step (single new token against the cache)
# ---------------------------------------------------------------------------

def decode_step(
    params,
    cfg: ModelConfig,
    tokens,            # (B,) int32 — current input token per slot
    cache,
    *,
    impl: str = "ref",
    window: Optional[int] = None,
    kv_repeat: int = 1,
):
    """One decode iteration. Returns (logits (B, V), cache')."""
    lengths = cache["length"]
    h = embed_apply(params["embed"], tokens)            # (B, d)

    if cfg.kind in ("dense", "vlm", "moe"):
        # static layout branch: a physically paged cache (block_tables in
        # the pytree) routes through the paged pool; the table itself is
        # layer-invariant, so it rides in as a scan closure, not an xs
        paged = "block_tables" in cache

        def block(h, xs):
            bp, kc, vc = xs
            x = rms_norm(h, bp["attn_norm_scale"], cfg.norm_eps)
            if paged:
                a, kc, vc = attn.attn_decode_paged(
                    bp["attn"], x, kc, vc, cache["block_tables"], lengths,
                    cfg, window=window, impl=impl, kv_repeat=kv_repeat,
                )
            else:
                a, kc, vc = attn.attn_decode(
                    bp["attn"], x, kc, vc, lengths, cfg, window=window,
                    impl=impl, kv_repeat=kv_repeat,
                )
            h = h + a
            x = rms_norm(h, bp["mlp_norm_scale"], cfg.norm_eps)
            if cfg.kind == "moe":
                y, _ = moe_lib.moe_apply(bp["moe"], x[:, None, :], cfg)
                y = y[:, 0]
            else:
                y = mlp_apply(bp["mlp"], x)
            return h + y, (kc, vc)

        h, (ks, vs) = jax.lax.scan(
            block, h, (params["blocks"], cache["k"], cache["v"])
        )
        cache = dict(cache, k=ks, v=vs)

    elif cfg.kind == "ssm":
        def block(h, xs):
            bp, hh, cv = xs
            x = rms_norm(h, bp["norm_scale"], cfg.norm_eps)
            y, st = ssm_lib.mamba1_decode(bp["mamba"], x, {"h": hh, "conv": cv}, cfg)
            return h + y, (st["h"], st["conv"])

        h, (hs, convs) = jax.lax.scan(
            block, h, (params["blocks"], cache["ssm_h"], cache["ssm_conv"])
        )
        cache = dict(cache, ssm_h=hs, ssm_conv=convs)

    elif cfg.kind == "hybrid" and cfg.hybrid_layer_ids:
        def mamba(h, t, lp, st):
            x = rms_norm(h if t is None else h + t, lp["norm_scale"],
                         cfg.norm_eps)
            y, st = ssm_lib.mamba2_decode(lp["mamba"], x, st, cfg)
            return h + y, st

        def attend(p, x, kv, app):
            a, kc, vc = attn.attn_decode_at(p, x, *kv, app, lengths, cfg,
                                            window=window, impl=impl,
                                            kv_repeat=kv_repeat)
            return a, (kc, vc)

        h, states, (ks, vs) = _zamba2_stack(
            params, cfg, h, h, mamba, attend,
            {"h": cache["ssm_h"], "conv": cache["ssm_conv"]},
            (cache["k"], cache["v"]), unroll=True)
        cache = dict(cache, ssm_h=states["h"], ssm_conv=states["conv"],
                     k=ks, v=vs)

    elif cfg.kind == "hybrid":
        shared = params["shared"]
        rounds = params["rounds"]["mamba"]["in_proj"].shape[0]
        per_round = params["rounds"]["mamba"]["in_proj"].shape[1]
        ssm_h = cache["ssm_h"].reshape((rounds, per_round) + cache["ssm_h"].shape[1:])
        ssm_conv = cache["ssm_conv"].reshape(
            (rounds, per_round) + cache["ssm_conv"].shape[1:]
        )

        def mamba_layer(h, xs):
            lp_norm, lp, hh, cv = xs
            x = rms_norm(h, lp_norm, cfg.norm_eps)
            y, st = ssm_lib.mamba2_decode(lp, x, {"h": hh, "conv": cv}, cfg)
            return h + y, (st["h"], st["conv"])

        def round_fn(h, xs):
            rp_norm, rp, hh_r, cv_r, kc, vc = xs
            h, (hs, convs) = jax.lax.scan(
                mamba_layer, h, (rp_norm, rp, hh_r, cv_r)
            )
            x = rms_norm(h, shared["attn_norm_scale"], cfg.norm_eps)
            a, kc, vc = attn.attn_decode(
                shared["attn"], x, kc, vc, lengths, cfg, window=window,
                impl=impl, kv_repeat=kv_repeat,
            )
            h = h + a
            x = rms_norm(h, shared["mlp_norm_scale"], cfg.norm_eps)
            h = h + mlp_apply(shared["mlp"], x)
            return h, (hs, convs, kc, vc)

        h, (hs, convs, ks, vs) = jax.lax.scan(
            round_fn,
            h,
            (
                params["rounds"]["norm_scale"],
                params["rounds"]["mamba"],
                ssm_h,
                ssm_conv,
                cache["k"],
                cache["v"],
            ),
        )
        cache = dict(
            cache,
            ssm_h=hs.reshape(cache["ssm_h"].shape),
            ssm_conv=convs.reshape(cache["ssm_conv"].shape),
            k=ks,
            v=vs,
        )

    elif cfg.kind in ("encdec", "audio"):
        enc_lengths = cache["enc_length"]

        def block(h, xs):
            bp, kc, vc, ck, cv = xs
            x = rms_norm(h, bp["self_norm_scale"], cfg.norm_eps)
            a, kc, vc = attn.attn_decode(
                bp["self_attn"], x, kc, vc, lengths, cfg, window=window, impl=impl
            )
            h = h + a
            x = rms_norm(h, bp["cross_norm_scale"], cfg.norm_eps)
            c = attn.cross_attn_apply(
                bp["cross_attn"], x[:, None, :], ck, cv, enc_lengths, cfg, impl=impl
            )
            h = h + c[:, 0]
            x = rms_norm(h, bp["mlp_norm_scale"], cfg.norm_eps)
            return h + mlp_apply(bp["mlp"], x), (kc, vc)

        h, (ks, vs) = jax.lax.scan(
            block,
            h,
            (
                params["dec_blocks"],
                cache["k"],
                cache["v"],
                cache["cross_k"],
                cache["cross_v"],
            ),
        )
        cache = dict(cache, k=ks, v=vs)
    else:
        raise ValueError(cfg.kind)

    h = rms_norm(h, params["final_norm"]["scale"], cfg.norm_eps)
    logits = unembed(params, h)
    cache = dict(cache, length=lengths + 1)
    return logits, cache


# ---------------------------------------------------------------------------
# Multi-position verify step (speculative decoding)
# ---------------------------------------------------------------------------

def verify_step(
    params,
    cfg: ModelConfig,
    tokens,            # (B, T) int32 — T = k+1 proposal window per slot
    cache,
    *,
    impl: str = "ref",
    window: Optional[int] = None,
    kv_repeat: int = 1,
):
    """Verify a T-token proposal window in one jitted call.

    Scans `decode_step` over the T positions: position j consumes
    tokens[:, j] against the cache as grown by positions < j, exactly as T
    sequential decode iterations would. This form is deliberate — the
    speculative engine's losslessness gate demands logits *bit-identical*
    to the non-speculative step-by-step decode (argmax ties must break the
    same way), which a parallel multi-position attention with a different
    reduction order could not guarantee. tests/test_speculative.py pins
    verify_step ≡ sequential decode_step bit-for-bit; the hardware *cost*
    of the fused window (one weight pass for T tokens) is modeled by
    LatencyModel.verify_latency, which is what makes speculation pay.

    Returns (logits (B, T, V), cache') with cache length advanced by T.
    Rejected positions leave stale KV beyond the accepted length; the
    serving layer rolls that back by length alone (models/cache.py
    docstring: length-gated attention never reads past `length`, and the
    next write lands on the first stale position).
    """
    def body(c, tok):
        logits, c = decode_step(
            params, cfg, tok, c, impl=impl, window=window,
            kv_repeat=kv_repeat,
        )
        return c, logits

    cache, logits = jax.lax.scan(body, cache, jnp.moveaxis(tokens, 0, 1))
    return jnp.moveaxis(logits, 0, 1), cache


def propose_step(
    params,
    cfg: ModelConfig,
    tokens,            # (B,) int32 — last committed token per slot
    cache,
    k: int,
    *,
    impl: str = "ref",
    window: Optional[int] = None,
    kv_repeat: int = 1,
):
    """Greedy-autoregress k+1 tokens in one jitted call (the draft side of
    speculative decoding): step 0 consumes `tokens`, every later step
    consumes its own argmax. The extra (k+1)-th step exists to keep the
    draft cache invariant uniform — after a fully-accepted proposal the
    draft must already have consumed its own k-th token so that the next
    round's single catch-up input is always exactly the last committed
    token (see serving/speculative.py). Returns (proposals (B, k+1), cache').
    """
    def body(carry, _):
        tok, c = carry
        logits, c = decode_step(
            params, cfg, tok, c, impl=impl, window=window,
            kv_repeat=kv_repeat,
        )
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return (nxt, c), nxt

    (_, cache), toks = jax.lax.scan(
        body, (tokens, cache), None, length=k + 1
    )
    return jnp.moveaxis(toks, 0, 1), cache
