"""Expert-parallel MoE dispatch with shard_map (beyond-paper, §Perf).

The GSPMD-compiled capacity-MoE (models/moe.py) lets XLA choose the
collectives; measured on qwen2-moe prefill it all-gathers every token to
every expert shard (~175 GB/dev) before selecting. This version writes the
communication explicitly with `shard_map` over the (data..., model) mesh:

  * tokens are sharded over the data axes and *replicated* over "model"
    (that is already the activation layout) — so each device can select the
    tokens routed to ITS local experts with zero communication;
  * each device runs its E/tp experts on its data shard's tokens (expert
    FLOPs are thereby sharded over the full mesh);
  * the only collective is one `psum` over "model" to combine the partial
    per-token outputs (each token's k experts live on ≤ k model shards).

Per-device traffic drops from gather(all tokens) + reduce(outputs) to just
reduce(outputs). Capacity is per (data shard × expert), which totals to the
same global 1.25·k·T slots as the baseline.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import moe as moe_lib
from repro.models.layers import mlp_apply


def moe_apply_ep(p, x: jax.Array, cfg: ModelConfig, mesh,
                 valid=None) -> Tuple[jax.Array, jax.Array]:
    """Drop-in for moe_apply under an explicit mesh. x (B, S, d)."""
    m = cfg.moe
    tp = int(mesh.shape["model"])
    # pad experts up to a multiple of the model axis (router never routes
    # to the pad experts — only their zero weights are carried)
    e_pad = (-m.num_experts) % tp
    experts = p["experts"]
    if e_pad:
        experts = jax.tree.map(
            lambda w: jnp.pad(w, ((0, e_pad),) + ((0, 0),) * (w.ndim - 1)),
            experts,
        )
    e_total = m.num_experts + e_pad
    e_local = e_total // tp
    dp = tuple(a for a in mesh.axis_names if a != "model")
    dp_spec = dp if len(dp) > 1 else dp[0]

    def local_fn(xl, vl, router, gate, up, down):
        # xl (B_loc, S, d) — this data shard's tokens (same on every model
        # shard); gate/up/down (E/tp, ...) — this model shard's experts.
        b, s, d = xl.shape
        t = b * s
        xt = xl.reshape(t, d)
        logits = (xt @ router).astype(jnp.float32)            # (T_loc, E)
        probs = jax.nn.softmax(logits, axis=-1)
        top_w, top_e = jax.lax.top_k(probs, m.top_k)
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
        if vl is not None:
            vt = vl.reshape(t)
            top_w = top_w * vt[:, None]
            top_e = jnp.where(vt[:, None], top_e, e_total)
            probs = probs * vt[:, None]

        # aux loss: identical on every model shard (inputs replicated);
        # average over data shards
        me = jnp.mean(probs, axis=0)
        onehot_full = jax.nn.one_hot(top_e, m.num_experts)
        ce = jnp.mean(jnp.sum(onehot_full, axis=1), axis=0) / m.top_k
        aux = m.num_experts * jnp.sum(me * ce) * m.router_aux_loss_coef
        aux = jax.lax.pmean(aux, dp) if dp else aux

        # ---- select slots routed to LOCAL experts -----------------------
        e0 = jax.lax.axis_index("model") * e_local
        local_e = top_e - e0                                   # (T_loc, k)
        is_local = (local_e >= 0) & (local_e < e_local)
        local_e = jnp.where(is_local, local_e, e_local)        # waste row
        onehot = jax.nn.one_hot(local_e, e_local)              # (T,k,E_loc)

        # live module-attribute lookup, NOT a from-import: the capacity
        # knob must stay shared with the GSPMD reference. A value bound at
        # import time silently diverges when callers (the no-drop
        # differential test, notably) retune moe.CAPACITY_FACTOR — the EP
        # path then drops tokens the reference keeps, which surfaced as a
        # ~1.6e-3 "numerical drift" in the divisible case that was really
        # a few dropped tokens.
        cap = int(moe_lib.CAPACITY_FACTOR * t * m.top_k / m.num_experts) + 1
        cap = min(cap, t)
        flat_e = local_e.reshape(t * m.top_k)
        flat_w = (top_w * is_local).reshape(t * m.top_k)
        flat_oh = onehot.reshape(t * m.top_k, e_local)
        pos_in_e = jnp.cumsum(flat_oh, axis=0) - 1.0
        slot_pos = jnp.sum(pos_in_e * flat_oh, axis=-1).astype(jnp.int32)
        keep = (slot_pos < cap) & (flat_e < e_local)
        slot_pos = jnp.where(keep, slot_pos, cap)

        token_idx = jnp.repeat(jnp.arange(t), m.top_k)
        buf = jnp.zeros((e_local, cap + 1, d), xl.dtype)
        buf = buf.at[jnp.minimum(flat_e, e_local - 1), slot_pos].add(
            jnp.where(keep[:, None], xt[token_idx], 0.0)
        )
        buf = buf[:, :cap]

        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, gate)) \
            * jnp.einsum("ecd,edf->ecf", buf, up)
        out = jnp.einsum("ecf,efd->ecd", h, down)

        gathered = out[jnp.minimum(flat_e, e_local - 1),
                       jnp.minimum(slot_pos, cap - 1)]
        gathered = gathered * (flat_w * keep)[:, None]
        y = jnp.zeros((t, d), xl.dtype).at[token_idx].add(gathered)
        # the ONLY cross-shard collective: combine partial expert outputs
        y = jax.lax.psum(y, "model")
        return y.reshape(b, s, d), aux

    vspec = P(dp_spec, None) if valid is not None else None
    args_in = (
        P(dp_spec, None, None),          # x: data-sharded, model-replicated
        vspec,
        P(None, None),                   # router replicated
        P("model", None, None),          # experts: E over model
        P("model", None, None),
        P("model", None, None),
    )
    if valid is None:
        def wrapper(xl, router, gate, up, down):
            return local_fn(xl, None, router, gate, up, down)
        y, aux = jax.shard_map(
            wrapper, mesh=mesh,
            in_specs=(args_in[0],) + args_in[2:],
            out_specs=(P(dp_spec, None, None), P()),
            check_vma=False,
        )(x, p["router"], experts["gate"], experts["up"], experts["down"])
    else:
        y, aux = jax.shard_map(
            local_fn, mesh=mesh, in_specs=args_in,
            out_specs=(P(dp_spec, None, None), P()),
            check_vma=False,
        )(x, valid, p["router"], experts["gate"], experts["up"],
          experts["down"])

    if "shared" in p:
        b, s, d = x.shape
        y = y + mlp_apply(p["shared"], x.reshape(-1, d)).reshape(b, s, d)
    return y, aux
