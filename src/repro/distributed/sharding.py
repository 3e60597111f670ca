"""Sharding rules: param/batch/cache pytrees -> PartitionSpecs.

Scheme (DESIGN.md §6): 2-D logical parallelism on top of the physical mesh
  * "model"  — tensor parallel: attention heads / d_ff / d_inner / experts
  * fsdp     — ("pod","data"): batch for activations, FSDP for weights
                (every weight matrix is additionally sharded on its
                non-tensor-parallel dim so 405B params + AdamW state fit)

Rules are name-based over the param tree and downgrade gracefully: a dim
that does not divide by its mesh-axis size is replicated instead (GSPMD
would accept uneven shards, but even sharding keeps the roofline terms
clean). Cache/batch rules handle the decode shapes, including the
batch=1 long-context case where the KV sequence axis is sharded instead of
batch (sequence parallelism over the cache).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig


def _axis_size(mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, tuple):
        return int(np.prod([mesh.shape[a] for a in axis]))
    return int(mesh.shape[axis])


def _fit(mesh, dim: int, axis):
    """axis if it divides dim, else None (replicate)."""
    return axis if (axis is not None and dim % _axis_size(mesh, axis) == 0) else None


def _fsdp(mesh):
    axes = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    return axes if len(axes) > 1 else axes[0]


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------

# (name fragment, spec builder over trailing dims)
def _rule_for(key_path: str):
    """Returns (n_base_dims, builder(mesh, shape_tail) -> spec_tail)."""
    k = key_path

    def two(in_ax, out_ax):
        return 2, lambda mesh, s: (
            _fit(mesh, s[0], in_ax(mesh)), _fit(mesh, s[1], out_ax(mesh)))

    fsdp = _fsdp
    mdl = lambda mesh: "model"

    if k.endswith("embed|table"):
        return two(mdl, fsdp)            # (V, d): vocab on model, d FSDP
    if "lm_head" in k:
        return two(fsdp, mdl)            # (d, V)
    if any(t in k for t in ("|wq", "|wk", "|wv", "|up", "|gate", "|in_proj",
                            "vision_proj")):
        if "experts|" in k:              # (E, d, fe)
            return 3, lambda mesh, s: (
                _fit(mesh, s[0], "model"), _fit(mesh, s[1], _fsdp(mesh)), None)
        return two(fsdp, mdl)
    if any(t in k for t in ("|wo", "|down", "|out_proj")):
        if "experts|" in k:              # (E, fe, d)
            return 3, lambda mesh, s: (
                _fit(mesh, s[0], "model"), None, _fit(mesh, s[1], _fsdp(mesh)))
        return two(mdl, fsdp)
    if k.endswith("|router"):
        return two(fsdp, lambda m: None)  # (d, E): E small, replicated
    if k.endswith("|x_proj") or k.endswith("|dt_proj"):
        return two(mdl, lambda m: None) if k.endswith("|x_proj") \
            else two(lambda m: None, mdl)
    if k.endswith("|conv_w"):
        return 2, lambda mesh, s: (None, _fit(mesh, s[1], "model"))
    if k.endswith("|A_log") and True:
        return 0, None                    # handled by dim count below
    return 0, None


def param_specs(mesh, params_tree) -> Dict:
    """PartitionSpec pytree matching `params_tree` (arrays or SDS)."""
    fsdp = _fsdp(mesh)

    def spec_one(path, leaf):
        key = "|".join(str(getattr(p, "key", getattr(p, "idx", p)))
                       for p in path)
        shape = leaf.shape
        nbase, builder = _rule_for(key)
        if builder is not None and len(shape) >= nbase:
            lead = (None,) * (len(shape) - nbase)
            tail = builder(mesh, shape[len(shape) - nbase:])
            return P(*(lead + tuple(tail)))
        # 1-D-ish leaves: shard big vectors on model, replicate small ones
        if shape and shape[-1] >= 1024:
            lead = (None,) * (len(shape) - 1)
            return P(*(lead + (_fit(mesh, shape[-1], "model"),)))
        if key.endswith("|A_log") and len(shape) >= 2 and shape[-2] >= 1024:
            lead = (None,) * (len(shape) - 2)
            return P(*(lead + (_fit(mesh, shape[-2], "model"), None)))
        return P()

    return jax.tree_util.tree_map_with_path(spec_one, params_tree)


# ---------------------------------------------------------------------------
# Batches and caches
# ---------------------------------------------------------------------------

def batch_specs(mesh, batch_tree, cfg: Optional[ModelConfig] = None) -> Dict:
    """Shard the leading batch dim of every batch leaf on the data axes."""
    dp = _fsdp(mesh)

    def spec_one(path, leaf):
        bdim = leaf.shape[0] if leaf.shape else 1
        first = _fit(mesh, bdim, dp)
        # fall back to single "data" axis if the combined axes don't divide
        if first is None and isinstance(dp, tuple):
            first = _fit(mesh, bdim, "data")
        return P(*((first,) + (None,) * (len(leaf.shape) - 1)))

    return jax.tree_util.tree_map_with_path(spec_one, batch_tree)


def cache_specs(mesh, cache_tree, cfg: ModelConfig) -> Dict:
    """Decode-cache shardings.

    KV (L, B, S, KV, hd): batch on data axes; heads on "model" when they
    divide, else head_dim on "model", else replicate. If batch itself does
    not divide (long_500k has B=1), the *sequence* axis takes the data axes
    instead (cache sequence parallelism).
    """
    dp = _fsdp(mesh)

    def kv_spec(shape):
        L, B, S, KV, HD = shape
        b_ax = _fit(mesh, B, dp)
        if b_ax is None and isinstance(dp, tuple):
            b_ax = _fit(mesh, B, "data")
        s_ax = None
        if b_ax is None:
            s_ax = _fit(mesh, S, dp)     # sequence parallelism fallback
        head_ax = _fit(mesh, KV, "model")
        hd_ax = None if head_ax else _fit(mesh, HD, "model")
        return P(None, b_ax, s_ax, head_ax, hd_ax)

    def spec_one(path, leaf):
        key = "|".join(str(getattr(p, "key", getattr(p, "idx", p)))
                       for p in path)
        shape = leaf.shape
        if key in ("k", "v", "cross_k", "cross_v"):
            return kv_spec(shape)
        if key == "length" or key == "enc_length":
            return P(_fit(mesh, shape[0], dp))
        if key == "ssm_h":
            # (L, B, di, N) or (L, B, HD, N, NH)
            b_ax = _fit(mesh, shape[1], dp)
            inner = _fit(mesh, shape[2], "model")
            return P(*((None, b_ax, inner) + (None,) * (len(shape) - 3)))
        if key == "ssm_conv":
            b_ax = _fit(mesh, shape[1], dp)
            return P(None, b_ax, None, _fit(mesh, shape[3], "model"))
        return P()

    return jax.tree_util.tree_map_with_path(spec_one, cache_tree)


def make_shardings(mesh, spec_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )
