"""Draft-model side of speculative decoding inside a ServingEngine.

A `DraftProposer` owns a second, smaller model (same tokenizer/vocab as the
target — e.g. a 1-layer granite-class config drafting for the full one) and
a second static-slot cache with the *same* slot layout as the target's, so
request -> slot mapping, preemption and swap round-trips stay one decision
made once by the engine's KVSlotManager.

Per scheduled step the proposer greedily autoregresses k+1 tokens in one
jitted scan (`Model.propose_step`); the engine verifies the window
[last_committed, d_1..d_k] against the target in one `Model.verify_step`
call and commits the longest matching prefix plus the correction/bonus
token — lossless by construction under greedy sampling.

Draft-cache bookkeeping reduces to ONE invariant, restored for free every
round:

    the draft cache's valid prefix is always committed[: context_len - 1]

i.e. the draft has consumed every committed token except the last, which is
exactly the next round's first input (the target's cache holds the same
prefix, so both caches are pinned to one length). Why it holds: the proposal scan
consumes k+1 inputs (the last committed token, then its own d_1..d_k — the
extra (k+1)-th step is what makes full acceptance not a special case).
After a tokens are accepted, the consumed inputs d_1..d_a coincide with the
newly committed tokens and everything after them is stale; re-pinning the
draft cache's `length` to the new context_len - 1 (done unconditionally at
the top of every `propose`) is the entire rollback, per the length-gate
contract in models/cache.py. No per-request draft state exists outside the
cache itself, which is why park/restore are plain slot-slice copies.

SSM/recurrent architectures are rejected up front: their state has no
length gate to roll back through (checkpoint-per-position would be needed),
and capacity-routed MoE couples slots within a batch, which would break the
per-request bit-identity the differential harness asserts. Dense attention
is the supported — and paper-relevant — regime.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import cache as cache_lib
from repro.models.model import Model


def check_speculation_compatible(target: Model, draft: Model) -> None:
    """Both models must be attention-only and share the token space."""
    for role, m in (("target", target), ("draft", draft)):
        if m.cfg.kind != "dense":
            raise ValueError(
                f"speculative decoding supports dense attention models; "
                f"{role} is kind={m.cfg.kind!r} (SSM state cannot be "
                f"length-rolled-back; MoE capacity routing couples slots)"
            )
    if target.cfg.vocab_size != draft.cfg.vocab_size:
        raise ValueError(
            f"draft must share the target's vocab: "
            f"{draft.cfg.vocab_size} != {target.cfg.vocab_size}"
        )


class DraftProposer:
    """Slot-parallel greedy proposer over a shared draft (model, params).

    `bucketed` (a serving.engine.BucketedPrefill over the draft model)
    routes draft prefills through the same jitted shape-bucketed path the
    engine's target prefills use: admissions flushed in one step build
    their draft KV in one padded multi-row call + one fused slot scatter
    (`prefill_batch`), bounding draft prefill compiles by the bucket grid.
    None (hot path off) keeps the eager exact-length batch-1 path."""

    def __init__(
        self,
        model: Model,
        params,
        *,
        num_slots: int,
        max_seq: int,
        cache_dtype=jnp.float32,
        bucketed=None,
    ):
        self.model = model
        self.params = params
        self.max_seq = max_seq
        self.bucketed = bucketed
        self.cache = model.init_cache(num_slots, max_seq, dtype=cache_dtype)
        self._propose = jax.jit(model.propose_step, static_argnames=("k",))

    # ---- per-slot cache lifecycle (mirrors the engine's target cache) ------
    def prefill(self, slot: int, tokens: np.ndarray) -> None:
        """Build the draft KV for a request's committed-minus-last prefix."""
        if self.bucketed is not None:
            self.prefill_batch([slot], [tokens])
            return
        from repro.serving.engine import _write_slot
        one = self.model.init_cache(
            1, self.max_seq, dtype=self.cache["k"].dtype
        )
        _, one = self.model.prefill(
            self.params, {"tokens": jnp.asarray(tokens, jnp.int32)[None]}, one
        )
        self.cache = _write_slot(self.cache, one, slot)

    def prefill_batch(self, slots, toks_list) -> int:
        """Bucketed multi-row draft prefill — the same grouped
        `BucketedPrefill.prefill_into` flush the engine's admission path
        uses (one padded call + one fused scatter per bucket group; each
        row bit-identical to a batch-1 prefill of the same request, so the
        slot-parallel propose scans see exactly the state the sequential
        path would have built). The draft never needs first-token ids, so
        the flush skips the device→host fetch entirely. Returns the number
        of bucket groups dispatched (the engine's dispatch accounting)."""
        self.cache, _, _, n_groups = self.bucketed.prefill_into(
            self.params, self.cache, list(slots), list(toks_list),
            need_first=False,
        )
        return n_groups

    def park(self, slot: int) -> dict:
        """Fetch a slot's draft slice to host (preemption swap-out)."""
        from repro.serving.engine import _read_slot
        return jax.device_get(_read_slot(self.cache, slot))

    def restore(self, slot: int, host_slice: dict) -> None:
        from repro.serving.engine import _write_slot
        self.cache = _write_slot(
            self.cache, jax.tree.map(jnp.asarray, host_slice), slot
        )

    # ---- proposal ----------------------------------------------------------
    def propose(self, last_tokens: np.ndarray, draft_lengths: np.ndarray,
                k: int) -> np.ndarray:
        """Greedy k-token proposals for every slot.

        last_tokens (num_slots,): the last committed token per slot (the
        single catch-up input — see the module-docstring invariant).
        draft_lengths (num_slots,): committed context_len - 1 per active
        slot (0 for inactive slots, whose outputs are ignored). Returns
        proposals (num_slots, k); the scan's (k+1)-th token is internal
        cache upkeep and is dropped here.
        """
        self.cache = cache_lib.with_lengths(self.cache, draft_lengths)
        toks, self.cache = self._propose(
            self.params, jnp.asarray(last_tokens, jnp.int32), self.cache, k=k
        )
        return np.asarray(toks)[:, :k]


__all__ = ["DraftProposer", "check_speculation_compatible"]
