"""The comparison that decides `correct`.

Two checks, on what the timed window itself served:

* wire: every token id a client read off the SSE stream equals the
  engine's record of that request at the same index (exact; limit 0).
* served_gap_max: for a sample of the requests finished in the window,
  drawn from the seed with the longest among them (and, where the window
  preempted any, the preempted ones first), the plain float32 reference
  is run once over each prompt with its served tokens. At every served
  position the gap is the reference's best logit minus its logit of the
  token the program served; the number compared is the widest gap. With
  greedy decoding a sound program serves the reference's best token up
  to near-ties that its own rounding can flip, so the gap stays small;
  a wrong token, state or cache shows as a wide one.

The control (bench/control.py, not part of a benchmark run) puts the
reference itself in the program's place, computed in a lower precision,
and reads the same gap for the token it puts first.
"""
from __future__ import annotations

import importlib
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

WANT_TOKENS = 600        # served tokens compared per run, at least
MAX_REQUESTS = 256       # where requests are short, as many as this
MAX_PREEMPTED = 4
MIN_BUCKET = 128         # reference sequences are padded to a power of two


def sample(finished: Dict[int, int], preempted: Sequence[int],
           rng: np.random.Generator,
           prompt_lens: Optional[Dict[int, int]] = None) -> List[int]:
    """rids to compare: preempted ones first, then the longest (by prompt
    and served tokens together), then a seeded draw of the rest until
    WANT_TOKENS served tokens are covered or MAX_REQUESTS are chosen.
    `finished` maps rid -> served tokens, `prompt_lens` rid -> prompt
    tokens."""
    if not finished:
        return []
    plen = prompt_lens or {}
    pre = [r for r in preempted if r in finished]
    rng.shuffle(pre)
    chosen = pre[:MAX_PREEMPTED]
    longest = max(finished,
                  key=lambda r: (finished[r] + plen.get(r, 0), -r))
    if longest not in chosen:
        chosen.append(longest)
    rest = [r for r in sorted(finished) if r not in chosen]
    rng.shuffle(rest)
    for r in rest:
        if (sum(finished[c] for c in chosen) >= WANT_TOKENS
                or len(chosen) >= MAX_REQUESTS):
            break
        chosen.append(r)
    return chosen


def reference_module(kind: str):
    return importlib.import_module(f"bench.reference.{kind}")


def make_scorer(kind: str, m: dict, mm: Callable):
    """Jitted fn(params, tokens (S,), targets (S,)) -> (gap (S,), argmax
    (S,)): the reference's best logit minus its logit of each target,
    and its own first choice, at every position."""
    import jax
    import jax.numpy as jnp

    mod = reference_module(kind)

    def score(params, tokens, targets):
        logits = mod.forward(params, m, tokens, mm)
        best = jnp.max(logits, axis=-1)
        got = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
        return best - got, jnp.argmax(logits, axis=-1).astype(jnp.int32)

    return jax.jit(score)


def pad_pair(prompt: np.ndarray, served: Sequence[int]) -> Tuple[np.ndarray,
                                                                  np.ndarray,
                                                                  int, int]:
    """The sequence the reference reads (prompt and all served tokens but
    the last), the targets at each position, and the slice of positions
    that predict served tokens, padded to a power of two (at least
    MIN_BUCKET), so few lengths compile. Padding sits after the sequence,
    so causal positions before it are exact."""
    served = np.asarray(served, np.int32)
    seq = np.concatenate([np.asarray(prompt, np.int32), served[:-1]])
    targets = np.concatenate([np.asarray(prompt[1:], np.int32), served])
    n = len(seq)
    padded = max(MIN_BUCKET, 1 << (n - 1).bit_length())
    seq = np.pad(seq, (0, padded - n))
    targets = np.pad(targets, (0, padded - n))
    return seq, targets, len(prompt) - 1, n


def out_of_vocab(pairs, vocab: int) -> int:
    """Served token ids that name no token of the vocabulary."""
    return int(sum(np.sum((np.asarray(s) < 0) | (np.asarray(s) >= vocab))
                   for _, s in pairs))


def served_gaps(scorer, params, pairs, vocab: int,
                choose=None) -> List[np.ndarray]:
    """Gap of every served token, one array per (prompt, served) pair
    (ids outside the vocabulary are counted by `out_of_vocab` and read
    here as token 0). With `choose`, a scorer standing in for the
    program (the control), the token compared at each served position is
    the one `choose` puts first there, on the same prompt and tokens."""
    import jax

    out = []
    for prompt, served in pairs:
        served = np.where((np.asarray(served) >= 0)
                          & (np.asarray(served) < vocab), served, 0)
        seq, targets, lo, hi = pad_pair(prompt, served)
        if choose is not None:
            _, targets = choose(params, seq, targets)
        gap, _ = scorer(params, seq, targets)
        out.append(np.asarray(jax.device_get(gap))[lo:hi])
    return out


def wire_mismatches(wire: Dict[int, List[int]],
                    engine: Dict[int, List[int]]) -> int:
    """Requests whose wire tokens are not a prefix of the engine's record.
    A request that read no token says nothing: one sent just before the
    window closed may not have reached the engine before the server
    stopped, and has no record there."""
    return len(wire_mismatch_rids(wire, engine))


def wire_mismatch_rids(wire: Dict[int, List[int]],
                       engine: Dict[int, List[int]]) -> List[int]:
    """The rids `wire_mismatches` counts."""
    bad = []
    for rid, toks in wire.items():
        if not toks:
            continue
        rec = engine.get(rid)
        if rec is None or list(rec[:len(toks)]) != list(toks):
            bad.append(rid)
    return bad
