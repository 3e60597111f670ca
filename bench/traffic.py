"""The one traffic generator. A mix is a JSON file of parameters.

The length shapes (the paper's Fig. 9 ShareGPT fit), the arrival
processes (Poisson, and gamma bursts with a stated coefficient of
variation, paper §6.4) and the reading-speed QoE trace (paper Table 1)
are copies of the program's `workload/` generators, so the yardstick does
not move with the program.

Every seed gets the same work: the arrival times, the (prompt, output)
lengths and the QoE specs are drawn from the mix's own `pool_seed`, and
`--seed` draws the prompt token ids (and, in bench/run.py, the weights).
Which request is due near the window's end decides how many of its
tokens the window sees, so a seed that reordered the work would move
`tokens_s` by several percent (about 10% between two seeds on the
virtual clock); with the order fixed, runs differ only by how the
system behaves.

Mix keys:
    prompt_lognormal, output_lognormal   [mean, sigma] of log length
    output_tokens                        optional: every output is this
                                         many tokens (the output draws
                                         are still made, so the rest of
                                         the pool is the same)
    len_min, len_max                     clip of each length
    arrival                              "poisson" | "gamma"
    cv                                   gamma's coefficient of variation
    load                                 rate as a multiple of the
                                         configuration's knee for this
                                         mix (`knee_rps[<traffic>]`)
    qoe                                  "reading" | "voice"
    ttft                                 expected TTFT (s)
    pool_seed                            seed of the multiset
"""
from __future__ import annotations

import dataclasses
from typing import List, Sequence, Tuple

import numpy as np

WORD_PER_TOKEN = 0.75
# (share, words per minute): reading speed by age group (paper Table 1)
READING_WPM = [(0.280, 236), (0.519, 200), (0.112, 192), (0.056, 185),
               (0.033, 175)]
# speaking speed by language (paper Table 2)
SPEAKING_WPM = [(0.793, 150), (0.070, 158), (0.069, 150), (0.036, 195),
                (0.032, 218)]


def sample_lengths(n: int, rng: np.random.Generator, prompt_ln=(5.0, 0.9),
                   output_ln=(5.3, 0.8), len_min: int = 4,
                   len_max: int = 1024) -> Tuple[np.ndarray, np.ndarray]:
    """(prompt_len, output_len) int arrays, log-normal and clipped."""
    p = rng.lognormal(mean=prompt_ln[0], sigma=prompt_ln[1], size=n)
    o = rng.lognormal(mean=output_ln[0], sigma=output_ln[1], size=n)
    return (np.clip(p, len_min, len_max).astype(np.int64),
            np.clip(o, len_min, len_max).astype(np.int64))


def poisson_arrivals(rate: float, n: int,
                     rng: np.random.Generator) -> np.ndarray:
    return np.cumsum(rng.exponential(1.0 / rate, size=n))


def gamma_arrivals(rate: float, n: int, rng: np.random.Generator,
                   cv: float = 3.0) -> np.ndarray:
    shape = 1.0 / (cv * cv)
    return np.cumsum(rng.gamma(shape, 1.0 / (rate * shape), size=n))


def tds_trace(mix: Sequence[Tuple[float, float]], n: int,
              rng: np.random.Generator) -> np.ndarray:
    """Expected token delivery speeds (tokens/s) drawn from a WPM mix."""
    shares = np.array([s for s, _ in mix])
    shares = shares / shares.sum()
    wpms = np.array([w for _, w in mix])
    idx = rng.choice(len(mix), size=n, p=shares)
    return wpms[idx] / 60.0 / WORD_PER_TOKEN


def clip_to_context(prompt: np.ndarray, output: np.ndarray, max_seq: int,
                    len_min: int) -> Tuple[np.ndarray, np.ndarray]:
    """Fit prompt + output into `max_seq`: the output is cut first; a
    prompt too long to leave `len_min` output tokens is cut to do so."""
    prompt = np.minimum(prompt, max_seq - len_min)
    output = np.minimum(output, max_seq - prompt)
    return prompt, output


@dataclasses.dataclass(frozen=True)
class Req:
    rid: int
    due: float            # seconds after the window opens
    prompt: np.ndarray    # int32 token ids
    output_len: int
    ttft: float
    tds: float


def generate(mix: dict, *, rate: float, seconds: float, seed: int,
             vocab: int, max_seq: int) -> List[Req]:
    """The requests due in a window of `seconds` at `rate` req/s."""
    pool = np.random.default_rng(int(mix["pool_seed"]))
    # gaps until the window is covered: the same count on every seed
    gaps: List[float] = []
    draw = {"poisson": lambda k: np.diff(poisson_arrivals(rate, k, pool),
                                         prepend=0.0),
            "gamma": lambda k: np.diff(gamma_arrivals(
                rate, k, pool, cv=float(mix["cv"])), prepend=0.0)}
    kind = mix["arrival"]
    if kind not in draw:
        raise ValueError(f"unknown arrival process {kind!r}")
    while sum(gaps) < seconds:
        gaps.extend(draw[kind](256).tolist())
    t = np.cumsum(gaps)
    n = int(np.searchsorted(t, seconds, side="left"))
    gaps_n = np.asarray(gaps[:n])
    len_min = int(mix["len_min"])
    prompt, output = sample_lengths(
        n, pool, tuple(mix["prompt_lognormal"]),
        tuple(mix["output_lognormal"]), len_min, int(mix["len_max"]))
    if "output_tokens" in mix:
        output = np.full(n, int(mix["output_tokens"]), np.int64)
    prompt, output = clip_to_context(prompt, output, max_seq, len_min)
    wpm = {"reading": READING_WPM, "voice": SPEAKING_WPM}[mix["qoe"]]
    tds = tds_trace(wpm, n, pool)

    rng = np.random.default_rng(int(seed))
    due = np.cumsum(gaps_n)
    return [Req(rid=i, due=float(due[i]),
                prompt=rng.integers(0, vocab, int(prompt[i])).astype(np.int32),
                output_len=int(output[i]), ttft=float(mix["ttft"]),
                tds=float(tds[i]))
            for i in range(n)]
