"""Real continuous-batching engine: runs an actual JAX model on device.

This is the integration target for the Andes scheduler — the same
Scheduler/FluidQoE/Request machinery as the simulator, but every decode
iteration executes the model's jitted ``decode_step`` against a static-slot
KV cache, prefills run the real prompt, preemption really moves cache
slices to host numpy (swap) or re-prefills (recompute), and tokens are
greedily sampled.

The clock is virtual by default (advanced by the LatencyModel per step) so
QoE specs in seconds are meaningful on a CPU container and tests are
deterministic.

``clock="wall"`` runs in *real time*: ``self.now`` is a
``time.monotonic()`` reading relative to ``reset()``, and idle engines
sleep until the next arrival instead of jumping the clock. How the time
between readings passes depends on the device the params live on
(``wall_clock_measures``), decided once at construction:

* **Paced, on the CPU.** The CPU's HardwareSpec is a stand-in
  (``hardware_for_device``), so the engine runs on the LatencyModel's
  schedule: every ``_tick(dt)`` sleeps off whatever part of ``dt`` the
  host computation didn't already consume, then stamps a reading. A CPU
  smoke model then behaves like the chip it stands in for.
* **Measured, on an accelerator.** The device sets its own pace:
  ``_tick`` only takes a reading and never sleeps, and every token's time
  is a reading taken after the host sync that delivered it (``_sync``
  refreshes the clock; a staged prefill's first token is stamped after
  the flush). The LatencyModel still prices operations for the
  scheduler; it no longer sets when anything happens.

Either way every recorded timestamp carries genuine OS scheduling
jitter and whatever the host stole. Consequences, by design:

* **Token text is identical** to the virtual-clock run of the same trace:
  the clock only decides *when* things happen, never *what* is computed —
  per-slot decode is row-independent and swap preemption is exact — so as
  long as admission order is preserved the emitted ids match 1:1
  (tests/test_tolerance.py pins this; the CI server smoke re-asserts it
  over a real socket).
* **Timestamps are NOT bit-exact**, so paced runs are validated by
  the tolerance-based differential harness (repro.serving.tolerance):
  TTFT/TDS/QoE distributions must agree with the virtual reference within
  stated tolerances. If the host cannot keep up with the modeled
  schedule, the drift shows up there — that is the harness *measuring*
  the gap, not a bug in the clock.
* ``run()`` takes real seconds: wall engines are for serving
  (repro.server), not sweeps.

The engine also serves as the oracle for validating the simulator
(tests/test_sim_vs_engine.py): same scheduler, same workload, same latency
model ⇒ near-identical scheduling traces.

Like the simulator, the engine is *steppable*: ``submit()`` enqueues
arrivals, ``step()`` executes one continuous-batching iteration (schedule
→ preempt → swap-in/prefill → decode), and ``result()``
snapshots a SimResult. ``run()`` is a thin loop over ``step()`` that
reproduces the pre-refactor batch loop bit-for-bit
(tests/test_engine_steppable.py holds a transcription of the legacy loop
as the differential oracle). This makes ServingEngine satisfy
``repro.cluster.replica.SteppableBackend`` verbatim, so real-model
replicas plug into the cluster layer unchanged.

Speculative decoding (``draft_model``/``spec_k``): each scheduled step a
small draft model greedily proposes ``k`` tokens per running request
(serving/speculative.py), the target verifies the whole window in one
``verify_step`` call, and the longest prefix matching the target's own
greedy argmax is committed plus the correction/bonus token — so every
request's emitted token sequence is *identical* to the non-speculative
engine's (lossless by construction; tests/test_speculative.py asserts it
trace-for-trace) while decode steps shrink by the acceptance factor. A
step emits a 1..k+1 token burst at one timestamp; FluidQoE.emit absorbs
it and the client-side pace_delivery smooths it back to the spec'd TDS,
which is precisely the paper's QoE machinery rewarding burst delivery.

Hot path (``HotpathConfig``, ON by default — PR 5)
--------------------------------------------------
Three optimizations make the loop run as fast as the hardware allows
without changing a single emitted token or timestamp (the four
differential suites run with them enabled):

* **Bucketed, batched prefill** (``prefill_buckets``): prompts are
  right-padded to a small geometric bucket grid (powers of two from
  ``bucket_min`` up past ``max_seq``) and driven through a jitted
  ``Model.prefill`` via its ``lengths`` masking, so prefill compile count
  is bounded by #length-buckets × #row-buckets instead of one compile per
  distinct prompt length. All requests admitted in the same ``step()``
  prefill together — grouped BY BUCKET, because a request's bucket must
  depend only on its own length for the batched call to stay bit-identical
  to the sequential batch-1 path the legacy oracle drives (row
  independence of the padded forward; pinned in tests/test_hotpath.py) —
  and land in their slots with one fused multi-row ``_write_slots``
  scatter instead of N separate dispatches. Virtual-clock bookkeeping
  (per-request prefill ticks, first-token emit times, KV accounting)
  is staged in admission order on the host, so timestamps are exactly
  the sequential path's. MoE models are excluded: expert capacity is
  proportional to the forward's TOTAL token count, padding included, so
  padded or batched prefill would change which tokens the capacity gate
  drops — MoE engines keep the eager exact-length path.

* **Fused on-device sampling** (``fused_sampling``): the jitted decode /
  verify entry points return argmax token ids ((slots,) int32) instead of
  ``(slots, vocab)`` logits, shrinking the per-iteration device→host
  transfer by a factor of vocab_size. The speculative accept-prefix scan
  (cumprod of proposal/greedy matches) moves on-device too, so one
  speculative iteration is ONE fused dispatch + ONE host sync
  (draft propose → window concat → target verify → argmax → accept counts)
  instead of two round-trips. Greedy ties break identically to the
  host-side argmax (first max wins) — the losslessness foundation.

* **Multi-step decode** (``multi_step`` = j_max): when the Andes selective
  trigger (§4.2 #1) is certifiably off for the whole window
  (``Scheduler.idle_steps`` projects the memory/latency triggers forward),
  every live request is decoding, no pending arrival (or driver ``until``
  bound) lands strictly inside the window, and no slot can finish inside
  it (output_len margin), the engine runs j decode iterations in one
  jitted ``lax.scan`` (``Model.decode_multi``) and commits j tokens per
  slot off a single host sync. Per-step virtual-clock emit timestamps are
  reconstructed EXACTLY: the clock is deterministic, so the commit loop
  replays the identical ``iter_latency(B, ctx)`` tick sequence (context
  grows by B per step) the one-step loop would have produced. j is
  quantized to powers of two so scan compile count stays bounded. EOS is
  unpredictable, so with ``eos_id`` enabled the scan may overshoot an
  end-of-sequence: committing stops exactly where the one-step baseline
  stops and the overshoot is discarded by the length gate
  (models/cache.py: attention never reads past ``length``) — which is why
  the EOS-enabled fast path is only legal on length-rollback-capable
  caches (``supports_length_rollback``; SSM/hybrid state cannot roll
  back, so those run multi-step only with EOS disabled, where the
  output_len margin makes overshoot impossible). Wall-clock engines
  (``clock="wall"``) cannot reconstruct per-step timestamps and always
  single-step.

``hotpath_stats()`` reports host syncs, prefill compile signatures, and
multi-step block counts — benchmarks/engine_hotpath.py gates the speedup
and compile-count claims on them — plus the seconds a wall-clock engine
slept to the LatencyModel and the bucketed prefill's real and padded
prompt tokens (exported on the server's ``GET /metrics``).

The engine opens named profiler spans (``repro.obs.spans``) around each
step, the scheduler's choice, every pacing sleep, each admission, each
prefill bucket group, each decode dispatch and each preemption; with no
profiler running each costs one no-op context manager.

Scale substrate (PR 8): chunked prefill + paged KV
--------------------------------------------------
Two knobs turn the 8-slot smoke engine into a 100x-scale serving
substrate (benchmarks/engine_hotpath.py --scale drives a 1000-request
heavy-tail trace through them):

* **Chunked prefill** (``prefill_chunk`` > 0): a prompt longer than the
  chunk size no longer monopolizes the device for one monolithic
  prefill. Admission commits only the first chunk; the request then
  holds its slot with a ``prefill_cursor`` and advances one chunk per
  scheduled iteration, interleaved with every other resident's decode
  tick — the §2.2 TTFT/TDS interference knob. The chunk-scheduling
  contract: a mid-prefill request is a RUNNING resident (the Andes
  knapsack prices it through ``QoEPricer.serve_delay`` by the chunks it
  still owes), it never joins the decode batch while its cursor is
  nonzero, KV charges grow chunk-by-chunk (page-granular when paged),
  and preemption either parks the committed prefix (swap; the cursor
  survives and chunking resumes after swap-in) or rewinds the cursor to
  zero (recompute). Each chunk recomputes the prefix at the cursor's
  bucket through the SAME jitted bucketed call the monolithic path
  uses, so the final chunk — full prompt length, full-length bucket —
  is bit-identical to the monolithic prefill: committed cache and first
  token match exactly (the differential oracle in
  tests/test_chunked_prefill.py), while the per-chunk
  ``LatencyModel.prefill_chunk_latency`` keeps its TTFT honest.
  Requires the bucketed prefill path (non-MoE) and ``spec_k=0``.

* **Paged KV** (``page_size``): ``KVSlotManager`` prices capacity as a
  pool of fixed-size pages with a block table per request
  (serving/kv_manager.py module docstring has the layout) —
  admission/`grow` charge whole pages, preemption returns partial
  pages, and the scheduler's capacity views round knapsack weights up
  to page multiples (``SchedulerConfig.page_size``, wired
  automatically). The device cache stays per-slot rows; pages govern
  accounting granularity. ``page_size=None`` (or >= max_seq) is the
  legacy fixed-depth manager bit-for-bit; ``page_size=1`` reproduces
  token-granular admission exactly (both pinned differentially in
  tests/test_paged_kv.py).

Physical paging + persistent decode loop
----------------------------------------
Two device-side follow-ons lift PR 8's host-side accounting onto the
accelerator (``tests/test_physical_paging.py`` / ``test_persistent_loop.py``):

* **Physical page pool** (``physical_pages``; auto-ON for paged,
  non-speculative engines over archs whose decode state is pure
  length-gated attention KV — ``cache_lib.supports_physical_paging``):
  the device cache becomes the pool layout of
  ``models/cache.py:init_paged_cache`` — ``k``/``v`` hold
  ``KVSlotManager.total_pages`` physical pages shared by all slots, and
  a ``block_tables`` leaf maps each slot's context onto the pages its
  manager-side block table names. The manager is now the ALLOCATOR, not
  just the accountant: ``evict_tail``/release free real HBM rows and
  admission capacity IS the physical pool. Decode routes through the
  pallas paged-attention kernel (kernels/paged_attention.py; gather
  resolved at DMA-issue time via scalar-prefetched tables), prefill
  commits scatter through ``paged_write_tokens``, and swap-out gathers
  a slot's pages back into one contiguous host row (identical bytes to
  the fixed-row slice, so swap accounting and the tolerance fingerprints
  carry over unchanged). Device tables re-upload lazily: the manager
  bumps a ``version`` on every page movement and the engine re-pins
  ``block_tables`` (pure data, no recompile) only when it changed.
  Every emitted token and timestamp is bit-identical to the
  accounting-only engine — the paged kernel's masked tiles contribute
  exact zeros — pinned at ``page_size=1`` and ``page_size >= max_seq``
  (the degenerate oracles) and at interior page sizes, both preemption
  modes. Because overdraft page ids name no physical row, the physical
  engine *pre-reserves* (``ensure_pages``) every page a decode block can
  write before dispatching and raises if the pool is exhausted — the
  scheduler watermark keeps certified demand under capacity, so this
  fires only on a genuinely over-admitting policy.

* **Persistent device decode loop** (``HotpathConfig.persistent``): the
  multi-step scan becomes a device-resident ``lax.while_loop``
  (``Model.decode_persistent``) whose iteration bound j is a *dynamic*
  scalar — ``Scheduler.idle_steps`` is the "how long may the device run
  unsupervised" certificate, and the loop runs until it expires or every
  live row hits EOS, committing the whole block off ONE host sync.
  Dynamic j means no power-of-two quantization (one compile per out-
  buffer depth serves every block size), so blocks are longer and host
  syncs strictly fewer than the PR 5 scan on the same trace, while the
  committed region replays the scan bit-for-bit (the while body IS the
  scan body; rows past the certificate are discarded by the length
  gate). With ``wall_multi_step`` a wall-clock engine (the HTTP server
  pump) runs j-step blocks too: emissions are stamped per step by
  `_tick` as always (paced on the CPU, all after the block's one sync
  on an accelerator), and a mid-block check breaks the commit early when a
  pending arrival lands so admission latency stays one iteration, not j
  — timestamps there are tolerance-gated (serving/tolerance.py), token
  text identical.
"""
from __future__ import annotations

import bisect
import dataclasses
import functools
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.latency_model import LatencyModel
from repro.core.qoe import FluidQoE
from repro.core.scheduler import Scheduler
from repro.models import cache as cache_lib
from repro.models.model import Model
from repro.obs.spans import span
from repro.serving.kv_manager import KVSlotManager
from repro.core.request import Request, ReqState
from repro.serving.simulator import SimResult
from repro.serving.speculative import DraftProposer, check_speculation_compatible


def wall_clock_measures(device) -> bool:
    """Whether a `clock="wall"` engine whose params live on `device` (a
    `jax.Device`) measures time rather than pacing to the LatencyModel. On
    the CPU the HardwareSpec is a stand-in (`hardware_for_device`), so the
    engine paces to it; an accelerator is its own clock."""
    return device.platform != "cpu"


@dataclasses.dataclass(frozen=True)
class HotpathConfig:
    """Engine hot-path optimization switches (module docstring; all
    lossless and ON by default — the benchmark baseline is
    ``HotpathConfig.baseline()``)."""
    prefill_buckets: bool = True    # bucketed/batched/jitted prefill
    bucket_min: int = 16            # smallest prompt-length bucket
    fused_sampling: bool = True     # on-device argmax (+ spec accept scan)
    multi_step: int = 8             # max decode iters per dispatch (1 = off)
    persistent: bool = True         # fused blocks via the device-resident
                                    # while_loop (dynamic, unquantized j)
                                    # instead of the static-j scan
    wall_multi_step: bool = True    # let wall-clock engines run fused
                                    # blocks (length-rollback archs only;
                                    # timestamps tolerance-gated)

    @staticmethod
    def baseline() -> "HotpathConfig":
        """The pre-PR-5 hot path: eager exact-length batch-1 prefill,
        full-logit host argmax, one decode iteration per dispatch."""
        return HotpathConfig(prefill_buckets=False, fused_sampling=False,
                             multi_step=1)


def _slot_axis(leaf_ndim: int) -> int:
    return 0 if leaf_ndim == 1 else 1   # length (B,) vs (L, B, ...)


@functools.partial(jax.jit, static_argnames=("slot",))
def _write_slot(cache, src, slot):
    """Insert batch-1 `src` pytree into `cache` at batch slot `slot`."""
    def ins(c, s):
        ax = _slot_axis(c.ndim)
        idx = [slice(None)] * c.ndim
        idx[ax] = slot
        return c.at[tuple(idx)].set(jnp.squeeze(s, ax).astype(c.dtype))
    return jax.tree.map(ins, cache, src)


@jax.jit
def _write_slots(cache, src, slots):
    """Insert an N-row `src` pytree into `cache` at batch slots `slots`
    ((N,) int32) — ONE fused scatter per leaf instead of N dispatches.
    Rows whose slot id is out of range (row-bucket padding uses
    num_slots as the sentinel) are dropped by the scatter."""
    def ins(c, s):
        ax = _slot_axis(c.ndim)
        cm = jnp.moveaxis(c, ax, 0)
        sm = jnp.moveaxis(s, ax, 0).astype(c.dtype)
        return jnp.moveaxis(cm.at[slots].set(sm, mode="drop"), 0, ax)
    return jax.tree.map(ins, cache, src)


@functools.partial(jax.jit, static_argnames=("slot",))
def _read_slot(cache, slot):
    def rd(c):
        ax = _slot_axis(c.ndim)
        return jax.lax.index_in_dim(c, slot, ax, keepdims=True)
    return jax.tree.map(rd, cache)


@jax.jit
def _paged_commit(cache, bt_rows, starts, k_seg, v_seg, counts):
    """Scatter contiguous k/v token segments into the physical page pool
    (the paged image of `_write_slots`): row i of the segs holds
    `counts[i]` tokens landing at absolute positions starts[i].. through
    the pages named by bt_rows[i]. Sentinel-routed positions drop, so
    padding rows (all-sentinel table row, count 0) are free."""
    return dict(
        cache,
        k=cache_lib.paged_write_tokens(
            cache["k"], bt_rows, starts, k_seg, counts),
        v=cache_lib.paged_write_tokens(
            cache["v"], bt_rows, starts, v_seg, counts),
    )


@functools.partial(jax.jit, static_argnames=("max_seq",))
def _paged_read_row(cache, table_row, slot, *, max_seq):
    """Gather one slot's pages back into a contiguous cache row — the
    paged image of `_read_slot`, same leaf shapes/bytes, so swap
    accounting and restore are layout-blind."""
    return {
        "length": cache["length"][slot][None],
        "k": cache_lib.paged_gather_rows(cache["k"], table_row, max_seq),
        "v": cache_lib.paged_gather_rows(cache["v"], table_row, max_seq),
    }


class BucketedPrefill:
    """Jitted, shape-bucketed prefill front-end for one model.

    Pads a group of prompts (all mapping to the same length bucket —
    the caller groups) to (row_bucket, len_bucket), runs one jitted
    ``Model.prefill`` with per-row ``lengths`` masking, takes the
    first-token argmax on device, and returns (first_ids (N,), cache rows)
    for a fused `_write_slots` scatter. Compile count is bounded by
    #length-buckets × #row-buckets; `shapes_seen` records the signatures
    actually compiled (the compile-count regression gate)."""

    def __init__(self, model: Model, cache_seq: int, cache_dtype, *,
                 max_seq: int, bucket_min: int = 16):
        self.model = model
        self.cache_seq = cache_seq
        self.cache_dtype = cache_dtype
        self.enc_seq = model.enc_seq(max_seq)
        # geometric (x2) grid from bucket_min; the terminal bucket is
        # clamped to the physical cache depth (prefill writes the padded
        # rows with dynamic_update_slice, which must fit) and still covers
        # max_seq because cache_seq >= max_seq always
        self.buckets: List[int] = []
        b = max(2, int(bucket_min))
        while b < max_seq and b < cache_seq:
            self.buckets.append(b)
            b *= 2
        self.buckets.append(min(b, cache_seq))
        self.shapes_seen = set()        # (rows, len_bucket) jit signatures
        self.on_compile = None          # optional fn(key) on new signature
        self.tokens_real = 0            # prompt tokens prefilled
        self.tokens_padded = 0          # rows x bucket of the device calls
        self._jit = jax.jit(self._call)

    def note_shape(self, key) -> None:
        """Record a jit signature entering the compile cache (fires the
        observability callback exactly once per new shape)."""
        if key not in self.shapes_seen:
            self.shapes_seen.add(key)
            if self.on_compile is not None:
                self.on_compile(key)

    def bucket(self, n: int) -> int:
        for b in self.buckets:
            if n <= b:
                return b
        return self.buckets[-1]

    @staticmethod
    def row_bucket(n: int) -> int:
        b = 1
        while b < n:
            b *= 2
        return b

    def _call(self, params, tokens, lengths, frames):
        cache = self.model.init_cache(
            tokens.shape[0], self.cache_seq, enc_seq=self.enc_seq,
            dtype=self.cache_dtype,
        )
        batch = {"tokens": tokens, "lengths": lengths}
        if self.enc_seq:
            batch["frames"] = frames
        logits, cache = self.model.prefill(params, batch, cache)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache

    def prefill_into(self, params, cache, slots, toks_list,
                     frames_list=None, *, need_first=True, write=None,
                     rids=None):
        """Grouped flush: prefill every (slot, tokens) pair and scatter the
        rows into `cache` — one padded multi-row call + one fused
        `_write_slots` per bucket group (grouping BY BUCKET keeps each
        row bit-identical to its own batch-1 call). The single flush
        implementation shared by the engine's admission path and the
        draft proposer's cache build. Returns (cache', first_ids (N,)
        int32 aligned with the inputs — zeros when need_first=False,
        which also skips the device→host fetch — the number of
        device→host sync rounds performed, and the number of bucket
        groups dispatched). `write` overrides the slot-row scatter (the
        physically paged engine passes its page-pool committer; rows map
        to slots via the same padded (N,) id array, sentinel=num_slots).
        `rids`, aligned with the inputs, only label each group's
        `prefill.group` span."""
        if write is None:
            write = lambda c, s, pad: _write_slots(c, s, jnp.asarray(pad))
        groups: dict = {}
        for i, t in enumerate(toks_list):
            groups.setdefault(self.bucket(len(t)), []).append(i)
        first_out = np.zeros(len(toks_list), np.int32)
        oob = cache["length"].shape[0]          # row-pad scatter sentinel
        syncs = 0
        for bucket in sorted(groups):
            idxs = groups[bucket]
            rows = self.row_bucket(len(idxs))
            real = sum(len(toks_list[i]) for i in idxs)
            self.tokens_real += real
            self.tokens_padded += rows * bucket
            label = " ".join(str(rids[i]) for i in idxs) if rids else ""
            with span("prefill.group", rows=rows, bucket=bucket, real=real,
                      rids=label):
                first, src = self.run(
                    params, [toks_list[i] for i in idxs],
                    [frames_list[i] for i in idxs] if frames_list else None,
                )
                pad = np.full((rows,), oob, np.int32)
                pad[: len(idxs)] = [slots[i] for i in idxs]
                cache = write(cache, src, pad)
                if need_first:
                    first = np.asarray(first)
                    syncs += 1
                    for j, i in enumerate(idxs):
                        first_out[i] = first[j]
        return cache, first_out, syncs, len(groups)

    def run(self, params, toks_list, frames_list=None):
        """Prefill one same-bucket group. toks_list: per-request token
        arrays; returns (first_ids np (N,), padded cache rows)."""
        n = len(toks_list)
        rows = self.row_bucket(n)
        seq = self.bucket(max(len(t) for t in toks_list))
        tokens = np.zeros((rows, seq), np.int32)
        lengths = np.zeros((rows,), np.int32)
        for i, t in enumerate(toks_list):
            tokens[i, : len(t)] = t
            lengths[i] = len(t)
        frames = 0
        if self.enc_seq:
            d = self.model.cfg.d_model
            frames = np.zeros((rows, self.enc_seq, d), np.float32)
            for i in range(n):
                f = frames_list[i] if frames_list else None
                if f is not None:
                    frames[i] = f
        self.note_shape((rows, seq))
        first, cache = self._jit(
            params, jnp.asarray(tokens), jnp.asarray(lengths),
            jnp.asarray(frames) if self.enc_seq else None,
        )
        return first, cache


def _admit_span(r: Request):
    """The `engine.admit` span of one admission."""
    return span("engine.admit", rid=r.rid, tokens=r.context_len)


@dataclasses.dataclass
class _StagedPrefill:
    """One admission whose host bookkeeping is done but whose device work
    (prefill + slot write + first-token id) is deferred to the batched
    flush. `emits`: the prefill emits the first token (False for recompute
    resumes). `emit_t` is its timestamp: the already-ticked time on a
    modeled clock, None until the flush's sync on a measured one."""
    req: Request
    slot: int
    toks: np.ndarray
    emits: bool
    emit_t: Optional[float]
    frames: Optional[np.ndarray] = None


class ServingEngine:
    """Real continuous-batching engine over a jitted JAX model.

    Incremental API (used by the cluster layer's `Replica`, identical to
    ServingSimulator's):
      submit(req)  enqueue an arrival (any time, in any order)
      step()       one scheduling+decode iteration; False when out of work
                   (`until=t` bounds the multi-step fast path so the clock
                   crosses t at the same single iteration it would when
                   single-stepping — what Replica.advance_to passes)
      has_work     pending or live requests remain
      result()     SimResult over every request ever submitted

    Batch API (classic single-node experiments):
      run(workload)  submit all + step to completion
    """

    def __init__(
        self,
        model: Model,
        params,
        scheduler: Scheduler,
        lat: LatencyModel,
        *,
        num_slots: int = 8,
        max_seq: int = 256,
        capacity_tokens: Optional[int] = None,
        preemption_mode: str = "swap",
        clock: str = "virtual",
        eos_id: int = -1,
        cache_dtype=jnp.float32,
        draft_model: Optional[Model] = None,
        draft_params=None,
        spec_k: int = 0,
        hotpath: Optional[HotpathConfig] = None,
        prefill_chunk: int = 0,
        page_size: Optional[int] = None,
        physical_pages: Optional[bool] = None,
    ):
        self.model = model
        self.params = params
        self.sched = scheduler
        self.lat = lat
        self.preemption_mode = preemption_mode
        self.clock = clock
        # a wall clock measures time on an accelerator and paces to the
        # LatencyModel on the CPU, by the device the params live on
        self.measured = clock == "wall" and wall_clock_measures(
            next(iter(jax.tree.leaves(params)[0].devices())))
        if self.measured:
            self._tick = self._read_clock
        self.eos_id = eos_id
        self.hotpath = hotpath if hotpath is not None else HotpathConfig()
        # observability (repro.obs): `self.obs` is the effective observer
        # (None = off; every instrumentation point guards on that) composed
        # from an installed Observer and/or a legacy `event_sink` callable
        # (deprecated; wrapped in EventSinkAdapter). Survives reset() so
        # run() keeps reporting to installed consumers.
        self._observer = None
        self._event_sink = None
        self.obs = None
        self.max_seq = max_seq
        self._num_slots = num_slots
        self._capacity_tokens = capacity_tokens
        # EOS-enabled multi-step may overshoot and roll back by length —
        # only legal on length-gated caches (models/cache.py)
        self._rollback_ok = cache_lib.supports_length_rollback(model.cfg)

        # ---- speculative decoding (optional) --------------------------
        self.spec_k = int(spec_k)
        # a verify window writes up to k+1 positions past a slot's
        # committed context; pad the *physical* cache by that much so the
        # writes never hit the dynamic_update_slice index clamp (which
        # would silently corrupt position max_seq-1 for requests ending
        # within k tokens of the boundary — breaking the lossless gate).
        # max_seq stays the logical per-request bound: emission is capped
        # at it and KV accounting never counts the slack.
        self._cache_seq = max_seq + (self.spec_k + 1 if self.spec_k else 0)
        if self.spec_k:
            if draft_model is None or draft_params is None:
                raise ValueError("spec_k > 0 requires draft_model/draft_params")
            check_speculation_compatible(model, draft_model)
            self.draft = DraftProposer(
                draft_model, draft_params, num_slots=num_slots,
                max_seq=self._cache_seq, cache_dtype=cache_dtype,
                bucketed=(BucketedPrefill(
                    draft_model, self._cache_seq, cache_dtype,
                    max_seq=max_seq, bucket_min=self.hotpath.bucket_min,
                ) if self.hotpath.prefill_buckets else None),
            )
            self._verify = jax.jit(model.verify_step)
            self._spec_fused = self._make_spec_fused()
            self._spec_block = self._make_spec_block()
        else:
            self.draft = None

        # ---- physical paging (PR 10): real device page pool ------------
        # page-granular *accounting* (PR 8) is `paged`; backing the block
        # tables with a physical pool is opt-in by capability: any paged,
        # non-speculative engine whose model family supports it (dense/
        # vlm/moe — the cache is a plain k/v pytree). `physical_pages`
        # overrides: True forces it (raising when unsupported, so callers
        # cannot silently fall back to accounting-only), False forces the
        # contiguous cache (the accounting-only differential baseline).
        paged = page_size is not None and 0 < int(page_size) < max_seq
        if physical_pages is None:
            physical_pages = (paged and not self.spec_k
                              and model.supports_physical_paging())
        elif physical_pages:
            if not paged:
                raise ValueError(
                    "physical_pages=True requires a paged engine "
                    "(0 < page_size < max_seq)")
            if self.spec_k:
                raise ValueError(
                    "physical_pages=True is incompatible with speculative "
                    "decoding (verify windows write past the block table)")
            if not model.supports_physical_paging():
                raise ValueError(
                    f"model kind {model.cfg.kind!r} does not support a "
                    "physically paged KV cache")
        self.physical_pages = bool(physical_pages)
        if self.physical_pages:
            # pool geometry mirrors KVSlotManager exactly: the physical
            # pool IS the admission capacity — every page id the manager
            # hands out names a real device row
            cap_tokens = capacity_tokens or num_slots * max_seq
            self._pool_pages = -(-cap_tokens // page_size)
            self._max_pages = -(-self._cache_seq // page_size)
            self.cache = model.init_paged_cache(
                num_slots, self._pool_pages, page_size, self._cache_seq,
                dtype=cache_dtype,
            )
        else:
            self._pool_pages = 0
            self._max_pages = 0
            self.cache = model.init_cache(
                num_slots, self._cache_seq, enc_seq=model.enc_seq(max_seq),
                dtype=cache_dtype
            )
        # a decode step rewrites the cache it is given: donating it lets the
        # step update it in place instead of copying it whole into a new one
        self._decode = jax.jit(model.decode_step, donate_argnums=(2,))
        self._decode_tok = jax.jit(model.decode_tokens, donate_argnums=(2,))
        self._decode_multi = jax.jit(model.decode_multi,
                                     static_argnames=("j",))
        self._decode_persist = jax.jit(model.decode_persistent,
                                       static_argnames=("j_cap", "eos_id"))
        self._prefill = BucketedPrefill(
            model, self._cache_seq, cache_dtype, max_seq=max_seq,
            bucket_min=self.hotpath.bucket_min,
        )
        # MoE expert capacity is proportional to the TOTAL token count of
        # the forward (padding included), so padding a prompt — or batching
        # it with others — changes which tokens the capacity gate drops:
        # bucketed prefill cannot be exact there. MoE engines keep the
        # eager exact-length path (tests/test_hotpath.py pins the
        # exclusion); every other family buckets and batches.
        self._prefill_bucketable = model.cfg.kind != "moe"
        # ---- scale substrate: chunked prefill + paged KV (PR 8) --------
        self.prefill_chunk = int(prefill_chunk)
        self._page_size = page_size
        if self.prefill_chunk:
            if self.spec_k:
                raise ValueError("chunked prefill requires spec_k=0")
            if not (self.hotpath.prefill_buckets
                    and self._prefill_bucketable):
                raise ValueError(
                    "chunked prefill requires the bucketed prefill path "
                    "(hotpath.prefill_buckets=True, non-MoE model)")
        self.reset()
        # scheduler capacity/pricing views follow the engine's granularity
        # (only when the caller hasn't configured them explicitly)
        if self.kv.paged and not self.sched.cfg.page_size:
            self.sched.cfg.page_size = self.kv.page_size
        if self.prefill_chunk and not self.sched.cfg.prefill_chunk:
            self.sched.cfg.prefill_chunk = self.prefill_chunk

    # ------------------------------------------------------------------ state
    def reset(self) -> None:
        """Clear all serving state (the device cache pytree is reused; live
        slots are always re-written at prefill/swap-in time). The
        KVSlotManager object is reused too — cleared in place — so gauges
        bound to `engine.kv` (repro.obs.metrics.register_backend_gauges)
        stay valid across run()/reset() cycles."""
        if getattr(self, "kv", None) is None:
            self.kv = KVSlotManager(self._num_slots, self.max_seq,
                                    self._capacity_tokens,
                                    burst_reserve=(self.spec_k + 1
                                                   if self.spec_k else 0),
                                    page_size=self._page_size)
        else:
            self.kv.reset()
        self.sched.reset()           # policy state (counters, orders)
        self.fluid = FluidQoE()
        self.spec_steps = 0          # verify iterations executed
        self.spec_proposed = 0       # draft tokens proposed per verify (k each)
        self.spec_accepted = 0       # draft tokens accepted by the target
        if hasattr(self.lat, "reset"):
            self.lat.reset()         # speculative acceptance EMA -> prior
        self.now = 0.0
        self.slot_req: Dict[int, Request] = {}
        self.preemptions = 0
        self.total_tokens = 0
        self.iterations = 0
        self.batch_sizes: List[int] = []
        self._pending: List[Request] = []    # sorted arrivals; admitted
        self._pending_pos = 0                #   prefix tracked by cursor
        self.live: List[Request] = []
        self.seen: List[Request] = []        # submit order
        self.stuck = False                   # deadlocked (cleared by submit)
        self.host_syncs = 0                  # device→host transfer rounds
        self.dispatches = 0                  # device computation launches
        self.multi_step_blocks = 0           # fused multi-iteration dispatches
        self.multi_step_iters = 0            # iterations committed by them
        self.persistent_blocks = 0           # of which: device while_loop blocks
        self.persistent_iters = 0            # device loop iterations executed
        self.page_gathers = 0                # pool→contiguous row gathers (swap)
        self.page_scatters = 0               # contiguous→pool scatters (commits)
        self.page_gather_bytes = 0           # bytes moved by those gathers
        self.pace_seconds = 0.0              # wall seconds slept in _tick
        self._prefill.tokens_real = 0
        self._prefill.tokens_padded = 0
        # device block tables are re-uploaded lazily: only when the page
        # assignment edition (kv.version) moved since the last upload
        self._kv_version_seen = -1
        self._bt_host = None
        self._wall0 = time.monotonic()

    # ------------------------------------------------------------ observers
    @property
    def observer(self):
        """Installed Observer (repro.obs); None = observability off."""
        return self._observer

    @observer.setter
    def observer(self, obs) -> None:
        self._observer = obs
        self._rewire_obs()

    @property
    def event_sink(self):
        """Legacy lifecycle callable `sink(kind, req, t, k)` (deprecated;
        kept as an EventSinkAdapter shim — prefer `observer`)."""
        return self._event_sink

    @event_sink.setter
    def event_sink(self, sink) -> None:
        self._event_sink = sink
        self._rewire_obs()

    def set_observer(self, obs) -> None:
        self.observer = obs

    def attach_observer(self, obs) -> None:
        """Add `obs` alongside any already-installed observer."""
        from repro.obs.observer import compose
        self.observer = compose(self._observer, obs)

    def _rewire_obs(self) -> None:
        from repro.obs.observer import EventSinkAdapter, compose
        sink_obs = (EventSinkAdapter(self._event_sink)
                    if self._event_sink is not None else None)
        self.obs = compose(self._observer, sink_obs)
        self.sched.obs = self.obs
        obs = self.obs
        cb = ((lambda key: obs.jit_compile(self.now, key))
              if obs is not None else None)
        self._prefill.on_compile = cb
        if self.spec_k and self.draft.bucketed is not None:
            self.draft.bucketed.on_compile = cb

    def _sync(self, n: int = 1) -> None:
        """Count host<->device synchronization rounds. A measured clock
        takes a reading here, so what the sync delivered is stamped at or
        after it."""
        if n:
            self.host_syncs += n
            if self.measured:
                self.now = self.wall_now()
            if self.obs is not None:
                self.obs.sync(self.now, n)

    def _dispatch(self, kind: str, n: int = 1) -> None:
        """Count device computation dispatches (model-forward launches;
        cheap metadata ops like `with_lengths` are not counted)."""
        if n:
            self.dispatches += n
            if self.obs is not None:
                self.obs.dispatch(self.now, kind, n)

    # ------------------------------------------------------ physical paging
    def _refresh_block_tables(self) -> None:
        """Re-pin the device block tables to the manager's current page
        assignment — a no-op unless pages moved since the last upload
        (kv.version gates it), so steady-state decode re-uploads nothing.

        The host mirror has num_slots+1 rows: row `slot` holds that slot's
        table (sentinel = pool size past its end — scatters drop, gathers
        clamp under the length mask), and the extra all-sentinel last row
        is the scatter target for padding rows in grouped prefills. Rows
        of slots that do not currently own a table are all-sentinel too,
        so a garbage decode write from an inactive batch lane drops
        instead of landing in a page some other slot now owns.

        Raises RuntimeError on overdraft ids (>= pool size): in physical
        mode those name no device row, and clamping them would alias a
        real page. The admission watermark (policies/andes.py) keeps a
        certified engine below the pool, so this firing means the policy
        overcommitted physical memory."""
        if not self.physical_pages or self.kv.version == self._kv_version_seen:
            return
        P = self._pool_pages
        bt = np.full((self.kv.num_slots + 1, self._max_pages), P, np.int32)
        for rid, table in self.kv.block_table.items():
            slot = self.kv.slot_of.get(rid)
            if slot is None:
                continue
            if table and max(table) >= P:
                raise RuntimeError(
                    f"physical page pool overdrawn (page id {max(table)} "
                    f">= pool size {P}): the scheduler admitted more "
                    "context than the device pool holds")
            if len(table) > self._max_pages:
                raise RuntimeError(
                    f"request {rid} holds {len(table)} pages but a slot "
                    f"spans at most {self._max_pages} "
                    f"(max_seq={self.max_seq}): its prompt_len + "
                    "output_len exceeds the engine's context budget — the "
                    "contiguous layout silently clamps such overflow "
                    "writes; the physical pool refuses it")
            bt[slot, : len(table)] = table
        self._bt_host = bt
        self.cache = cache_lib.with_block_tables(self.cache, bt[:-1])
        self._kv_version_seen = self.kv.version

    def _paged_writer(self, cache, src, pad):
        """Scatter a contiguous prefill result `src` (rows of k/v planes
        plus lengths) into the page pool — the paged image of
        `_write_slots`. `pad` maps rows to slots exactly as the contiguous
        path's scatter does (sentinel = num_slots → the all-sentinel extra
        block-table row → every write drops). Chunked prefill recomputes
        the whole prefix each chunk, so starts are always 0 and counts the
        committed length."""
        rows = np.asarray(pad, np.int32)
        bt_rows = jnp.asarray(self._bt_host[rows])
        # counts = length + 1: the contiguous path writes the FULL padded
        # row, and the one junk position a fresh request ever attends is
        # index `prompt` (its first emitted token's KV is never written —
        # the decode window reaches it from the first iteration on). The
        # +1 copies that position's contiguous content; rows whose page
        # coverage stops at `length` (recompute resumes) route it to the
        # sentinel and drop, exactly where the extra position is
        # overwritten in-step by the next decode anyway.
        counts = src["length"].astype(jnp.int32) + 1
        starts = jnp.zeros_like(counts)
        self.page_scatters += 1
        return _paged_commit(cache, bt_rows, starts,
                             src["k"], src["v"], counts)

    def submit(self, req: Request) -> None:
        """Enqueue an arrival. Stable insert keeps equal-arrival order
        (bisect_right above the admitted-prefix cursor — identical order
        to the old insort-into-a-popped-list, without its O(n²) drain)."""
        i = bisect.bisect_right(self._pending, req.arrival,
                                lo=self._pending_pos,
                                key=lambda r: r.arrival)
        self._pending.insert(i, req)
        self.seen.append(req)
        if self.obs is not None:
            self.obs.submit(req, req.arrival)
        # a new arrival may change the scheduler's choice even if the
        # current live set deadlocked — try again
        self.stuck = False

    def cancel(self, rid: int) -> bool:
        """Abort a request by rid (client disconnect / explicit cancel).

        The request is finalized immediately with whatever it has emitted:
        marked ``cancelled`` + FINISHED, its KV slot (or parked host swap
        slices) freed, and the scheduler notified — so the next step()'s
        knapsack prices the freed memory. Safe in any state; returns False
        if the rid is unknown or already finished (cancel racing normal
        completion is expected with live clients and must be a no-op)."""
        t = self.wall_now()
        for i in range(self._pending_pos, len(self._pending)):
            r = self._pending[i]
            if r.rid == rid:
                # never admitted: no fluid slot, scheduler never saw it
                del self._pending[i]
                r.cancelled = True
                r.state = ReqState.FINISHED
                r.finish_time = t
                if self.obs is not None:
                    self.obs.cancel(r, t)
                return True
        for r in self.live:
            if r.rid == rid:
                if r.state == ReqState.RUNNING:
                    slot = r.engine_slot
                    self.kv.release(r)
                    self.slot_req.pop(slot, None)
                elif r.state == ReqState.SWAPPED:
                    self.kv.host_store.pop(r.rid, None)
                    self.kv.draft_store.pop(r.rid, None)
                r.cancelled = True
                r.state = ReqState.FINISHED
                r.finish_time = t
                r.prefill_cursor = 0
                self.sched.on_request_finish(r)
                self.live = [x for x in self.live if x is not r]
                self.stuck = False   # freed memory may unblock the rest
                if self.obs is not None:
                    self.obs.cancel(r, t)
                return True
        return False

    @property
    def pending(self) -> List[Request]:
        """Submitted-but-not-admitted requests (protocol view; the hot loop
        uses the cursor directly and never materializes this slice)."""
        return self._pending[self._pending_pos:]

    @property
    def has_work(self) -> bool:
        return self._pending_pos < len(self._pending) or bool(self.live)

    def hotpath_stats(self) -> dict:
        """Hot-path instrumentation (benchmarks/engine_hotpath.py)."""
        shapes = set(self._prefill.shapes_seen)
        if self.spec_k and self.draft.bucketed is not None:
            shapes |= self.draft.bucketed.shapes_seen
        return {
            "host_syncs": self.host_syncs,
            "dispatches": self.dispatches,
            "prefill_shapes": sorted(shapes),
            "prefill_compiles": len(shapes),
            "prefill_bucket_grid": list(self._prefill.buckets),
            "multi_step_blocks": self.multi_step_blocks,
            "multi_step_iters": self.multi_step_iters,
            "persistent_blocks": self.persistent_blocks,
            "persistent_iters": self.persistent_iters,
            "page_gathers": self.page_gathers,
            "page_scatters": self.page_scatters,
            "page_gather_bytes": self.page_gather_bytes,
            "pace_seconds_total": self.pace_seconds,
            "prefill_tokens_real_total": self._prefill.tokens_real,
            "prefill_tokens_padded_total": self._prefill.tokens_padded,
        }

    # ---------------------------------------------------------------- clock
    def _tick(self, seconds: float, op: str) -> None:
        """Advance the clock by one modeled operation of kind `op`
        (prefill, chunk, decode, swap, overhead).

        Virtual: now += seconds (deterministic). Wall, paced (the CPU):
        the operation's *deadline* is now + seconds; sleep off whatever the
        host's real computation left of it, then stamp a real monotonic
        reading — so the engine is paced to the LatencyModel schedule but
        timestamps carry true wall jitter. A host slower than the schedule
        never sleeps and simply drifts late (the tolerance harness
        measures it). Each sleep is an `engine.pace` span and adds to
        `pace_seconds`. Wall, measured (an accelerator): `_read_clock`
        takes this method's place at construction.
        """
        if self.clock == "virtual":
            self.now += seconds
        else:
            deadline = self.now + seconds
            w = time.monotonic() - self._wall0
            if deadline > w:
                with span("engine.pace", op=op, modeled_us=seconds * 1e6):
                    time.sleep(deadline - w)
                woke = time.monotonic() - self._wall0
                self.pace_seconds += woke - w
                w = woke
            self.now = w

    def _read_clock(self, seconds: float, op: str) -> None:
        """`_tick` on a measured clock: the device sets the pace, so the
        modeled `seconds` of `op` only priced it for the scheduler; the
        clock takes a fresh reading and nothing sleeps."""
        self.now = self.wall_now()

    def wall_now(self) -> float:
        """Current time on this engine's clock for *external* events
        (arrival stamping by a live frontend): a fresh monotonic reading
        in wall mode, `self.now` in virtual mode (where time only exists
        between steps)."""
        if self.clock == "virtual":
            return self.now
        return time.monotonic() - self._wall0

    # -------------------------------------------------------------- prefill
    def _prompt_tokens(self, r: Request) -> np.ndarray:
        """The tokens a prefill puts in the cache: the prompt (synthesized
        deterministically from the rid for token-less simulator-style
        requests) plus, on a recompute resume, every generated token but
        the last — the last is the next decode step's input, which writes
        it (the cache holds `context_len - 1` positions once a token is
        emitted; `_decode_active`)."""
        if r.prompt_tokens is None:
            rng = np.random.default_rng(r.rid)
            r.prompt_tokens = rng.integers(
                0, self.model.cfg.vocab_size, r.prompt_len
            ).astype(np.int32)
        return np.concatenate([
            np.asarray(r.prompt_tokens, np.int32),
            np.asarray(r.output_tokens[: max(r.generated - 1, 0)], np.int32),
        ])

    def _can_stage_prefill(self, r: Request) -> bool:
        """May this admission join the step's batched prefill? The staged
        flush defers only the first token's *value*; it must not be able
        to finish the request mid-admission (slot reuse), so EOS-enabled
        engines and single-token responses take the sequential path."""
        if not self.hotpath.prefill_buckets or not self._prefill_bucketable:
            return False
        return r.generated > 0 or (self.eos_id < 0 and r.output_len > 1)

    def _stage_prefill(self, r: Request) -> _StagedPrefill:
        """Host half of one admission: slot allocation, the prefill tick,
        and (for fresh requests) the first-token emission bookkeeping —
        everything the sequential path does except the token id itself,
        which `_flush_prefills` fills in after the batched device call.
        Clock/fluid/KV state is therefore bit-identical to sequential
        admission regardless of how many requests share the flush."""
        toks = self._prompt_tokens(r)
        slot = self.kv.allocate(r)
        self.slot_req[slot] = r
        self._tick(self.lat.prefill_latency(len(toks)), "prefill")
        if self.obs is not None:
            self.obs.prefill(r, self.now, len(toks))
        frames = getattr(r, "frames", None) if self._prefill.enc_seq else None
        return self._staged(r, slot, toks, frames)

    def _staged(self, r: Request, slot: int, toks: np.ndarray,
                frames) -> _StagedPrefill:
        """Stage a prefill that completes `r`'s context, counting its first
        token if it emits one. The token's time is `self.now` on a modeled
        clock; a measured clock stamps it after the flush's sync
        (`_stamp_first`)."""
        emits = r.generated == 0
        rec = _StagedPrefill(r, slot, toks, emits, None, frames)
        if emits:
            r.generated = 1
            self.kv.grow(r)
            self.total_tokens += 1
            if not self.measured:
                self._stamp_first(rec)
        return rec

    def _stamp_first(self, rec: _StagedPrefill) -> None:
        """Record a staged prefill's first token at `self.now`."""
        rec.emit_t = self.now
        rec.req.emit_times.append(self.now)
        self.fluid.emit(rec.req.fluid_idx, self.now, 1)

    # ------------------------------------------------------ chunked prefill
    def _should_chunk(self, r: Request) -> bool:
        """Route this admission through chunked prefill? Only prompts
        longer than one chunk, and only when the staged machinery applies
        (the same exclusions as `_can_stage_prefill`: the final chunk's
        first token must not be able to finish the request mid-flush)."""
        return (self.prefill_chunk > 0
                and r.context_len > self.prefill_chunk
                and self._can_stage_prefill(r))

    def _stage_chunk(self, r: Request) -> _StagedPrefill:
        """Advance one chunked prefill by one chunk: commit up to
        `prefill_chunk` more context tokens, stage the device recompute
        of the prefix at the new cursor's bucket (the same jitted
        bucketed call the monolithic path makes — so the FINAL chunk,
        whose prefix is the whole prompt, is bit-identical to monolithic
        prefill), and tick the per-chunk cost. On the final chunk the
        first-token bookkeeping fires exactly as `_stage_prefill`'s."""
        toks = self._prompt_tokens(r)
        total = len(toks)
        if r.prefill_cursor == 0:                  # admission: first chunk
            slot = self.kv.allocate(r, tokens=0)
            self.slot_req[slot] = r
        else:
            slot = r.engine_slot
        step = min(self.prefill_chunk, total - r.prefill_cursor)
        r.prefill_cursor += step
        self.kv.grow(r, step)
        self._tick(self.lat.prefill_chunk_latency(step, r.prefill_cursor),
                   "chunk")
        if self.obs is not None:
            self.obs.prefill_chunk(r, self.now, r.prefill_cursor, total)
        prefix = toks[: r.prefill_cursor]
        frames = getattr(r, "frames", None) if self._prefill.enc_seq else None
        if r.prefill_cursor < total:
            return _StagedPrefill(r, slot, prefix, False, None, frames)
        r.prefill_cursor = 0                       # final chunk
        if r.generated:
            # a recompute resume's prefix leaves out its pending input
            # token; charge it as every other resident's is charged
            self.kv.grow(r)
        if self.obs is not None:
            self.obs.prefill(r, self.now, total)
        return self._staged(r, slot, prefix, frames)

    def _flush_prefills(self, staged: List[_StagedPrefill]) -> None:
        """Run every staged admission's device work (the shared
        `BucketedPrefill.prefill_into` grouped flush). First-token
        emissions finalize in STAGED (admission) order, not group order,
        so event-sink consumers observe the same chronology the
        sequential path produces."""
        if not staged:
            return
        writer = None
        if self.physical_pages:
            # staging allocated/grew pages — pin the moved tables before
            # the grouped scatter lands in them
            self._refresh_block_tables()
            writer = self._paged_writer
        slots = [rec.slot for rec in staged]
        self.cache, first, syncs, n_groups = self._prefill.prefill_into(
            self.params, self.cache, slots,
            [rec.toks for rec in staged],
            [rec.frames for rec in staged],
            write=writer, rids=[rec.req.rid for rec in staged],
        )
        self._sync(syncs)
        self._dispatch("prefill", n_groups)
        self._dispatch("write", n_groups)
        if self.spec_k:
            # draft invariant: committed[:-1] — what the target's cache
            # holds too (`_prompt_tokens`)
            n_draft = self.draft.prefill_batch(
                slots, [rec.toks for rec in staged])
            self._dispatch("draft_prefill", n_draft)
            self._dispatch("write", n_draft)
        obs = self.obs
        for i, rec in enumerate(staged):
            if rec.emits:
                if rec.emit_t is None:
                    self._stamp_first(rec)
                rec.req.output_tokens.append(int(first[i]))
                if obs is not None:
                    obs.emit(rec.req, rec.emit_t, 1)

    def _prefill_request(self, r: Request) -> None:
        """Run the prompt (plus any generated prefix on recompute) —
        the sequential path: one request, one prefill, one slot write.
        With the hot path enabled this is the staged machinery applied to
        a single request (same bucketed jitted call the batched flush
        makes, so sequential ≡ batched bit-for-bit); the legacy eager
        exact-length path survives underneath as the benchmark baseline."""
        if self.hotpath.prefill_buckets and self._prefill_bucketable:
            # batch-1 through the bucketed jitted path (the EOS and
            # single-token fallback — cases `_can_stage_prefill` excludes
            # from multi-request flushes; MoE never reaches here)
            rec = self._stage_prefill(r)
            self._flush_prefills([rec])
            if rec.emits:
                # replay `_emit`'s done check, which the deferred-token
                # staging skips: the first token may finish the request
                tok = r.output_tokens[-1]
                if (r.generated >= r.output_len
                        or (self.eos_id >= 0 and tok == self.eos_id)):
                    self._finish(r)
            return
        toks = self._prompt_tokens(r)
        enc_seq = self.model.enc_seq(self.max_seq)
        kv_dtype = self.cache["k"].dtype if "k" in self.cache \
            else self.cache["ssm_conv"].dtype
        one = self.model.init_cache(
            1, self._cache_seq, enc_seq=enc_seq, dtype=kv_dtype
        )
        batch = {"tokens": jnp.asarray(toks)[None]}
        if self.model.cfg.kind in ("encdec", "audio"):
            frames = getattr(r, "frames", None)
            batch["frames"] = (jnp.asarray(frames)[None] if frames is not None
                               else jnp.zeros((1, enc_seq, self.model.cfg.d_model),
                                              jnp.float32))
        logits, one = self.model.prefill(self.params, batch, one)
        self._prefill.note_shape((1, len(toks)))        # exact-length compile
        self._dispatch("prefill")
        slot = self.kv.allocate(r)
        if self.physical_pages:
            if r.generated == 0:
                # own the page under position len(toks) now: the first
                # emitted token's KV never lands there, so the decode
                # window reads whatever this scatter leaves (zeros from
                # the scratch row — the contiguous path's content). The
                # emit below re-counts the token; grow is idempotent on
                # the already-taken page.
                self.kv.ensure_pages(r, len(toks) + 1)
            self._refresh_block_tables()
            self.page_scatters += 1
            self.cache = _paged_commit(
                self.cache, jnp.asarray(self._bt_host[[slot]]),
                jnp.zeros((1,), jnp.int32), one["k"], one["v"],
                jnp.asarray([len(toks) + 1], jnp.int32),
            )
        else:
            self.cache = _write_slot(self.cache, one, slot)
        self._dispatch("write")
        self.slot_req[slot] = r
        if self.spec_k:
            # the draft holds committed[:-1] (speculative.py invariant),
            # which is `toks`, the target's prefix too
            self.draft.prefill(slot, toks)
            self._dispatch("draft_prefill")
            self._dispatch("write")
        self._tick(self.lat.prefill_latency(len(toks)), "prefill")
        if self.obs is not None:
            self.obs.prefill(r, self.now, len(toks))
        if r.generated == 0:
            tok = int(jnp.argmax(logits[0]))
            self._sync()
            self._emit(r, tok)

    # ---------------------------------------------------------------- emit
    def _emit(self, r: Request, tok: int) -> None:
        r.output_tokens.append(tok)
        r.generated += 1
        r.emit_times.append(self.now)
        self.fluid.emit(r.fluid_idx, self.now, 1)
        self.kv.grow(r)
        self.total_tokens += 1
        if self.obs is not None:
            self.obs.emit(r, self.now, 1)
        done = (r.generated >= r.output_len
                or (self.eos_id >= 0 and tok == self.eos_id))
        if done:
            self._finish(r)

    def _emit_burst(self, r: Request, toks) -> int:
        """Commit a verify step's accepted tokens: all visible at self.now
        (one burst — FluidQoE.emit with k>1; pace_delivery re-smooths it
        client-side). Truncates at output_len / EOS exactly where the
        one-token-per-step baseline would have stopped. Returns the number
        actually emitted."""
        emitted = []
        for tok in toks:
            if r.generated >= r.output_len:
                break
            tok = int(tok)
            emitted.append(tok)
            r.output_tokens.append(tok)
            r.generated += 1
            r.emit_times.append(self.now)
            if self.eos_id >= 0 and tok == self.eos_id:
                break
        if emitted:
            self.fluid.emit(r.fluid_idx, self.now, len(emitted))
            self.kv.grow(r, len(emitted))
            self.total_tokens += len(emitted)
            if self.obs is not None:
                self.obs.emit(r, self.now, len(emitted))
        done = (r.generated >= r.output_len
                or (self.eos_id >= 0 and emitted and
                    emitted[-1] == self.eos_id))
        if done:
            self._finish(r)
        return len(emitted)

    def _finish(self, r: Request) -> None:
        r.state = ReqState.FINISHED
        r.finish_time = self.now
        self.sched.on_request_finish(r)
        slot = r.engine_slot
        self.kv.release(r)
        self.slot_req.pop(slot, None)
        if self.obs is not None:
            self.obs.finish(r, self.now)

    # ------------------------------------------------------------ preempt
    def _preempt(self, r: Request) -> None:
        r.preemptions += 1
        self.preemptions += 1
        slot = r.engine_slot
        if self.preemption_mode == "swap":
            self._dispatch("read")
            if self.physical_pages:
                # gather the victim's pages into a contiguous host row —
                # identical leaf shapes/bytes to the `_read_slot` slice,
                # so swap accounting and the restore path are layout-blind
                self._refresh_block_tables()
                host_slice = jax.device_get(_paged_read_row(
                    self.cache, jnp.asarray(self._bt_host[[slot]]), slot,
                    max_seq=self._cache_seq))
                self.page_gathers += 1
                self.page_gather_bytes += sum(
                    v.nbytes for k, v in host_slice.items() if k != "length")
            else:
                host_slice = jax.device_get(_read_slot(self.cache, slot))
            self._sync()
            draft_slice = self.draft.park(slot) if self.spec_k else None
            self.kv.swap_out(r, host_slice, draft_slice)
            r.state = ReqState.SWAPPED
            # a mid-prefill victim only moves its committed prefix (the
            # cursor survives; chunking resumes after swap-in)
            self._tick(self.lat.swap_latency(
                r.prefill_cursor or r.context_len), "swap")
        else:
            self.kv.drop(r)
            r.state = ReqState.WAITING
            r.prefilled = False
            r.prefill_cursor = 0        # recompute rewinds the chunk cursor
        self.slot_req.pop(slot, None)
        self.sched.record_preemptions(1)
        if self.obs is not None:
            self.obs.preempt(r, self.now, self.preemption_mode)

    def _swap_in(self, r: Request) -> None:
        host_slice = self.kv.swap_in(r)
        draft_slice = self.kv.swap_in_draft(r)
        slot = self.kv.allocate(r, tokens=(r.prefill_cursor or None))
        if self.physical_pages:
            # scatter the parked contiguous row into the freshly allocated
            # pages; counts = the committed context (mid-chunk victims
            # restore their cursor's prefix), exactly the page coverage
            # `allocate` just took
            self._refresh_block_tables()
            self.page_scatters += 1
            self.cache = _paged_commit(
                self.cache, jnp.asarray(self._bt_host[[slot]]),
                jnp.zeros((1,), jnp.int32),
                jnp.asarray(host_slice["k"]), jnp.asarray(host_slice["v"]),
                jnp.asarray([r.prefill_cursor or r.context_len], jnp.int32),
            )
        else:
            self.cache = _write_slot(
                self.cache, jax.tree.map(jnp.asarray, host_slice), slot
            )
        self._dispatch("write")
        if draft_slice is not None:
            self.draft.restore(slot, draft_slice)
            self._dispatch("write")
        self.slot_req[slot] = r
        r.state = ReqState.RUNNING
        self._tick(self.lat.swap_latency(r.prefill_cursor or r.context_len),
                   "swap")
        if self.obs is not None:
            self.obs.swap_in(r, self.now)

    # ------------------------------------------------------- speculative
    def _make_spec_fused(self):
        """One jitted dispatch for a whole speculative iteration: draft
        propose → window concat → target verify → greedy argmax → accepted
        prefix length (cumprod-of-matches scan) — all on device, so
        `_speculative_iteration` syncs exactly once and the transfer is
        three small int arrays instead of (slots, k+1, vocab) logits."""
        model, k = self.model, self.spec_k
        dmodel = self.draft.model

        def fn(params, dparams, tokens, target_cache, draft_cache):
            props, draft_cache = dmodel.propose_step(
                dparams, tokens, draft_cache, k
            )
            window = jnp.concatenate([tokens[:, None], props[:, :k]], axis=1)
            logits, target_cache = model.verify_step(
                params, window, target_cache
            )
            greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            match = (window[:, 1:] == greedy[:, :k]).astype(jnp.int32)
            accepted = jnp.sum(jnp.cumprod(match, axis=1), axis=1)
            return window, greedy, accepted, target_cache, draft_cache

        return jax.jit(fn)

    def _make_spec_block(self):
        """`_make_spec_fused`'s round, folded into a device-resident
        `lax.while_loop` over `s` verify rounds (multi-step INSIDE
        speculation; s is loop data, bounded by the static buffer cap).
        Each round re-pins both caches' length gates exactly as the host
        does between single rounds — both hold committed[:-1]
        (speculative.py invariant) — then advances the committed length by
        accepted+1 and feeds the correction/bonus token to the next
        round's draft. The host replays the per-round windows off ONE
        sync."""
        model, k = self.model, self.spec_k
        dmodel = self.draft.model

        def fn(params, dparams, tokens, lengths, tcache, dcache, s, *,
               s_cap):
            b = tokens.shape[0]

            def cond(c):
                return c[0] < s

            def body(c):
                r, tok, ln, tc, dc, W, G, A = c
                dc = dict(dc, length=ln)
                tc = dict(tc, length=ln)
                props, dc = dmodel.propose_step(dparams, tok, dc, k)
                window = jnp.concatenate([tok[:, None], props[:, :k]],
                                         axis=1)
                logits, tc = model.verify_step(params, window, tc)
                greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                match = (window[:, 1:] == greedy[:, :k]).astype(jnp.int32)
                accepted = jnp.sum(jnp.cumprod(match, axis=1), axis=1)
                nxt = jnp.take_along_axis(greedy, accepted[:, None],
                                          axis=1)[:, 0]
                return (r + 1, nxt, ln + accepted + 1, tc, dc,
                        W.at[r].set(window), G.at[r].set(greedy),
                        A.at[r].set(accepted))

            carry = (jnp.asarray(0, jnp.int32), tokens, lengths, tcache,
                     dcache,
                     jnp.zeros((s_cap, b, k + 1), jnp.int32),
                     jnp.zeros((s_cap, b, k + 1), jnp.int32),
                     jnp.zeros((s_cap, b), jnp.int32))
            c = jax.lax.while_loop(cond, body, carry)
            return c[5], c[6], c[7], c[3], c[4]

        return jax.jit(fn, static_argnames=("s_cap",))

    def _spec_block_plan(self, active) -> int:
        """Rounds of speculative verify that may run unsupervised in one
        device dispatch — the decode `_multi_step_plan` adapted to an
        acceptance-dependent clock. A round commits 1..k+1 tokens per
        slot, so the `idle_steps` certificate is spent in TOKENS (one
        round consumes up to k+1 of them) and the block is sized so
        neither output_len nor max_seq can truncate mid-block: what gets
        committed then depends on acceptance alone (EOS still truncates —
        the replay discards the tail and the length gates roll both
        caches back). The arrival/`until` bound is NOT precomputed here:
        round ticks depend on accepted context, so the commit replay
        breaks at the first crossing instead. Returns 1 when any
        condition fails."""
        cap = self.hotpath.multi_step
        if cap <= 1 or not self.hotpath.persistent:
            return 1
        if not self.hotpath.fused_sampling:
            return 1
        if self.clock != "virtual" and not self.hotpath.wall_multi_step:
            return 1
        if len(active) != len(self.live):
            return 1
        if not self._rollback_ok:
            return 1                    # discarded tails need the gate
        k1 = self.spec_k + 1
        s_max = min(
            cap,
            min((r.output_len - r.generated) // k1
                for r in active.values()),
            min((self.max_seq - r.context_len) // k1
                for r in active.values()),
        )
        if s_max < 2:
            return 1
        # the acceptance-dependent clock, folded into the certificate:
        # `idle_steps` projects the latency trigger at the CURRENT
        # acceptance EMA, but commits inside the block move the EMA — so
        # re-check the trigger at the EMA floor (expected_step_tokens→1,
        # i.e. per-token latency = full iter latency), which dominates
        # every acceptance trajectory the block can observe
        stiffest = max((r.spec.tds for r in active.values()), default=0.0)
        if stiffest > 0 and \
                self.lat.iter_latency(len(self.live)) > 1.0 / stiffest:
            return 1
        s_tok = self.sched.idle_steps(self.live, s_max * k1 - 1) + 1
        s_max = min(s_max, s_tok // k1)
        return s_max if s_max >= 2 else 1

    def _speculative_block(self, active, lengths, tokens, s: int,
                           until: Optional[float]) -> int:
        """Run up to `s` speculative verify rounds in one device-resident
        while_loop dispatch and replay the acceptance-dependent clock on
        the host off ONE sync: round r's tick is priced at the context the
        ledger reached after round r-1's commits — exactly the sequence
        single-round stepping produces. Returns rounds committed (< s when
        an EOS landed, a pending arrival came due, or the driver's `until`
        was crossed: the tail is discarded and both length gates roll the
        caches back)."""
        k = self.spec_k
        self.draft.cache = cache_lib.with_lengths(self.draft.cache, lengths)
        W, G, A, self.cache, self.draft.cache = self._spec_block(
            self.params, self.draft.params, jnp.asarray(tokens),
            jnp.asarray(lengths), self.cache, self.draft.cache,
            jnp.int32(s), s_cap=self.hotpath.multi_step)
        self._dispatch("spec_block")
        W, G, A = jax.device_get((W, G, A))     # ONE sync for s rounds
        self._sync()
        self.multi_step_blocks += 1
        self.persistent_blocks += 1
        items = list(active.items())
        b = len(items)
        committed = 0
        for rnd in range(s):
            if rnd:
                self.batch_sizes.append(b)
            ctx = sum(r.context_len for _slot, r in items)
            self._tick(self.lat.iter_latency(b, ctx), "decode")
            step_accepted = 0
            finished = False
            for slot, r in items:
                d, g = W[rnd, slot, 1:], G[rnd, slot]
                a = int(A[rnd, slot])
                m_safe = max(1, self.max_seq - r.context_len)
                toks = (list(d[:a]) + [int(g[a])])[:m_safe]
                self.spec_steps += 1
                self.spec_proposed += k
                self.spec_accepted += a
                step_accepted += a
                if hasattr(self.lat, "observe_acceptance"):
                    self.lat.observe_acceptance(a)
                self._emit_burst(r, toks)
                finished = finished or not r.is_live
            if self.obs is not None:
                self.obs.spec(self.now, k * b, step_accepted)
            committed += 1
            if committed < s:
                if finished:
                    break   # batch composition changes next round
                if (self._pending_pos < len(self._pending)
                        and self._pending[self._pending_pos].arrival
                        <= self.now):
                    break   # an arrival is waiting — the scheduler must
                            # see it at this iteration boundary
                if until is not None and not (self.now < until):
                    break   # incremental driver regains control
        self.multi_step_iters += committed
        self.persistent_iters += s
        self.sched.skip_iterations(committed - 1)
        if self.obs is not None:
            self.obs.multi_step(self.now, s, committed)
            self.obs.persistent_loop(self.now, s, s)
        return committed

    def _speculative_iteration(self, active, lengths, tokens,
                               total_ctx: int) -> None:
        """Draft-propose k tokens per running slot, verify the whole window
        in one target pass, commit the longest greedy-matching prefix plus
        the correction/bonus token (lossless; 1..k+1 tokens per step)."""
        k = self.spec_k
        # both caches hold committed[:-1] (speculative.py invariant); the
        # window's first input, the last committed token, lands at `lengths`
        if self.hotpath.fused_sampling:
            self.draft.cache = cache_lib.with_lengths(self.draft.cache,
                                                      lengths)
            window, greedy, accepted, self.cache, self.draft.cache = \
                self._spec_fused(self.params, self.draft.params,
                                 jnp.asarray(tokens), self.cache,
                                 self.draft.cache)
            self._dispatch("spec_fused")
            self._tick(self.lat.iter_latency(len(active), total_ctx), "decode")
            window, greedy, accepted = jax.device_get(
                (window, greedy, accepted)
            )
            self._sync()
        else:
            proposals = self.draft.propose(tokens, lengths, k)
            self._dispatch("propose")
            self._sync()
            window = np.concatenate([tokens[:, None], proposals], axis=1)
            logits, self.cache = self._verify(
                self.params, jnp.asarray(window), self.cache
            )
            self._dispatch("verify")
            # one step's cost: k+1 draft decodes + the fused verify (the
            # SpeculativeLatencyModel's iter_latency — same call as baseline)
            self._tick(self.lat.iter_latency(len(active), total_ctx), "decode")
            greedy = np.asarray(jnp.argmax(logits, axis=-1))  # (slots, k+1)
            self._sync()
            accepted = None
        step_accepted = 0
        for s, r in list(active.items()):
            d, g = window[s, 1:], greedy[s]
            if accepted is not None:
                a = int(accepted[s])
            else:
                a = 0
                while a < k and d[a] == g[a]:
                    a += 1
            # logical max_seq bound: the cache slack (_cache_seq) makes
            # every window position's logits well-defined, but committed
            # context must never exceed what a baseline engine could hold
            m_safe = max(1, self.max_seq - r.context_len)
            toks = (list(d[:a]) + [int(g[a])])[:m_safe]
            self.spec_steps += 1
            self.spec_proposed += k
            self.spec_accepted += a
            step_accepted += a
            if hasattr(self.lat, "observe_acceptance"):
                self.lat.observe_acceptance(a)
            self._emit_burst(r, toks)
        if self.obs is not None:
            self.obs.spec(self.now, k * len(active), step_accepted)

    def spec_stats(self) -> dict:
        """Acceptance-side counters (speculative engines only)."""
        return {
            "spec_k": self.spec_k,
            "spec_steps": self.spec_steps,
            "proposed": self.spec_proposed,
            "accepted": self.spec_accepted,
            "acceptance_rate": (self.spec_accepted / self.spec_proposed
                                if self.spec_proposed else 0.0),
        }

    # ------------------------------------------------------ multi-step decode
    def _multi_step_plan(self, active, total_ctx: int,
                         until: Optional[float]) -> int:
        """Largest j (quantized to a power of two) for which running j
        decode iterations in one fused scan is *provably* bit-identical to
        single-stepping — see the module docstring for the full invariant.
        Returns 1 whenever any condition fails."""
        cap = self.hotpath.multi_step
        if cap <= 1 or self.spec_k:
            return 1
        if self.clock != "virtual" and not (
                self.hotpath.wall_multi_step and self._rollback_ok):
            # wall-clock engines may fuse only when a mid-block arrival
            # can be honored by rolling back the uncommitted tail —
            # length-gated caches only (timestamps are tolerance-gated;
            # token ids stay exact either way: greedy decode rows are
            # batch-independent)
            return 1
        if len(active) != len(self.live):
            return 1                    # a waiting/swapped request needs
                                        # the per-iteration scheduler
        if self.eos_id >= 0 and not self._rollback_ok:
            return 1                    # overshoot would be unrecoverable
        margin = min(r.output_len - r.generated for r in active.values())
        j_max = min(cap, margin)
        if j_max < 2:
            return 1
        j_max = min(j_max, self.sched.idle_steps(self.live, j_max - 1) + 1)
        if j_max < 2:
            return 1
        # arrival/driver bound: every INTERMEDIATE step end must stay
        # strictly before the next pending arrival and the driver's
        # `until`, so admission lands at the same iteration boundary as
        # single-stepping (the block's last step may cross — that is
        # exactly the crossing iteration the baseline runs)
        bound = np.inf
        if self._pending_pos < len(self._pending):
            bound = self._pending[self._pending_pos].arrival
        if until is not None:
            bound = min(bound, until)
        j = 1
        if bound != np.inf:
            t = self.now
            ticks = self.lat.iter_latency_schedule(
                len(active), total_ctx, j_max
            )
            while j < j_max:
                t = t + ticks[j - 1]                    # end of step j
                if not (t < bound):
                    break
                j += 1
        else:
            j = j_max
        if j < 2:
            return 1
        if self.hotpath.persistent:
            # the device while_loop takes j as DATA — no compile grid, so
            # the certificate is spent at full, unquantized resolution
            return j
        return 1 << (j.bit_length() - 1)        # pow-2 compile grid

    def _commit_block(self, active, ids, total_ctx: int, j: int) -> int:
        """Replay a fused block's per-step bookkeeping exactly as the
        one-step loop performs it (same `iter_latency` tick sequence —
        context grows by B per step — same per-slot emit order). Returns
        iterations committed (< j when an EOS landed mid-block — the
        remainder is discarded and the length gate rolls the cache back —
        or, on a wall clock, when a pending arrival came due mid-block:
        the tail is dropped the same way so admission lands at the next
        iteration boundary)."""
        items = list(active.items())
        b = len(items)
        ticks = self.lat.iter_latency_schedule(b, total_ctx, j)
        committed = 0
        for s in range(j):
            if s:
                self.batch_sizes.append(b)
            self._tick(ticks[s], "decode")
            finished = False
            for slot, r in items:
                self._emit(r, int(ids[s, slot]))
                finished = finished or not r.is_live
            committed += 1
            if committed < j:
                if finished:
                    break   # batch composition changes next iteration;
                            # drop the overshoot (length-gate rollback)
                if (self.clock != "virtual"
                        and self._pending_pos < len(self._pending)
                        and self._pending[self._pending_pos].arrival
                        <= self.now):
                    break   # wall mode: an arrival is waiting — stop the
                            # block so the scheduler sees it now
        return committed

    def _multi_step_decode(self, active, tokens, total_ctx: int,
                           j: int) -> int:
        """Run j fused decode iterations (static-j scan) and commit with
        `_commit_block` — ONE device→host sync for the whole block."""
        ids, self.cache = self._decode_multi(
            self.params, jnp.asarray(tokens), self.cache, j=j
        )
        self._dispatch("decode_multi")
        ids = np.asarray(ids)                   # ONE sync for j iterations
        self._sync()
        self.multi_step_blocks += 1
        committed = self._commit_block(active, ids, total_ctx, j)
        self.multi_step_iters += committed
        self.sched.skip_iterations(committed - 1)
        if self.obs is not None:
            self.obs.multi_step(self.now, j, committed)
        return committed

    def _persistent_decode(self, active, tokens, total_ctx: int,
                           j: int) -> int:
        """Run up to j decode iterations in the device-resident
        `lax.while_loop` (models/model.py `decode_persistent`): j is data,
        not a compile-time constant, and EOS-enabled engines stop the
        device early once every active row has emitted its EOS. The
        scheduler's `idle_steps` certificate (core/policies/base.py) is
        what makes running that long unsupervised legal; the commit
        replay is the same `_commit_block` the scan path uses, so the
        persistent path inherits every bit-identity the scan proved."""
        act = np.zeros(self.kv.num_slots, bool)
        for s in active:
            act[s] = True
        ids, self.cache, steps = self._decode_persist(
            self.params, jnp.asarray(tokens), self.cache,
            jnp.int32(j), jnp.asarray(act),
            j_cap=self.hotpath.multi_step, eos_id=self.eos_id,
        )
        self._dispatch("decode_persistent")
        ids, steps = jax.device_get((ids, steps))   # ONE sync for the block
        self._sync()
        self.multi_step_blocks += 1
        self.persistent_blocks += 1
        committed = self._commit_block(active, np.asarray(ids),
                                       total_ctx, j)
        self.multi_step_iters += committed
        self.persistent_iters += int(steps)
        self.sched.skip_iterations(committed - 1)
        if self.obs is not None:
            self.obs.multi_step(self.now, j, committed)
            self.obs.persistent_loop(self.now, j, int(steps))
        return committed

    # ----------------------------------------------------------- main loop
    def _admit_arrivals(self) -> None:
        pend = self._pending
        pos = self._pending_pos
        obs = self.obs
        while pos < len(pend) and pend[pos].arrival <= self.now:
            r = pend[pos]
            pos += 1
            r.fluid_idx = self.fluid.add(r.arrival, r.spec)
            r.state = ReqState.WAITING
            self.live.append(r)
            self.sched.on_request_arrival(r)
            if obs is not None:
                obs.admit(r, self.now)
        self._pending_pos = pos
        # amortized compaction: drop the consumed prefix once it dominates
        if pos and pos * 2 >= len(pend):
            del pend[:pos]
            self._pending_pos = 0

    def step(self, until: Optional[float] = None) -> bool:
        """One continuous-batching iteration (schedule → preempt →
        swap-in/prefill → decode over all occupied slots). Returns False
        when there is nothing left to do.

        `until`: incremental drivers that will submit more work once the
        clock reaches t (Replica.advance_to) pass it so the multi-step
        fast path never skips past t inside one block — the clock then
        crosses t at the same single iteration it would when
        single-stepping, keeping routed-engine timelines bit-identical to
        submit-everything-upfront runs. Single-step behavior is unaffected
        (iterations are indivisible; the crossing step still overshoots)."""
        if self.stuck or not self.has_work:
            return False
        with span("engine.step", live=len(self.live)) as sp:
            return self._step(until, sp)

    def _step(self, until: Optional[float], sp) -> bool:
        """The body of `step`; `sp` is its `engine.step` span."""
        if self.measured:
            # a step starts now: what was submitted since the last one is due
            self.now = self.wall_now()
        if not self.live and self._pending_pos < len(self._pending):
            nxt = self._pending[self._pending_pos].arrival
            if self.clock != "virtual" and nxt > self.now:
                # an idle wall-clock engine waits out the gap for real
                # (the virtual clock jumps it)
                self._wait_for_arrival(nxt)
            else:
                self.now = max(self.now, nxt)
        self._admit_arrivals()
        if not self.live:
            return True

        with span("sched.schedule", live=len(self.live)) as sp_sched:
            target = self.sched.schedule(self.now, self.live, self.fluid)
            sp_sched.set_metadata(chosen=len(target))
        target_ids = {id(r) for r in target}

        n_preempted = 0
        for r in list(self.slot_req.values()):
            if id(r) not in target_ids and r.state == ReqState.RUNNING:
                with span("engine.preempt", rid=r.rid,
                          mode=self.preemption_mode):
                    self._preempt(r)
                n_preempted += 1
        n_admitted = 0
        staged: List[_StagedPrefill] = []
        for r in target:
            if r.state == ReqState.SWAPPED and self.kv.can_allocate(
                    r, tokens=(r.prefill_cursor or None)):
                with _admit_span(r):
                    self._swap_in(r)
                n_admitted += 1
            elif r.state == ReqState.RUNNING and r.prefill_cursor:
                # chunked prefill in flight: the resident advances one
                # chunk per scheduled iteration, interleaved with every
                # other slot's decode tick (it joins the decode batch
                # only once the cursor completes)
                with _admit_span(r):
                    staged.append(self._stage_chunk(r))
                n_admitted += 1
            elif r.state == ReqState.WAITING:
                if self._should_chunk(r):
                    # finer-grained admission: only the first chunk's
                    # tokens (pages) need to fit right now
                    if self.kv.can_allocate(r, tokens=self.prefill_chunk):
                        r.state = ReqState.RUNNING
                        r.prefilled = True
                        with _admit_span(r):
                            staged.append(self._stage_chunk(r))
                        n_admitted += 1
                elif self.kv.can_allocate(r):
                    r.state = ReqState.RUNNING
                    r.prefilled = True
                    if self._can_stage_prefill(r):
                        with _admit_span(r):
                            staged.append(self._stage_prefill(r))
                    else:
                        # a sequential prefill fires its emit (and possibly
                        # finish) events inline — flush what is staged first
                        # so event-sink chronology matches the sequential
                        # path (earlier admissions report first)
                        self._flush_prefills(staged)
                        staged = []
                        with _admit_span(r):
                            self._prefill_request(r)
                    n_admitted += 1
        self._flush_prefills(staged)

        # ---- decode over all occupied slots ---------------------------
        active = {s: r for s, r in self.slot_req.items()
                  if r.state == ReqState.RUNNING and not r.prefill_cursor}
        self.batch_sizes.append(len(active))
        committed_iters = 1
        if active:
            with span("engine.decode", rows=len(active)) as sp_dec:
                committed_iters = self._decode_active(active, until)
                sp_dec.set_metadata(j=committed_iters)
        else:
            self._tick(self.lat.hw.overhead, "overhead")
        sp.set_metadata(admitted=n_admitted, active=len(active))

        self.iterations += committed_iters
        self.live = [r for r in self.live if r.is_live]
        n_live = len(self.live)
        self._admit_arrivals()
        newly_arrived = len(self.live) > n_live

        # ---- deadlock guard -------------------------------------------
        # Nothing decoded, admitted, preempted, or newly arrived (the
        # overhead tick can advance the clock past a pending arrival),
        # and no future arrival can change the picture: every live
        # request is permanently unschedulable (e.g. prompt larger than
        # KV capacity). The legacy loop spun on overhead ticks until
        # max_iterations; the steppable engine halts so unbounded drivers
        # (cluster drain) terminate. With arrivals still pending the
        # clock keeps advancing by the overhead tick exactly as the
        # legacy loop did, preserving bit-for-bit admission times; a
        # measured clock, whose overhead tick does not sleep, waits for
        # the next arrival instead of spinning its caller.
        if not active and not n_admitted and not n_preempted \
                and not newly_arrived:
            if self._pending_pos >= len(self._pending):
                self.stuck = True            # a later submit() may clear it
                return False
            if self.measured:
                self._wait_for_arrival(
                    self._pending[self._pending_pos].arrival)
        return True

    def _wait_for_arrival(self, t: float) -> None:
        """Sleep (an `engine.pace` span, op=arrival) until the arrival due
        at `t` on a wall clock, then re-read so its admission timestamp is
        a genuine reading."""
        w = self.wall_now()
        if t > w:
            with span("engine.pace", op="arrival",
                      modeled_us=(t - self.now) * 1e6):
                time.sleep(t - w)
        self.now = max(self.now, self.wall_now())

    def _decode_active(self, active: Dict[int, Request],
                       until: Optional[float]) -> int:
        """One decode dispatch over the `active` slots (single step, a
        fused multi-step or persistent block, or speculative), through its
        host sync and commit. Returns the iterations committed."""
        # each slot's cache holds its committed context but the last token,
        # which this step consumes and writes at position `lengths[s]`;
        # the LatencyModel prices the step on the committed context
        lengths = np.zeros(self.kv.num_slots, np.int32)
        tokens = np.zeros(self.kv.num_slots, np.int32)
        for s, r in active.items():
            lengths[s] = r.context_len - 1
            tokens[s] = r.output_tokens[-1] if r.output_tokens else 0
        self.cache = cache_lib.with_lengths(self.cache, lengths)
        total_ctx = sum(r.context_len for r in active.values())
        if self.spec_k:
            s_rounds = self._spec_block_plan(active)
            if s_rounds > 1:
                return self._speculative_block(active, lengths, tokens,
                                               s_rounds, until)
            self._speculative_iteration(active, lengths, tokens, total_ctx)
            return 1
        j = self._multi_step_plan(active, total_ctx, until)
        if self.physical_pages:
            # pre-reserve every page the block will write (decode
            # step s writes position context_len - 1 + s): no host round-trip
            # can grow a table mid-block, so the whole block's
            # coverage must exist before dispatch. The scheduler's
            # paged idle_steps projection certified the demand
            # fits the pool. Pin the tables after.
            for _s, r in active.items():
                self.kv.ensure_pages(
                    r, min(r.context_len - 1 + j, self._cache_seq))
            self._refresh_block_tables()
        if j > 1:
            if self.hotpath.persistent:
                committed = self._persistent_decode(active, tokens,
                                                    total_ctx, j)
            else:
                committed = self._multi_step_decode(active, tokens,
                                                    total_ctx, j)
            if self.physical_pages:
                # EOS truncation / mid-block break may leave pages
                # reserved past the committed context — return
                # them to the pool (admission capacity is real now)
                for r in list(active.values()):
                    if r.is_live:
                        self.kv.trim_pages(r)
            return committed
        if self.hotpath.fused_sampling:
            ids, self.cache = self._decode_tok(
                self.params, jnp.asarray(tokens), self.cache
            )
            self._dispatch("decode")
            self._tick(self.lat.iter_latency(len(active), total_ctx),
                       "decode")
            nxt = np.asarray(ids)
        else:
            logits, self.cache = self._decode(
                self.params, jnp.asarray(tokens), self.cache
            )
            self._dispatch("decode")
            self._tick(self.lat.iter_latency(len(active), total_ctx),
                       "decode")
            nxt = np.asarray(jnp.argmax(logits, axis=-1))
        self._sync()
        for s, r in list(active.items()):
            self._emit(r, int(nxt[s]))
        return 1

    def result(self) -> SimResult:
        return SimResult(
            requests=list(self.seen),
            makespan=self.now,
            total_tokens=self.total_tokens,
            preemptions=self.preemptions,
            iterations=self.iterations,
            batch_sizes=self.batch_sizes,
        )

    def run(self, workload: List[Request], max_iterations: int = 100_000):
        """Serve the workload to completion. Returns the finished requests.

        A thin loop over step(): reset + submit all + iterate until
        drained — the same batch semantics as ServingSimulator.run (on a
        fresh engine the reset is a no-op, so this still reproduces the
        pre-refactor monolithic loop bit-for-bit; the differential oracle
        lives in tests/test_engine_steppable.py)."""
        self.reset()
        for r in sorted(workload, key=lambda r: r.arrival):
            self.submit(r)
        while self.iterations < max_iterations:
            if not self.step():
                break
        return workload
