"""The measured wall clock: what `clock="wall"` does on an accelerator.

Every test here runs on the CPU with `wall_clock_measures` patched to
answer True, so the engine measures time instead of sleeping to the
LatencyModel. Checked: nothing sleeps to a modeled price, token ids
equal the virtual-clock run of the same trace, every token is stamped at
or after the host read that delivered it, and a step with nothing to do
does not spin its caller.
"""
import dataclasses
import time
import types

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import LatencyModel, QoESpec, TPU_V5E, make_scheduler
from repro.core.request import Request
from repro.models import Model
from repro.serving import HotpathConfig, ServingEngine, compare_requests
from repro.serving import engine as engine_mod

SPEC = QoESpec(ttft=1.0, tds=4.8)


@pytest.fixture(scope="module")
def llama():
    cfg = get_smoke_config("llama3-8b")
    m = Model(cfg)
    return cfg, m, m.init(jax.random.PRNGKey(0))


@pytest.fixture
def measured(monkeypatch):
    monkeypatch.setattr(engine_mod, "wall_clock_measures", lambda device: True)


def mk_engine(llama, clock, hw=TPU_V5E, **kw):
    cfg, m, params = llama
    lat = LatencyModel(cfg, hw)
    num_slots = kw.pop("num_slots", 4)
    cap = kw.pop("capacity_tokens", num_slots * 64)
    sched = make_scheduler("andes", cap, lat)
    return ServingEngine(m, params, sched, lat, num_slots=num_slots,
                         max_seq=64, capacity_tokens=cap, clock=clock, **kw)


def trace(cfg, n=6, out_len=6, stagger=0.01, seed=3):
    """`n` requests `stagger` s apart; every third answers in one token."""
    rng = np.random.default_rng(seed)
    wl = []
    for i in range(n):
        plen = int(rng.integers(5, 30))
        wl.append(Request(rid=i, arrival=i * stagger, prompt_len=plen,
                          output_len=1 if i % 3 == 0 else out_len, spec=SPEC,
                          prompt_tokens=rng.integers(0, cfg.vocab_size,
                                                     plen)))
    return wl


class SpanLog:
    """Stands in for `repro.obs.spans.span`, keeping each span's name and
    arguments."""

    def __init__(self):
        self.opened = []

    def __call__(self, name, **args):
        self.opened.append((name, args))
        return self

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **args):
        pass


def test_predicate_paces_on_the_cpu_and_measures_on_an_accelerator(llama):
    assert engine_mod.wall_clock_measures(jax.devices("cpu")[0]) is False
    assert engine_mod.wall_clock_measures(
        types.SimpleNamespace(platform="tpu")) is True
    assert not mk_engine(llama, "wall").measured


def test_engine_consults_the_predicate_with_its_params_device(
        llama, monkeypatch):
    """Once, at construction, with the device its params live on; a
    virtual clock never measures."""
    seen = []
    monkeypatch.setattr(engine_mod, "wall_clock_measures",
                        lambda device: seen.append(device) or True)
    assert mk_engine(llama, "wall").measured
    assert seen == [next(iter(jax.tree.leaves(llama[2])[0].devices()))]
    assert not mk_engine(llama, "virtual").measured


def test_burst_is_served_at_device_speed_not_at_modeled_prices(
        llama, measured, monkeypatch):
    """Each prefill is priced at >= 0.25 s, yet a burst of one-token
    requests is served in a fraction of the summed prices: nothing sleeps
    to the LatencyModel, and the only pacing span left is the wait for an
    arrival."""
    cfg = llama[0]
    slow = dataclasses.replace(TPU_V5E, overhead=0.25)
    eng = mk_engine(llama, "wall", hw=slow)
    assert eng.measured

    def burst():
        return [Request(rid=i, arrival=0.0, prompt_len=8 + i, output_len=1,
                        spec=SPEC,
                        prompt_tokens=np.arange(8 + i) % cfg.vocab_size)
                for i in range(8)]

    eng.run(burst())                    # compiles every shape the run uses
    log = SpanLog()
    monkeypatch.setattr(engine_mod, "span", log)
    t0 = time.monotonic()
    out = eng.run(burst())
    elapsed = time.monotonic() - t0
    priced = sum(eng.lat.prefill_latency(r.prompt_len) for r in out)
    assert priced >= 8 * 0.25
    assert all(r.generated == 1 for r in out)
    assert elapsed < 0.25 * priced, (elapsed, priced)
    assert eng.hotpath_stats()["pace_seconds_total"] == 0
    paces = [a for name, a in log.opened if name == "engine.pace"]
    assert all(a["op"] == "arrival" for a in paces), paces
    assert any(name == "prefill.group" for name, _ in log.opened)


@pytest.mark.parametrize("hotpath", [
    HotpathConfig(), HotpathConfig(multi_step=1), HotpathConfig.baseline()],
    ids=["persistent", "single_step", "baseline"])
def test_token_ids_equal_the_virtual_run(llama, measured, hotpath):
    cfg = llama[0]
    ref = mk_engine(llama, "virtual", hotpath=hotpath).run(trace(cfg))
    eng = mk_engine(llama, "wall", hotpath=hotpath)
    assert eng.measured
    cand = eng.run(trace(cfg))
    rep = compare_requests(ref, cand)
    assert not rep.token_mismatches and not rep.missing_rids, rep.summary()
    assert all(r.generated == r.output_len for r in cand)


class LateRead:
    """A device result whose host read takes `delay` s (a device slower
    than its dispatch) and logs the monotonic time at which it returned."""

    def __init__(self, x, on_read, delay=0.01):
        self.x, self.on_read, self.delay = x, on_read, delay

    def __array__(self, dtype=None, copy=None):
        time.sleep(self.delay)
        a = np.asarray(self.x)
        self.on_read(time.monotonic())
        return a if dtype is None else a.astype(dtype)


def test_every_token_is_stamped_after_its_device_result_returned(
        llama, measured):
    """For every prefill flush and decode call, record when its result
    reached the host and which token index of which request it carried;
    each such token's emit time must be at or after that reading."""
    cfg = llama[0]
    eng = mk_engine(llama, "wall", hotpath=HotpathConfig(multi_step=1))
    returned = []                       # (monotonic time, rid, token index)

    prefill_into = eng._prefill.prefill_into

    def timed_prefill(params, cache, slots, *a, **kw):
        rids = [eng.slot_req[s].rid for s in slots]
        out = prefill_into(params, cache, slots, *a, **kw)
        t = time.monotonic()
        returned.extend((t, rid, 0) for rid in rids)
        return out

    decode_tok = eng._decode_tok

    def late_decode(params, tokens, cache):
        ids, cache = decode_tok(params, tokens, cache)
        due = [(r.rid, r.generated) for r in eng.slot_req.values()
               if not r.prefill_cursor]
        return LateRead(ids, lambda t: returned.extend(
            (t, rid, k) for rid, k in due)), cache

    eng._prefill.prefill_into = timed_prefill
    eng._decode_tok = late_decode
    out = {r.rid: r for r in eng.run(trace(cfg, n=6, out_len=5))}
    decoded = sum(r.output_len - 1 for r in out.values())
    assert len(returned) == len(out) + decoded
    for t, rid, k in returned:
        assert eng._wall0 + out[rid].emit_times[k] >= t, (rid, k)


def test_idle_step_waits_for_the_next_arrival_instead_of_spinning(
        llama, measured):
    """A live request that can never be scheduled (its prompt exceeds the
    KV capacity) leaves steps with nothing to run. With a later arrival
    pending, a measured engine sleeps until it rather than spinning
    through empty steps; with none, the deadlock guard stops the engine
    (a server's pump then blocks in its command queue)."""
    eng = mk_engine(llama, "wall", num_slots=2, capacity_tokens=20)
    big = Request(rid=0, arrival=0.0, prompt_len=50, output_len=4, spec=SPEC,
                  prompt_tokens=np.zeros(50, np.int64))
    eng.reset()
    eng.submit(big)
    later = Request(rid=1, arrival=eng.wall_now() + 0.3, prompt_len=5,
                    output_len=4, spec=SPEC,
                    prompt_tokens=np.arange(5, dtype=np.int64))
    eng.submit(later)
    steps = 0
    while eng.step():
        steps += 1
        assert steps < 20, "the engine spun through empty steps"
    assert eng.stuck and big.generated == 0
    assert later.generated == later.output_len
    assert later.emit_times[0] >= later.arrival
