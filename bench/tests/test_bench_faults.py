"""A whole run of the harness at a size the CPU holds, past the look for
a chip: sound, it is correct; with the timed path broken underneath
(a decoded token altered where it is produced, a decode step that returns
its cache unchanged, a token altered on the wire, and for one-token
answers a first token altered in the prefill) `correct` comes out
false."""
import dataclasses
import io
import json
import pathlib

import jax
import jax.numpy as jnp
import pytest

from bench import run as bench_run
from repro.configs import get_smoke_config
from repro.models.model import Model

ROOT = pathlib.Path(__file__).resolve().parents[2]
SECONDS = 5.0


def tiny_cell(traffic="chat"):
    # granite's smoke preset with four layers: enough attention that a
    # decode step reading a stale cache serves other tokens
    c = dataclasses.replace(get_smoke_config("granite-3-2b"), num_layers=4)
    config = json.loads((ROOT / "bench/configs/granite-3-2b.json").read_text())
    config["model"] = dict(
        hidden_size=c.d_model, num_hidden_layers=c.num_layers,
        num_attention_heads=c.num_heads, num_key_value_heads=c.num_kv_heads,
        intermediate_size=c.d_ff, vocab_size=c.vocab_size,
        rms_norm_eps=c.norm_eps, rope_theta=c.rope_theta,
        tie_word_embeddings=c.tie_embeddings)
    config["engine"] = dict(config["engine"], num_slots=2, max_seq=64)
    config["knee_rps"] = {"chat": 4.0, "first_token": 8.0}
    # at this size sound runs read a widest gap of 0.045-0.080 logits
    # (seeds 2**31 + 3..8 on the CPU: bf16 near-ties), a decode step that
    # keeps its state 0.51-0.94 (seeds 3..5), a token altered 2.5-3.1
    config["limits"] = {"served_gap_max": 0.2, "min_tokens": 20}
    mix = json.loads((ROOT / f"bench/traffic/{traffic}.json").read_text())
    mix.update(prompt_lognormal=[2.5, 0.5], output_lognormal=[3.6, 0.3])
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = bench_run.Cell(name="tiny", chips=1, traffic=traffic,
                          config=config, mix=mix,
                          end_to_end=spec["end_to_end"], per_layer=[])
    return cell, c


@pytest.fixture(scope="module")
def cache_dir(tmp_path_factory):
    """Compiles shared by the runs of this file, kept out of the checkout;
    the process's cache settings are restored after them."""
    from jax.experimental.compilation_cache import compilation_cache

    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs")
    was = {n: getattr(jax.config, n) for n in names}
    compilation_cache.reset_cache()
    jax.config.update("jax_compilation_cache_dir",
                      str(tmp_path_factory.mktemp("jax_cache")))
    yield
    for n, v in was.items():
        jax.config.update(n, v)
    compilation_cache.reset_cache()


def run_tiny(monkeypatch, traffic="chat"):
    import repro.launch.compile_cache as cc

    monkeypatch.setattr(cc, "enable_compile_cache", lambda: "")
    cell, model_cfg = tiny_cell(traffic)
    out = io.StringIO()
    result = bench_run.run_cell(cell, 2 ** 31 + 3, SECONDS, False,
                                require_tpu=False, model_cfg=model_cfg,
                                out=out)
    assert json.loads(out.getvalue().strip().splitlines()[-1]) == result
    return result


def test_sound_run_is_correct(cache_dir, monkeypatch):
    result = run_tiny(monkeypatch)
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert list(result)[-1] == "checks"
    m = {k: v["value"] for k, v in result["metrics"].items()}
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert set(m) == {e["name"] for e in spec["end_to_end"]}
    assert m["ttft_p90_s"] > 0
    assert m["tokens_s"] > 0 and m["setup_s"] > 0


def test_token_altered_in_decode_is_caught(cache_dir, monkeypatch):
    step = Model.decode_step

    def wrong_token(self, params, tokens, cache):
        logits, cache = step(self, params, tokens, cache)
        return jnp.roll(logits, 1, axis=-1), cache

    monkeypatch.setattr(Model, "decode_step", wrong_token)
    result = run_tiny(monkeypatch)
    assert not result["correct"]
    assert result["checks"]["served_gap_max"]["value"] > 0.2


def test_decode_step_that_keeps_its_state_is_caught(cache_dir, monkeypatch):
    step = Model.decode_step

    def stale(self, params, tokens, cache):
        logits, _ = step(self, params, tokens, cache)
        return logits, cache

    monkeypatch.setattr(Model, "decode_step", stale)
    result = run_tiny(monkeypatch)
    assert not result["correct"]
    assert result["checks"]["served_gap_max"]["value"] > 0.2


def test_first_token_sound_run_is_correct(cache_dir, monkeypatch):
    """One-token answers: every compared token comes from a prefill."""
    result = run_tiny(monkeypatch, "first_token")
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["checks"]["tokens_compared"]["value"] >= 20


def test_first_token_altered_in_prefill_is_caught(cache_dir, monkeypatch):
    prefill = Model.prefill

    def wrong_token(self, params, batch, cache):
        logits, cache = prefill(self, params, batch, cache)
        return jnp.roll(logits, 1, axis=-1), cache

    monkeypatch.setattr(Model, "prefill", wrong_token)
    result = run_tiny(monkeypatch, "first_token")
    assert not result["correct"]
    assert result["checks"]["served_gap_max"]["value"] > 0.2


def wire_altered_at(monkeypatch, index):
    import repro.server.app as app

    fmt = app.format_sse

    def altered(event, data, *a, **k):
        if event == "token" and data.get("index") == index:
            data = dict(data, token=(data["token"] + 1) % 512)
        return fmt(event, data, *a, **k)

    monkeypatch.setattr(app, "format_sse", altered)


def test_token_altered_on_the_wire_is_caught(cache_dir, monkeypatch):
    wire_altered_at(monkeypatch, 2)
    result = run_tiny(monkeypatch)
    assert not result["correct"]
    assert result["checks"]["wire_mismatch"]["value"] > 0


def test_first_token_altered_on_the_wire_is_caught(cache_dir, monkeypatch):
    wire_altered_at(monkeypatch, 0)
    result = run_tiny(monkeypatch, "first_token")
    assert not result["correct"]
    assert result["checks"]["wire_mismatch"]["value"] > 0


def control_through_the_runs_comparison(monkeypatch, traffic):
    """The control, the reference in float8 in the program's place, is
    judged by the run's own comparison on the run's own sample: the same
    tokens compared, each gap finite, and `passed` decides. At this size
    it does not clear the limit (the chip-size readings are
    bench/control.py's)."""
    import numpy as np

    from bench import control

    import repro.launch.compile_cache as cc

    monkeypatch.setattr(cc, "enable_compile_cache", lambda: "")
    cell, model_cfg = tiny_cell(traffic)
    keep = {}
    result = bench_run.run_cell(cell, 2 ** 31 + 3, SECONDS, False,
                                require_tpu=False, model_cfg=model_cfg,
                                out=io.StringIO(), keep=keep)
    low = []
    checks = control.control_checks(cell, keep, low)
    assert checks.keys() == result["checks"].keys()
    assert (checks["tokens_compared"]["value"]
            == result["checks"]["tokens_compared"]["value"])
    flat = np.concatenate(low)
    assert np.isfinite(flat).all() and (flat >= 0).all()
    assert checks["served_gap_max"]["value"] == float(flat.max())
    assert isinstance(bench_run.passed(checks), bool)


def test_low_precision_control_goes_through_the_runs_comparison(
        cache_dir, monkeypatch):
    control_through_the_runs_comparison(monkeypatch, "chat")


def test_first_token_control_goes_through_the_runs_comparison(
        cache_dir, monkeypatch):
    control_through_the_runs_comparison(monkeypatch, "first_token")
