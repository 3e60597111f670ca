"""Work counted from shapes against hand-reckoned numbers and the
program's own parameter layout."""
import json
import pathlib

import jax
import pytest

from bench import work

CONFIGS = pathlib.Path(__file__).resolve().parents[1] / "configs"


def model(name):
    c = json.loads((CONFIGS / f"{name}.json").read_text())
    return c["model"], c["kind"], c["registry"]


def test_granite_by_hand():
    m, kind, _ = model("granite-3-2b")
    # embedding 49155 x 2048 (tied); per layer q,o 2048x2048, k,v
    # 2048x512, MLP 3 x 2048x8192, two norms; 40 layers; final norm
    per_layer = 2 * 2048 * 2048 + 2 * 2048 * 512 + 3 * 2048 * 8192 + 2 * 2048
    params = 49155 * 2048 + 40 * per_layer + 2048
    assert work.weight_bytes(m, kind) == 2 * params == 5_067_063_296
    # K and V, 40 layers x 8 heads x 64, bf16
    assert work.kv_bytes_per_token(m, kind) == 81_920
    # decode: every weight read once, plus the rows' KV
    flops, byts = work.decode_iteration(m, kind, 16, 16 * 400)
    assert byts == 5_067_063_296 + 81_920 * (16 * 401 + 16)
    matmul = 40 * (per_layer - 2 * 2048) + 49155 * 2048
    assert flops == 16 * 2 * matmul + 4 * 40 * 32 * 64 * (16 * 400 + 16)
    assert work.flops_per_token(m, kind, 0) == 2 * matmul


@pytest.mark.parametrize("name", ["granite-3-2b", "zamba2-2.7b"])
def test_weight_bytes_match_program_layout(name):
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.models import Model

    m, kind, registry = model(name)
    abstract = Model(get_config(registry),
                     param_dtype=jnp.bfloat16).abstract_params()
    assert work.weight_bytes(m, kind) == sum(
        x.size * x.dtype.itemsize for x in jax.tree.leaves(abstract))


def test_least_time_is_the_larger_bound():
    m, kind, _ = model("granite-3-2b")
    f, b = work.decode_iteration(m, kind, 4, 1000)
    t = work.least_seconds([(4, 1000)], m, kind, 1e12, 1e9)
    assert t == max(f / 1e12, b / 1e9)


@pytest.mark.parametrize("name", ["granite-3-2b", "zamba2-2.7b"])
def test_configuration_files_state_the_registry_config(name):
    from bench import run as bench_run
    from repro.configs import get_config

    config = json.loads((CONFIGS / f"{name}.json").read_text())
    bench_run.check_registry(get_config(config["registry"]), config)
    changed = dict(config, model=dict(config["model"], hidden_size=1))
    with pytest.raises(SystemExit):
        bench_run.check_registry(get_config(config["registry"]), changed)


def test_zamba2_by_hand():
    m, kind, _ = model("zamba2-2.7b")
    # 45 Mamba-2 layers (9 rounds of 5) at d_inner 5120, 80 heads of 64,
    # state 64; one shared attention + MLP block; untied head
    in_proj = 2560 * (2 * 5120 + 2 * 64 + 80)
    conv = 4 * 5248 + 5248
    mamba = in_proj + 5120 * 2560 + conv + 3 * 80 + 5120 + 2560
    shared = 4 * 2560 * 2560 + 3 * 2560 * 10240 + 2 * 2560
    params = 45 * mamba + shared + 2 * 32000 * 2560 + 2560
    assert work.weight_bytes(m, kind) == 2 * params == 4_127_352_160
    # K and V of the 9 shared-block applications, 32 heads x 80, bf16
    assert work.kv_bytes_per_token(m, kind) == 9 * 2 * 32 * 80 * 2 == 92_160
    # per row: f32 state 80 x 64 x 64 and bf16 conv lookback 3 x 5248
    assert work.shapes(m, kind)["state_bytes"] == \
        45 * (80 * 64 * 64 * 4 + 3 * 5248 * 2)


def test_granite_prefill_by_hand():
    m, kind, _ = model("granite-3-2b")
    per_layer = 2 * 2048 * 2048 + 2 * 2048 * 512 + 3 * 2048 * 8192
    # 100 tokens through 40 layers, causal attention over 1..100
    # positions, the head for the last token only
    want = (100 * 2 * 40 * per_layer + 4 * 40 * 32 * 64 * 100 * 101 / 2
            + 2 * 49155 * 2048)
    assert work.prefill_flops(m, kind, 100) == want
    # two calls in one flush read the weights twice
    f, b = work.prefill_flush(m, kind, 2, [100, 30])
    assert f == want + work.prefill_flops(m, kind, 30)
    assert b == 2 * 5_067_063_296 + 81_920 * 130


def test_prefill_flushes_group_by_dispatch():
    from bench.observe import Recorder

    rec = Recorder()
    rec.prefills = [(1.0, 0, 50), (1.1, 1, 70), (2.0, 2, 30), (5.0, 3, 9)]
    rec.dispatches = [(1.2, "prefill", 2), (1.3, "write", 2),
                      (2.1, "prefill", 1), (2.2, "decode", 1),
                      (5.1, "prefill", 1)]
    assert rec.prefill_flushes(0.0, 3.0) == [(2, [50, 70]), (1, [30])]
    assert rec.prefill_flushes(2.0, 9.0) == [(1, [30]), (1, [9])]
