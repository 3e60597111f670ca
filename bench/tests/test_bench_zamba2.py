"""zamba2-7b: the plain reference against transformers' published
implementation, the program against the reference (prefill, then decode
through the KV cache and recurrent state), and the configuration file
against the program's registry config and parameter layout."""
import dataclasses
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import oracle, work
from bench.reference import mm_f32
from repro.configs import get_config, get_smoke_config
from repro.models import Model

CONFIG = pathlib.Path(__file__).resolve().parents[1] / "configs" / \
    "zamba2-7b.json"
ref = oracle.reference_module("zamba2")


def model_block(cfg):
    return {k: v for k, v in ref.registry_view(cfg).items() if k != "kind"}


def tiny_config():
    """The smoke preset at 8 layers with hybrid ids 1 and 6: a plain layer
    before the first application, a gap of 5, and a plain layer after the
    last."""
    return dataclasses.replace(get_smoke_config("zamba2-7b"), num_layers=8,
                               hybrid_layer_ids=(1, 6))


def test_reference_matches_program_prefill_and_decode():
    """At f32 the program's prefill logits and its decode logits through
    the cache and state are the reference's. Tolerance 1e-5 of the logit
    scale: the two differ in reduction order only (measured 8e-7)."""
    cfg = tiny_config()
    m = model_block(cfg)
    model = Model(cfg, param_dtype=jnp.float32)
    params = jax.jit(lambda k: ref.init(m, k, jnp.float32))(
        jax.random.PRNGKey(3))
    toks = jnp.asarray(np.random.default_rng(0).integers(0, cfg.vocab_size,
                                                         40), jnp.int32)
    prefill, decode = jax.jit(model.prefill), jax.jit(model.decode_step)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(lambda p, t: ref.forward(p, m, t, mm_f32))(
            params, toks))
        cache = model.init_cache(1, 64, dtype=jnp.float32)
        logits, cache = prefill(params, {"tokens": toks[None, :30]}, cache)
        got = [np.asarray(logits[0])]
        for i in range(30, 39):
            logits, cache = decode(params, toks[i:i + 1], cache)
            got.append(np.asarray(logits[0]))
    np.testing.assert_allclose(np.stack(got), want[29:39], rtol=0,
                               atol=1e-5 * np.abs(want).max())


def test_benchmark_weights_are_the_program_layout():
    """The weights the benchmark makes from the seed have the program's
    tree, shapes and dtypes at full width (shapes only), and their bytes
    are the ones bench/work.py counts."""
    config = json.loads(CONFIG.read_text())
    m = config["model"]
    cfg = get_config(config["registry"])
    want = Model(cfg, param_dtype=jnp.bfloat16).abstract_params()
    got = jax.eval_shape(lambda k: ref.init(m, k, jnp.bfloat16),
                         jax.random.PRNGKey(0))
    assert jax.tree.structure(want) == jax.tree.structure(got)
    assert all(a.shape == b.shape and a.dtype == b.dtype for a, b in
               zip(jax.tree.leaves(want), jax.tree.leaves(got)))
    assert work.weight_bytes(m, "zamba2") == sum(
        x.size * x.dtype.itemsize for x in jax.tree.leaves(want))


def test_configuration_file_by_hand():
    """Parameters, KV and state of the 24-layer cut, reckoned by hand."""
    config = json.loads(CONFIG.read_text())
    m, kind = config["model"], config["kind"]
    from bench import run as bench_run

    bench_run.check_registry(get_config(config["registry"]), config)
    d, di, f, conv = 3584, 7168, 14336, 7168 + 2 * 2 * 64
    mamba = (d * (di + conv + 112) + di * d + 4 * conv + conv + 3 * 112
             + di + d)
    block = 2 * d + 3 * 2 * d * 7168 + 7168 * d + d + 3 * d * f
    app = d * 128 + 128 * 2 * f + d * d
    params = 24 * mamba + 2 * block + 4 * app + 32000 * d + d
    assert params == 2_733_050_240 == get_config("zamba2-7b").param_count()
    assert work.weight_bytes(m, kind) == 2 * params
    assert config["reckoning"]["weights_bytes"] == 2 * params
    # K and V of the 4 applications, 32 heads of 224, bf16
    assert work.kv_bytes_per_token(m, kind) == 4 * 2 * 32 * 224 * 2 == \
        config["reckoning"]["kv_bytes_per_token"]
    # per row: f32 state 112 x 64 x 64 and bf16 conv lookback 3 x 7424
    assert work.shapes(m, kind)["state_bytes"] == \
        24 * (112 * 64 * 64 * 4 + 3 * conv * 2) == \
        config["reckoning"]["state_bytes_per_slot"]


def _copy_into_torch(hf, params, m):
    """The seeded weights into transformers' Zamba2ForCausalLM."""
    import torch

    t = lambda a: torch.tensor(np.asarray(a, np.float32))
    ids, M = m["hybrid_layer_ids"], m["num_mem_blocks"]
    lay = params["layers"]
    with torch.no_grad():
        hf.model.embed_tokens.weight.copy_(t(params["embed"]["table"]))
        hf.lm_head.weight.copy_(t(params["embed"]["table"]))
        hf.model.final_layernorm.weight.copy_(
            t(params["final_norm"]["scale"]))
        for i, layer in enumerate(hf.model.layers):
            dec = layer.mamba_decoder if i in ids else layer
            p = jax.tree.map(lambda x: x[i], lay["mamba"])
            dec.input_layernorm.weight.copy_(t(lay["norm_scale"][i]))
            mx = dec.mamba
            mx.in_proj.weight.copy_(t(p["in_proj"]).T)
            mx.conv1d.weight.copy_(t(p["conv_w"]).T[:, None, :])
            mx.conv1d.bias.copy_(t(p["conv_b"]))
            for name in ("dt_bias", "A_log", "D"):
                getattr(mx, name).copy_(t(p[name]))
            mx.norm.weight.copy_(t(p["norm_scale"]))
            mx.out_proj.weight.copy_(t(p["out_proj"]).T)
        for k, i in enumerate(ids):
            layer, b = hf.model.layers[i], k % M
            layer.linear.weight.copy_(t(params["hybrid"]["linear"][k]).T)
            blk = layer.shared_transformer
            sh = jax.tree.map(lambda x: x[b], params["shared"])
            blk.input_layernorm.weight.copy_(t(sh["attn_norm_scale"]))
            for name in ("q", "k", "v", "o"):
                getattr(blk.self_attn, f"{name}_proj").weight.copy_(
                    t(sh["attn"][f"w{name}"]).T)
            blk.pre_ff_layernorm.weight.copy_(t(sh["mlp_norm_scale"]))
            ff = blk.feed_forward
            ff.gate_up_proj.weight.copy_(t(np.concatenate(
                [sh["mlp"]["gate"], sh["mlp"]["up"]], axis=1)).T)
            ff.down_proj.weight.copy_(t(sh["mlp"]["down"]).T)
            adapter = ff.gate_up_proj_adapter_list[k]
            adapter[0].weight.copy_(t(params["hybrid"]["adapter_a"][k]).T)
            adapter[1].weight.copy_(t(params["hybrid"]["adapter_b"][k]).T)


def test_reference_matches_transformers(monkeypatch):
    """The reference's logits are transformers' Zamba2ForCausalLM's (torch,
    its slow path on the CPU, f32) on the same seeded weights, for a tiny
    config with the catalog's key set: 2 shared blocks, 2 groups, MLP
    adapters, RoPE on the shared attention, hybrid ids with gaps of 5 and
    6. The sequence fits one SSD chunk: over more than one, that slow path
    disagrees with its own one-chunk result (1e-2 at 20 tokens in chunks
    of 8), while the token-by-token recurrence here is the definition both
    forms compute. Tolerance 1e-4 of the logit scale: torch's chunked SSD
    and its reductions round differently in f32 (measured 3e-6 of 2.4);
    a missing piece of the mathematics — an adapter, the group split, the
    D skip, the softmax scale — moves the logits by far more."""
    monkeypatch.setenv("USE_TF", "0")       # transformers' torch side only
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")

    d, heads, mheads, layers = 32, 4, 8, 13
    blocks = ["mamba"] * layers
    for i in (1, 6, 12):
        blocks[i] = "hybrid"
    published = dict(
        adapter_rank=4, add_bias_linear=False, attention_head_dim=2 * d //
        heads, attention_hidden_size=2 * d, chunk_size=32, ffn_hidden_size=48,
        hidden_act="gelu", hidden_size=d, hybrid_layer_ids=[1, 6, 12],
        intermediate_size=48, kv_channels=d // heads,
        layers_block_type=blocks, mamba_d_conv=4, mamba_d_state=8,
        mamba_expand=2, mamba_headdim=2 * d // mheads, mamba_ngroups=2,
        max_position_embeddings=64, n_mamba_heads=mheads,
        num_attention_heads=heads, num_hidden_layers=layers,
        num_key_value_heads=heads, num_logits_to_keep=1, num_mem_blocks=2,
        num_query_groups=heads, rms_norm_eps=1e-5, rope_theta=10000,
        time_step_floor=1e-4, time_step_limit=None, time_step_max=0.1,
        time_step_min=0.001, use_conv_bias=True, use_long_context=False,
        use_mem_rope=True, use_shared_attention_adapter=False,
        use_shared_mlp_adapter=True, vocab_size=64)
    hf_cfg = transformers.Zamba2Config(**published)
    for key in ("attention_head_dim", "mamba_headdim", "hybrid_layer_ids"):
        assert getattr(hf_cfg, key) == published[key], key
    hf_cfg._attn_implementation = "eager"
    m = dict(published, tie_word_embeddings=True)

    @jax.jit
    def seeded(key):
        # nothing at its init value: biases, time steps and scales move too
        params = ref.init(m, key, jnp.float32)
        leaves, tree = jax.tree.flatten(params)
        ks = jax.random.split(jax.random.fold_in(key, 1), len(leaves))
        return tree.unflatten([x + 0.1 * jax.random.normal(k, x.shape)
                               for x, k in zip(leaves, ks)])

    params = seeded(jax.random.PRNGKey(7))
    hf = transformers.Zamba2ForCausalLM(hf_cfg).eval()
    _copy_into_torch(hf, params, m)
    toks = np.random.default_rng(1).integers(0, 64, 20)
    with torch.no_grad():
        want = hf(torch.tensor(toks[None]), use_cache=False).logits[0].numpy()
    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.jit(lambda p, t: ref.forward(p, m, t, mm_f32))(
            params, jnp.asarray(toks)))
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=1e-4 * np.abs(want).max())


def tiny_cell():
    """The zamba2-7b cell at the smoke size: 2 slots of 64 positions,
    short chat_steady streams."""
    from bench import run as bench_run

    root = CONFIG.parents[2]
    cfg = tiny_config()
    config = json.loads(CONFIG.read_text())
    config["model"] = model_block(cfg)
    config["engine"] = dict(config["engine"], num_slots=2, max_seq=64)
    config["knee_rps"] = {"chat_steady": 4.0}
    # at this size sound runs read a widest gap of 0.005-0.011 (seeds 5,
    # 2**31 + 11, 2**31 + 99 on the CPU: bf16 rounding), a decode step
    # that keeps its state 0.77-1.02
    config["limits"] = {"served_gap_max": 0.2, "min_tokens": 20}
    mix = json.loads((root / "bench/traffic/chat_steady.json").read_text())
    mix.update(prompt_lognormal=[2.5, 0.5], output_lognormal=[3.6, 0.3])
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cell = bench_run.Cell(name="tiny", chips=1, traffic="chat_steady",
                          config=config, mix=mix,
                          end_to_end=spec["end_to_end"], per_layer=[])
    return cell, cfg


@pytest.fixture
def cache_settings():
    """A run sets the process's persistent-cache threshold; put it back."""
    name = "jax_persistent_cache_min_compile_time_secs"
    was = getattr(jax.config, name)
    yield
    jax.config.update(name, was)


def run_tiny(monkeypatch):
    import io

    import repro.launch.compile_cache as cc
    from bench import run as bench_run

    monkeypatch.setattr(cc, "enable_compile_cache", lambda: "")
    cell, cfg = tiny_cell()
    return bench_run.run_cell(cell, 2 ** 31 + 11, 5.0, False,
                              require_tpu=False, model_cfg=cfg,
                              out=io.StringIO())


@pytest.mark.parametrize("fault", [None, "stale_state"])
def test_tiny_cell_run(monkeypatch, cache_settings, fault):
    """A whole run of the harness on the zamba2 cell at the smoke size:
    sound, it is correct; with a decode step that returns its cache (K/V
    and Mamba state) unchanged, it is not."""
    if fault:
        step = Model.decode_step

        def stale(self, params, tokens, cache):
            logits, _ = step(self, params, tokens, cache)
            return logits, cache

        monkeypatch.setattr(Model, "decode_step", stale)
    result = run_tiny(monkeypatch)
    gap = result["checks"]["served_gap_max"]["value"]
    if fault:
        assert not result["correct"] and gap > 0.2
    else:
        assert result["correct"], result["checks"]
        assert result["attempted"] > 0 and result["failed"] == 0


def test_scopes_by_hand():
    """A window [100, 1100) ns of one program: operations under zamba2.ssd
    (inside zamba2.mamba), under zamba2.shared and under none, one
    straddling the window's start, one in a program the trace has no HLO
    for."""
    from bench import devtrace, scopes

    path = "jit(_call)/jit(main)/while/body/"
    names = {"jit__call(7)": {
        "fusion.1": path + "zamba2.mamba/zamba2.ssd/while/body/mul",
        "fusion.2": path + "zamba2.mamba/dot_general",
        "fusion.3": path + "zamba2.shared/dot_general",
        "copy.4": "jit(_call)/jit(main)/copy",
        "fusion.5": path + "zamba2.mamba/zamba2.ssd/add",
        "fusion.6": path + "not_zamba2.ssd_x/add"}}
    ops = [("%fusion.1 = f32[4]{0} fusion(p), kind=kLoop", 50, 100),
           ("%fusion.2 = bf16[4]{0} fusion(p)", 200, 100),
           ("%fusion.3 = bf16[4]{0} fusion(p)", 300, 200),
           ("%copy.4 = bf16[4]{0} copy(p)", 600, 100),
           ("%fusion.5 = f32[4]{0} fusion(p)", 650, 100),
           ("%fusion.6 = f32[4]{0} fusion(p)", 900, 50),
           ("%fusion.1 = f32[4]{0} fusion(p)", 1050, 200)]
    planes = [
        devtrace.Plane("/host:CPU", [devtrace.Line("python", [
            (devtrace.MARK, 100, 1000)])]),
        devtrace.Plane("/device:TPU:0", [
            devtrace.Line(devtrace.MODULES, [("jit__call(7)", 0, 1000),
                                             ("jit_other(9)", 1000, 300)]),
            devtrace.Line(devtrace.OPS, ops)])]
    sc = scopes.reduce(planes, names)
    # ssd: [100, 150) and [650, 750); mamba adds [200, 300); shared
    # [300, 500); busy adds [600, 650), [900, 950) and [1050, 1100)
    assert sc.seconds == {"zamba2.mamba": 250e-9, "zamba2.ssd": 150e-9,
                          "zamba2.shared": 200e-9}
    assert sc.busy_s == pytest.approx(600e-9)
    # no scope: what no scoped op covers, [600, 650) of the copy,
    # fusion.6 [900, 950), and the other program's op [1050, 1100)
    assert sc.no_scope_s == pytest.approx(150e-9)
    assert scopes.scopes_of("a/zamba2.ssd/b") == ("zamba2.ssd",)
    assert scopes.scopes_of("a/zamba2.ssdx/b") == ()


def test_scope_names_come_from_the_trace(tmp_path):
    """The op names of a real trace's programs, from the HLO the profiler
    keeps in its metadata plane, carry the scopes of the model code."""
    from bench import scopes

    @jax.jit
    def f(x):
        with jax.named_scope("zamba2.ssd"):
            y = jnp.sin(x) @ x
        with jax.named_scope("zamba2.shared"):
            return jnp.cos(y) @ y

    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    f(x).block_until_ready()
    jax.profiler.stop_trace()
    path, = tmp_path.rglob("*.xplane.pb")
    names = scopes.programs(memoryview(path.read_bytes()))
    ops = next(v for k, v in names.items() if k.startswith("jit_f("))
    found = {scopes.scopes_of(o) for o in ops.values()}
    assert {("zamba2.ssd",), ("zamba2.shared",)} <= found


def test_ssd_roofline_by_hand(monkeypatch):
    """The recurrence's least time from the model block, over its scoped
    time: one decode iteration of 16 rows at full width is bound by the
    state's bytes, read and written."""
    import importlib.util
    import types

    from bench import scopes

    spec = importlib.util.spec_from_file_location(
        "ssd_roofline", CONFIG.parents[1] / "metrics" / "ssd_roofline.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    m = json.loads(CONFIG.read_text())["model"]
    f, b = mod.work(m, 16, 16, True)
    assert f == 24 * 16 * 5 * 112 * 64 * 64
    assert b == 24 * 16 * 112 * 64 * 64 * 4 * 2 + 24 * 16 * (
        7168 + 2 * 2 * 64 + 112) * 2
    obs = types.SimpleNamespace(prefill_flushes=lambda a, b: [],
                                decode_iterations=lambda a, b: [(16, 4000)])
    run = types.SimpleNamespace(
        traced={"mono": (0.0, 1.0)}, obs=obs,
        cell=types.SimpleNamespace(config={"model": m}),
        peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9})
    monkeypatch.setattr(scopes, "of", lambda run: scopes.Scoped(
        {"zamba2.ssd": 0.01}, 0.02, 0.0, 1))
    assert mod.read(run) == pytest.approx(100 * (b / 819e9) / 0.01)
