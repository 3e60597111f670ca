"""Sharding rules + dry-run machinery on a small faked-device mesh.

conftest pins this test process to 1 CPU device, so these tests spawn a
subprocess with --xla_force_host_platform_device_count to build real meshes
(same pattern as launch/dryrun.py).
"""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SMALL_MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.configs import get_smoke_config
from repro.distributed.sharding import param_specs, batch_specs, cache_specs, make_shardings
from repro.launch.mesh import make_debug_mesh
from repro.models import Model

results = {}
mesh = make_debug_mesh(2, 4)
for arch in ["llama3-8b", "qwen2-moe-a2.7b", "falcon-mamba-7b", "zamba2-2.7b"]:
    cfg = get_smoke_config(arch)
    model = Model(cfg, param_dtype=jnp.float32)
    params = model.abstract_params()
    specs = param_specs(mesh, params)
    # every leaf got a spec; rank matches
    ok = True
    for (path, leaf), (_, spec) in zip(
        jax.tree_util.tree_flatten_with_path(params)[0],
        jax.tree_util.tree_flatten_with_path(specs, is_leaf=lambda x: isinstance(x, P))[0],
    ):
        if len(spec) > len(leaf.shape):
            ok = False
    # lower+compile a real train step on the small mesh
    shard = make_shardings(mesh, specs)
    b = {"tokens": jax.ShapeDtypeStruct((4, 16), jnp.int32),
         "labels": jax.ShapeDtypeStruct((4, 16), jnp.int32)}
    bs = make_shardings(mesh, batch_specs(mesh, b, cfg))
    f = jax.jit(model.loss, in_shardings=(shard, bs))
    compiled = f.lower(params, b).compile()
    cost = compiled.cost_analysis()
    results[arch] = {"ok": ok, "flops": float(cost.get("flops", 0))}

    # decode path compiles too
    cache = model.init_cache(4, 32, dtype=jnp.float32, abstract=True)
    cs = make_shardings(mesh, cache_specs(mesh, cache, cfg))
    ts = make_shardings(mesh, batch_specs(mesh, {"t": jax.ShapeDtypeStruct((4,), jnp.int32)}, cfg))["t"]
    g = jax.jit(model.decode_step, in_shardings=(shard, ts, cs), out_shardings=(None, cs))
    g.lower(params, jax.ShapeDtypeStruct((4,), jnp.int32), cache).compile()
    results[arch]["decode_ok"] = True
print(json.dumps(results))
"""


@pytest.mark.slow
def test_sharding_rules_compile_on_small_mesh():
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-c", SMALL_MESH_SCRIPT],
        capture_output=True, text=True, env=env, timeout=560,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    results = json.loads(out.stdout.strip().splitlines()[-1])
    assert len(results) == 4
    for arch, r in results.items():
        assert r["ok"] and r["decode_ok"], (arch, r)
        assert r["flops"] > 0


def test_hlo_stats_parser():
    from repro.launch.hlo_stats import collective_stats
    hlo = """
HloModule test

%cond (x: s32[]) -> pred[] {
  %c = s32[] constant(12)
  ROOT %lt = pred[] compare(%x, %c), direction=LT
}

%body (x: s32[]) -> s32[] {
  %ag = f32[128,64]{1,0} all-gather(%p), channel_id=1, replica_groups=[2,4]<=[8], dimensions={1}
  ROOT %n = s32[] add(%x, %one)
}

ENTRY %main () -> f32[] {
  %w = (s32[]) while(%t), condition=%cond, body=%body
  %ar = f32[256]{0} all-reduce(%z), channel_id=2, replica_groups=[2,4]<=[8]
  ROOT %r = f32[] constant(0)
}
"""
    stats = collective_stats(hlo)
    # all-gather inside 12-trip while: 128*64*4 * (3/4) * 12
    assert stats.count_by_op["all-gather"] == 12
    assert stats.bytes_by_op["all-gather"] == pytest.approx(
        128 * 64 * 4 * (3 / 4) * 12)
    # all-reduce in entry: 256*4 * 2 * 3/4
    assert stats.bytes_by_op["all-reduce"] == pytest.approx(256 * 4 * 2 * 0.75)


def test_dryrun_results_exist_and_wellformed():
    """The 40-combo baselines (both meshes) produced by launch/dryrun.py."""
    d = os.path.join(REPO, "experiments", "dryrun")
    if not os.path.isdir(d):
        pytest.skip("dry-run artifacts not generated yet")
    files = [f for f in os.listdir(d) if f.endswith(".json")]
    pod = [f for f in files if f.endswith("_pod.json")]
    multi = [f for f in files if f.endswith("_multipod.json")]
    assert len(pod) >= 40, f"expected 40 single-pod baselines, got {len(pod)}"
    assert len(multi) >= 40, f"expected 40 multi-pod runs, got {len(multi)}"
    for f in files[:10]:
        with open(os.path.join(d, f)) as fh:
            r = json.load(fh)
        assert r["hlo_flops_per_device"] > 0
        assert r["roofline"]["dominant"] in ("compute_s", "memory_s",
                                             "collective_s")
