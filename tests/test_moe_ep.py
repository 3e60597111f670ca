"""shard_map expert-parallel MoE dispatch == GSPMD reference (bit-exact).

Runs in a subprocess with faked host devices (same pattern as
test_distributed.py; this process is pinned to 1 device by conftest).
"""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
import jax, jax.numpy as jnp
import repro.models.moe as moe
from repro.distributed.moe_ep import moe_apply_ep
from repro.configs import get_smoke_config
from repro.configs.base import ModelConfig, MoEConfig
from repro.launch.mesh import make_debug_mesh

moe.CAPACITY_FACTOR = 8.0   # no-drop regime: outputs must match exactly
failures = []

# divisible experts (4 experts, tp=4)
cfg = get_smoke_config("qwen2-moe-a2.7b")
mesh = make_debug_mesh(4, 4)
p = moe.init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model)) * 0.3
y_ref, aux_ref = moe.moe_apply(p, x, cfg)
y_ep, aux_ep = jax.jit(lambda p_, x_: moe_apply_ep(p_, x_, cfg, mesh))(p, x)
if float(jnp.max(jnp.abs(y_ep - y_ref))) > 1e-5:
    failures.append("divisible")
if abs(float(aux_ep - aux_ref)) > 1e-5:
    failures.append("aux")

# padded experts (6 experts, tp=8) + ragged valid mask
cfg2 = ModelConfig(name="padtest", kind="moe", num_layers=2, d_model=64,
                   num_heads=4, num_kv_heads=4, d_ff=32, vocab_size=128,
                   moe=MoEConfig(num_experts=6, num_shared_experts=1,
                                 top_k=2, d_expert=32))
mesh2 = make_debug_mesh(2, 8)
p2 = moe.init_moe(jax.random.PRNGKey(2), cfg2, jnp.float32)
x2 = jax.random.normal(jax.random.PRNGKey(3), (4, 8, cfg2.d_model)) * 0.3
valid = jnp.arange(8)[None] < jnp.array([8, 4, 8, 2])[:, None]
y_ref2, _ = moe.moe_apply(p2, x2, cfg2, valid=valid)
y_ep2, _ = jax.jit(
    lambda p_, x_, v_: moe_apply_ep(p_, x_, cfg2, mesh2, valid=v_)
)(p2, x2, valid)
if float(jnp.max(jnp.abs(y_ep2 - y_ref2))) > 1e-5:
    failures.append("padded+masked")

# gradients: EP must differentiate like the reference (train path, iter 4)
def loss_ref(pp):
    y, aux = moe.moe_apply(pp, x2, cfg2)
    return jnp.sum(y ** 2) + aux
def loss_ep(pp):
    y, aux = moe_apply_ep(pp, x2, cfg2, mesh2)
    return jnp.sum(y ** 2) + aux
g_ref = jax.grad(loss_ref)(p2)
g_ep = jax.jit(jax.grad(loss_ep))(p2)
for a, b in zip(jax.tree.leaves(g_ref), jax.tree.leaves(g_ep)):
    if float(jnp.max(jnp.abs(a - b))) > 1e-4:
        failures.append("grad")
        break

print("FAILURES:" + ",".join(failures) if failures else "OK")
"""


@pytest.mark.slow
def test_moe_ep_matches_reference():
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", SCRIPT],
                         capture_output=True, text=True, env=env, timeout=560)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[-1] == "OK", out.stdout
