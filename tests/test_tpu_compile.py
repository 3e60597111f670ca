"""Compile for a described TPU v5e, with no chip attached.

The TPU compiler is installed with jaxlib and compiles for a topology that
is described rather than present. These tests compile the main path's
Pallas kernels at real widths (each must lower to a `tpu_custom_call`),
and granite-3-2b's and zamba2-7b's full-width bf16 decode steps and the
serving engine's largest prefills, whose bytes must fit one 16 GB chip.
Nothing runs, so they say nothing about results or times.

The topology, and everything built from it, is made inside module-scoped
fixtures of this file: only the process that runs these tests loads the
TPU library.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.paged_attention import paged_decode_attention
from repro.kernels.selective_scan import selective_scan
from repro.models import Model
from repro.serving.engine import BucketedPrefill
from repro.server.app import FULL_MAX_SEQ, FULL_SLOTS

HBM_BYTES = 16e9        # one TPU v5e (Google Cloud documentation, "TPU v5e")
PAGE = 16


@pytest.fixture(scope="module")
def topo():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield topo


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep it out of any cache."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _place(sharding, tree):
    return jax.tree.map(lambda a: _spec(sharding, a.shape, a.dtype), tree)


def _granite_kernel_args(name, sh):
    """(kernel, argument shapes) at granite-3-2b's heads and the full
    server's engine size, in bf16."""
    cfg = get_config("granite-3-2b")
    h, kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    b, s = FULL_SLOTS, FULL_MAX_SEQ
    lengths = _spec(sh, (b,), jnp.int32)
    if name == "flash_attention":
        return (functools.partial(flash_attention, causal=True,
                                  interpret=False),
                (_spec(sh, (1, 2048, h, hd)), _spec(sh, (1, 2048, kv, hd)),
                 _spec(sh, (1, 2048, kv, hd))))
    if name == "decode_attention":
        return (functools.partial(decode_attention, interpret=False),
                (_spec(sh, (b, h, hd)), _spec(sh, (b, s, kv, hd)),
                 _spec(sh, (b, s, kv, hd)), lengths))
    pages = b * s // PAGE
    return (functools.partial(paged_decode_attention, interpret=False),
            (_spec(sh, (b, h, hd)), _spec(sh, (pages, PAGE, kv, hd)),
             _spec(sh, (pages, PAGE, kv, hd)),
             _spec(sh, (b, s // PAGE), jnp.int32), lengths))


def _mamba_scan_args(sh):
    """selective_scan at falcon-mamba-7b's d_inner and d_state."""
    cfg = get_config("falcon-mamba-7b")
    b, s, d, n = 1, 2048, cfg.d_inner, cfg.ssm.d_state
    f32 = jnp.float32
    return (functools.partial(selective_scan, interpret=False),
            (_spec(sh, (b, s, d), f32), _spec(sh, (b, s, d), f32),
             _spec(sh, (d, n), f32), _spec(sh, (b, s, n), f32),
             _spec(sh, (b, s, n), f32), _spec(sh, (d,), f32)))


@pytest.mark.parametrize("name", ["flash_attention", "decode_attention",
                                  "paged_decode_attention", "selective_scan"])
def test_kernel_compiles_to_tpu_custom_call(one_chip, name):
    if name == "selective_scan":
        fn, args = _mamba_scan_args(one_chip)
    else:
        fn, args = _granite_kernel_args(name, one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture(scope="module")
def granite(one_chip):
    """granite-3-2b at its published widths in bf16: the model and its
    parameter shapes on one chip."""
    model = Model(get_config("granite-3-2b"), param_dtype=jnp.bfloat16)
    return model, _place(one_chip, model.abstract_params())


@pytest.fixture(scope="module")
def zamba2(one_chip):
    """zamba2-7b (24 of 81 layers) at its published widths in bf16."""
    model = Model(get_config("zamba2-7b"), param_dtype=jnp.bfloat16)
    return model, _place(one_chip, model.abstract_params())


def _device_bytes(compiled):
    m = compiled.memory_analysis()
    return m.argument_size_in_bytes + m.temp_size_in_bytes


def _decode_step(model, params, sh, slots=FULL_SLOTS, max_seq=FULL_MAX_SEQ):
    cache = _place(sh, model.init_cache(
        slots, max_seq, dtype=jnp.bfloat16, abstract=True))
    tokens = _spec(sh, (slots,), jnp.int32)
    return jax.jit(model.decode_tokens).lower(params, tokens, cache).compile()


def _largest_prefill(model, params, sh, slots=FULL_SLOTS,
                     max_seq=FULL_MAX_SEQ):
    """The largest prefill an engine of `slots` x `max_seq` can form:
    every slot a row, each padded to the terminal length bucket."""
    bp = BucketedPrefill(model, max_seq, jnp.bfloat16, max_seq=max_seq)
    rows, seq = bp.row_bucket(slots), bp.buckets[-1]
    assert seq == max_seq
    return jax.jit(bp._call).lower(
        params, _spec(sh, (rows, seq), jnp.int32),
        _spec(sh, (rows,), jnp.int32), None).compile()


def test_full_width_decode_step_fits_one_chip(granite, one_chip):
    assert _device_bytes(_decode_step(*granite, one_chip)) < HBM_BYTES


def test_largest_engine_prefill_fits_one_chip(granite, one_chip):
    assert _device_bytes(_largest_prefill(*granite, one_chip)) < HBM_BYTES


# the benchmark's zamba2-7b engine (bench/configs/zamba2-7b.json, whose
# `reckoning` holds these compiles' bytes)
ZAMBA2_SLOTS, ZAMBA2_MAX_SEQ = 16, 1024


def test_zamba2_decode_step_fits_one_chip(zamba2, one_chip):
    """The hybrid's decode step: weights, the four applications' K/V and
    every layer's recurrent state for 16 slots of 1024."""
    compiled = _decode_step(*zamba2, one_chip, ZAMBA2_SLOTS, ZAMBA2_MAX_SEQ)
    assert _device_bytes(compiled) < HBM_BYTES


def test_zamba2_largest_engine_prefill_fits_one_chip(zamba2, one_chip):
    compiled = _largest_prefill(*zamba2, one_chip, ZAMBA2_SLOTS,
                                ZAMBA2_MAX_SEQ)
    assert _device_bytes(compiled) < HBM_BYTES
