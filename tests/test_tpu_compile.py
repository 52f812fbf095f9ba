"""Compile rehearsal for a described TPU v5e: the main path's kernels and
the full-width qwen2.5-3b decode step, compiled by the TPU compiler without
a chip attached.  Catches what interpret mode cannot: Mosaic tiling and
VMEM limits, and a step that does not fit the chip's HBM.

The topology is described inside a module-scoped fixture (never at import
time): only one process at a time may load the TPU library, and every test
worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.flash_decode import flash_decode_partials


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no compiler logs
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a TPU executable written to the persistent cache cannot be read back
    # without a chip; keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes, dtype=jnp.bfloat16):
    args = [jax.ShapeDtypeStruct(s, dtype, sharding=sharding) for s in shapes]
    return jax.jit(fn).lower(*args).compile()


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def test_matmul_compiles(one_chip):
    # qwen2.5-3b MLP up-projection of 2048 tokens, planner-chosen blocks
    _assert_kernel(_compile(lambda a, b: ops.matmul(a, b, interpret=False),
                            one_chip, (2048, 2048), (2048, 11008)))


def test_attention_compiles(one_chip):
    _assert_kernel(_compile(
        lambda q, k, v: ops.attention(q, k, v, causal=True, interpret=False),
        one_chip, (32, 4096, 128), (32, 4096, 128), (32, 4096, 128)))


def test_flash_decode_partials_compiles(one_chip):
    _assert_kernel(_compile(
        lambda q, k, v: flash_decode_partials(q, k, v, interpret=False),
        one_chip, (32, 1, 128), (32, 8192, 128), (32, 8192, 128)))


def test_grouped_matmul_compiles(one_chip):
    _assert_kernel(_compile(
        lambda x, w: ops.grouped_matmul(x, w, interpret=False),
        one_chip, (8, 512, 2048), (8, 2048, 1408)))


def test_wkv6_compiles(one_chip):
    # rwkv6-3b: 4 sequences x 40 heads of 64, 1024 tokens
    shapes = [(160, 1024, 64)] * 4 + [(160, 64)]
    _assert_kernel(_compile(
        lambda r, k, v, w, u: ops.wkv6(r, k, v, w, u, interpret=False),
        one_chip, *shapes, dtype=jnp.float32))


def test_qwen_full_width_decode_step_fits_hbm(one_chip):
    """The serving driver's decode step at qwen2.5-3b's published width
    (batch 4, cache 161) compiles for one chip, i.e. fits its HBM."""
    from repro.launch.serve import serving_config
    from repro.models import build_model
    cfg = serving_config("qwen2.5-3b")
    assert cfg.d_model == 2048 and cfg.n_layers == 36
    api = build_model(cfg)

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one_chip), tree)

    params = on_chip(api.abstract_params())
    cache = on_chip(jax.eval_shape(lambda: api.init_cache(cfg, 4, 161)))
    tokens = jax.ShapeDtypeStruct((4, 1), jnp.int32, sharding=one_chip)
    compiled = jax.jit(api.decode_step, donate_argnums=(2,)).lower(
        params, tokens, cache).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes > 6e9          # bf16 weights, 3.1B
