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


# what a v5e chip's 16 GB leave to a program (the runtime keeps the rest)
V5E_USABLE_HBM = 15.75e9


def test_qwen_top_prompt_chunk_fits_hbm(one_chip):
    """The serve step at the top width of the prompt ladder, as the
    benchmark's qwen2.5-3b cell feeds a prompt (full width, batch 16, 128
    positions, slab 1024), compiles for one chip, and its arguments plus
    temporaries fit the chip's HBM."""
    from repro.launch.serve import PREFILL_WIDTHS, serving_config
    from repro.models import build_model
    cfg = serving_config("qwen2.5-3b")
    api = build_model(cfg)

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one_chip), tree)

    width = PREFILL_WIDTHS[0]
    assert width == 128
    params = on_chip(api.abstract_params())
    cache = on_chip(jax.eval_shape(lambda: api.init_cache(cfg, 16, 1024)))
    tokens = jax.ShapeDtypeStruct((16, width), jnp.int32, sharding=one_chip)
    compiled = jax.jit(api.decode_step, donate_argnums=(2,)).lower(
        params, tokens, cache).compile()
    assert compiled.out_info[0].shape == (16, 1, cfg.padded_vocab)
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < V5E_USABLE_HBM)


def test_qwen_decode_step_ops_all_have_scopes(one_chip):
    """Every device op of the benchmark's qwen2.5-3b decode step (full
    width, batch 16, slab 1024) lies in a named scope of the step, and the
    float32 copies of a layer's whole K and V slab lie in
    ``attention/core``, the upcast the contract puts there."""
    import re
    from repro.launch.serve import serving_config
    from repro.obs import scopes
    from repro.models import build_model
    cfg = serving_config("qwen2.5-3b")
    api = build_model(cfg)

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one_chip), tree)

    params = on_chip(api.abstract_params())
    cache = on_chip(jax.eval_shape(lambda: api.init_cache(cfg, 16, 1024)))
    tokens = jax.ShapeDtypeStruct((16, 1), jnp.int32, sharding=one_chip)
    text = jax.jit(api.decode_step, donate_argnums=(2,)).lower(
        params, tokens, cache).compile().as_text()
    assert scopes.uncovered(text) == []
    found = scopes.scope_map(text)
    assert {"layer_loop", "attention/core", "ffn", "lm_head"} <= set(
        found.values())
    slab_f32 = [n for n in re.findall(
        r"^\s*%([\w.\-]+) = f32\[16,1024,2,128\]", text, re.M)
        if n in found]
    assert slab_f32 and {found[n] for n in slab_f32} == {"attention/core"}


@pytest.mark.parametrize("width", [1, 128])
def test_latent_serve_step_fits_and_keeps_scopes(one_chip, width):
    """moonlight-16b-a3b's serve step as the benchmark runs it (full width,
    8 of 64 experts held, batch 16, slab 8192) compiles for one chip and
    fits its HBM.  Decoding, it reads the latent slab where it lies: no
    temporary holds a layer's slab (16 x 8192 x 512 bfloat16, 134 MB), and
    every device op lies in a named scope, the core's loop over the slab's
    blocks in ``attention/core``.  A prompt chunk's core builds no scores
    over the whole slab (16 x 16 heads x 128 x 8192 float32, 1.07 GB)."""
    import dataclasses
    from repro.launch.serve import serving_config
    from repro.models import build_model
    from repro.obs import scopes
    cfg = dataclasses.replace(serving_config("moonlight-16b-a3b"),
                              n_experts_held=8)
    api = build_model(cfg)

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one_chip), tree)

    params = on_chip(api.abstract_params())
    cache = on_chip(jax.eval_shape(lambda: api.init_cache(cfg, 16, 8192)))
    tokens = jax.ShapeDtypeStruct((16, width), jnp.int32, sharding=one_chip)
    compiled = jax.jit(api.decode_step, donate_argnums=(2,)).lower(
        params, tokens, cache).compile()
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < V5E_USABLE_HBM)
    assert mem.alias_size_in_bytes > 4e9             # the cache, in place
    assert mem.temp_size_in_bytes < 16 * 16 * width * 8192 * 4
    if width == 1:
        assert mem.temp_size_in_bytes < 16 * 8192 * 512 * 2
        text = compiled.as_text()
        assert scopes.uncovered(text) == []
        scope = scopes.scope_map(text)
        assert {"layer_loop", "attention/proj", "attention/kv_cache",
                "attention/core", "ffn/router", "ffn/experts",
                "lm_head"} <= set(scope.values())
        instrs = scopes.instructions(text)
        loop = [n for n, (op, name, _) in instrs.items()
                if op not in scopes.NO_WORK and name
                and "/attention/core/while/" in name]
        assert loop and {scope[n] for n in loop} == {"attention/core"}
        # the trailing prefetch of the next execution's first weight (a
        # copy-done nothing uses) takes the scope of that weight's other
        # user, the dense MLP's ("ffn"), and adds nothing to the core's
        users = {}
        for n, (_, _, operands) in instrs.items():
            for o in operands:
                users.setdefault(o, []).append(n)
        trailing = [n for n, (op, _, _) in instrs.items()
                    if op == "copy-done" and n not in users]
        assert trailing
        for n in trailing:
            (start,) = instrs[n][2]
            (weight,) = instrs[start][2]
            others = {scope[u] for u in users[weight] if u != start}
            assert scope[n] in others
            assert scope[n] != "attention/core"
