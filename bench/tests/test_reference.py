"""The plain references in ``bench/reference`` against the program, at the
configurations' ``reduced()`` size on the CPU: the benchmark's weights fill
the program's parameter tree leaf for leaf, the reference's logits are the
program's float32 forward (``api.logits_fn``) over them, and the ids that
cached ``greedy_generate`` serves are the reference's best."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, harness, reference, weights
from bench.reference import common
from repro.models import build_model

SEED = 2 ** 31 + 17


def test_weights_fill_the_program(small):
    cell, cfg = small
    mine = weights.make(cell.model, SEED)
    api = build_model(cfg)
    theirs = weights.for_program(mine, api.abstract_params())
    assert (jax.tree.structure(theirs)
            == jax.tree.structure(api.abstract_params()))
    made = jax.tree.leaves(mine)
    assert all(any(a is b for b in made) for a in jax.tree.leaves(theirs))
    assert all(a.dtype == jnp.bfloat16 for a in made)
    std = np.std(np.asarray(mine["blocks"]["attn"]["wq"], np.float32))
    assert std == pytest.approx(cell.model["initializer_range"], rel=0.05)
    assert np.any(np.asarray(weights.make(cell.model, SEED + 1)["embed"][
        "table"]) != np.asarray(mine["embed"]["table"]))


def test_weights_refuse_a_layout_they_lack(small, monkeypatch):
    cell, cfg = small
    abstract = build_model(cfg).abstract_params()
    mine = weights.make(cell.model, SEED)
    abstract["blocks"]["attn"]["wqkv"] = abstract["blocks"]["attn"].pop("wq")
    with pytest.raises(KeyError):
        weights.for_program(mine, abstract)
    monkeypatch.setitem(weights.RENAMES, "blocks/attn/wqkv", "blocks/attn/wq")
    weights.for_program(mine, abstract)


def test_logits_match_program_forward(small):
    cell, cfg = small
    fam = reference.family(cell.model)
    w = weights.make(cell.model, SEED)
    # 8 tokens: under the MoE's capacity floor, so the program drops none
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (1, 8)), jnp.int32)
    rows = jnp.arange(8)[None]
    ref = fam.forward(w, tokens, rows, cell.model)
    api = build_model(dataclasses.replace(cfg, compute_dtype="float32",
                                          param_dtype="float32"))
    params = weights.for_program(w, build_model(cfg).abstract_params())
    with jax.default_matmul_precision("highest"):
        got = api.logits_fn(common.f32(params), {"tokens": tokens})
    got = got[..., :cfg.vocab_size]
    scale = float(jnp.max(jnp.abs(ref)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=1e-5 * scale, rtol=0)


def test_served_ids_at_reference_best(small):
    """Through the cache, token by token, computing in float32 over the
    bfloat16 weights: the served ids are the reference's best up to the
    rounding of the cached keys and values to bfloat16."""
    cell, cfg = small
    cfg = dataclasses.replace(cfg, compute_dtype="float32")
    server = harness.setup(cell, SEED, cfg)
    window = harness.serve_window(server, cell, SEED, 0.2)
    s = check.sample(window.batches, cell.traffic, SEED)
    gaps = check.reference_gaps(cell.model, SEED, s)
    assert gaps.max() < 1e-3, gaps
