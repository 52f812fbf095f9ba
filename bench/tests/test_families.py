"""A model family is one module of ``bench/reference``, found by name: the
program's config, the weights, the reference forward, the check and the
step counts all go through it, and a new family is a new file alone."""
import dataclasses
import shutil

import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, counts, harness, reference, weights
from repro.launch import serve
from repro.models import build_model

from conftest import small_cell

SEED = 2 ** 31 + 41
# configuration file key -> ModelConfig field, as the harness kept it in
# one table before each family module stated its own
COMMON_BEFORE = {"vocab_size": "vocab_size", "hidden_size": "d_model",
                 "num_hidden_layers": "n_layers",
                 "num_attention_heads": "n_heads",
                 "num_key_value_heads": "n_kv_heads",
                 "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
                 "tie_word_embeddings": "tie_embeddings",
                 "hidden_act": "mlp_activation"}


@pytest.mark.parametrize("config,keys_before", [
    ("qwen2.5-3b", {"intermediate_size": "d_ff", "qkv_bias": "qkv_bias"}),
    ("deepseek-moe-16b-8L", {"moe_intermediate_size": "moe_d_ff",
                             "n_routed_experts": "n_experts",
                             "n_shared_experts": "n_shared_experts",
                             "num_experts_per_tok": "experts_per_token"}),
])
def test_model_config_as_before(config, keys_before):
    model = harness.load_json(harness.BENCH / "configs" / f"{config}.json")
    keys = {**COMMON_BEFORE, **keys_before}
    before = dataclasses.replace(serve.serving_config(model["arch"]),
                                 **{f: model[k] for k, f in keys.items()})
    assert harness.model_config(model) == before


def test_unknown_family_is_named():
    model = dict(harness.load_cell("qwen2.5-3b.chat-b16-ctx1k").model,
                 family="nosuch")
    for call in (reference.family, harness.model_config, weights.make):
        args = (model, SEED) if call is weights.make else (model,)
        with pytest.raises(LookupError, match=r"'nosuch'.*'dense', 'moe'"):
            call(*args)
    with pytest.raises(LookupError, match="'common'"):
        reference.family({"family": "common"})


def _tree_of(path):
    return {p: p.stat().st_mtime_ns for p in path.rglob("*")
            if "__pycache__" not in p.parts}


def test_a_new_family_is_one_new_module(family_dir):
    """A copy of the dense reference under another name, in a directory
    of its own, serves a configuration that names it through every part
    of the harness, and nothing under ``bench/`` is written."""
    files = _tree_of(harness.BENCH)
    shutil.copy(harness.BENCH / "reference" / "dense.py",
                family_dir / "toy.py")
    assert "toy" in reference.names()
    cell, cfg = small_cell("qwen2.5-3b.chat-b16-ctx1k")
    dense, toy = cell.model, dict(cell.model, family="toy")

    assert harness.model_config(toy) == harness.model_config(dense)
    w = weights.make(toy, SEED)
    params = weights.for_program(w, build_model(cfg).abstract_params())
    assert params["embed"]["table"] is w["embed"]["table"]
    tokens = jnp.asarray(np.random.default_rng(1).integers(
        0, cfg.vocab_size, (2, 9)), jnp.int32)
    rows = jnp.tile(jnp.arange(4, 9), (2, 1))
    logits = reference.family(toy).forward(w, tokens, rows, toy)
    np.testing.assert_array_equal(
        logits, reference.family(dense).forward(w, tokens, rows, dense))
    best = np.asarray(jnp.argmax(logits, -1), np.int32)
    gaps = check.reference_gaps(toy, SEED, check.Sample(
        np.asarray(tokens), np.asarray(rows), best))
    assert gaps.shape == best.shape and gaps.max() < 1e-3
    for batch, filled in [(1, 1), (4, 30)]:
        assert (counts.step_counts(toy, batch, filled)
                == counts.step_counts(dense, batch, filled))
    assert _tree_of(harness.BENCH) == files
