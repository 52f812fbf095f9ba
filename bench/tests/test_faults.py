"""A whole run at the reduced size, past the look for a chip, with the
timed path broken underneath: ``correct`` has to come out false.

The faults a serving cell on one chip can have: a step that returns its
cache unchanged, half of the batch left out (its rows copied from the
other half), and an id altered where it is produced.  The exchange between
chips does not exist on one chip, so it has no case here."""
import dataclasses
import itertools

import jax
import jax.numpy as jnp
import pytest

import repro.models
from bench import run
from repro.launch import serve

from conftest import CPU_PEAK

SEED = 2 ** 31 + 23


def _broken_step(kind):
    build = repro.models.build_model

    def build_broken(cfg):
        api = build(cfg)
        step = api.decode_step

        def state_unchanged(p, t, c):
            return step(p, t, c)[0], c

        def half_batch(p, t, c):
            logits, new = step(p, t, c)
            h = t.shape[0] // 2
            return jnp.concatenate([logits[:h], logits[:h]]), new

        bad = {"state_unchanged": state_unchanged,
               "half_batch": half_batch}[kind]
        return dataclasses.replace(api, decode_step=bad)

    return build_broken


def _altered_pick():
    compile_greedy = serve.compile_greedy

    def compile_altered(step, params, tokens, cache, vocab_size):
        decode, pick = compile_greedy(step, params, tokens, cache,
                                      vocab_size)
        calls = itertools.count()

        def altered(logits):
            row, tok = pick(logits)
            if next(calls) % 4 == 1:          # one id in four of each batch
                tok = (tok + 1) % vocab_size
            return row, tok

        return decode, altered

    return compile_altered


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "id_altered"])
def test_fault_is_not_correct(small, fault, monkeypatch):
    cell, cfg = small
    if fault == "id_altered":
        monkeypatch.setattr(serve, "compile_greedy", _altered_pick())
    else:
        monkeypatch.setattr(repro.models, "build_model", _broken_step(fault))
    out = run.run(cell, SEED, 0.2, False, jax.devices(), cfg=cfg,
                  peak=CPU_PEAK)
    assert out["compared"], "the configuration sets no limit"
    assert out["correct"] is False, out["compared"]
