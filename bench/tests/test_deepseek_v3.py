"""The DeepSeek-V3 family (``bench/reference/deepseek_v3.py``) at the
benchmark's full size, Moonlight-16B-A3B with 8 of its 64 experts held:
its weights, the work its step needs, and the two readers of latent
attention's core on a small reading."""
import math

import numpy as np
import pytest

from bench import counts, harness, reference, run, trace
from bench.reference import common as C

from conftest import CPU_PEAK, small_cell

CELL = "moonlight-16b-a3b-ep8.chat-b16-ctx8k"
MODEL = harness.load_cell(CELL).model
FAM = reference.family(MODEL)


def _size(tree):
    return sum(math.prod(leaf.shape) for leaf in
               __import__("jax").tree.leaves(
                   tree, is_leaf=lambda x: isinstance(x, C.Leaf)))


def test_weights_are_one_chips_share():
    """27 layers at the published widths with 8 experts a MoE layer:
    3.36 B values, 6.73 GB in bfloat16."""
    w = FAM.layout(MODEL)
    assert _size(w["dense_blocks"]) == 82_973_184
    assert _size(w["blocks"]) == 26 * 100_405_824
    assert _size({k: w[k] for k in ("embed", "final_norm", "lm_head")}) \
        == 671_090_688
    assert 2 * _size(w) == pytest.approx(6.73e9, rel=1e-3)
    assert w["blocks"]["moe"]["router"].shape == (26, 2048, 64)
    assert w["blocks"]["moe"]["w_up"].shape == (26, 8, 2048, 1408)


def test_block_counts():
    # one token hits 6 x 8/64 = 0.75 of the held experts
    assert FAM.block_counts(MODEL, 1) == C.BlockCounts(
        1_062_857_856.0, 1_062_731_776.0, 27 * 576, 27 * 2 * 16 * 1088)
    read, used, cache, flops = FAM.block_counts(MODEL, 16)
    hit = 8 * (1 - (1 - 6 / 64) ** 16)                    # 6.28 of 8
    assert read == pytest.approx(1_062_857_856 + 26 * (hit - 0.75)
                                 * 3 * 2048 * 1408)
    assert (used, cache, flops) == (1_062_731_776.0, 15552, 940032)


def test_core_counts():
    """Each filled position of each layer read once, in bfloat16, and
    2 x 16 heads x (576 + 512) FLOPs a token."""
    assert FAM.core_counts(MODEL, 16, 1000) == (
        16 * 27 * 34816 * 1000.0, 2.0 * 16 * 27 * 576 * 1000)


def test_step_counts():
    assert counts.step_counts(MODEL, 16, 1000) == pytest.approx(
        (59_785_347_072.0, 5_816_184_297.53), rel=1e-9)
    assert counts.step_counts(MODEL, 1, 1) == (2_797_492_224.0,
                                               2_797_171_328.0)


P, N, B = 5, 4, 2


def _reading(scopes):
    cell, _ = small_cell(CELL)
    batch = harness.Batch(0, np.zeros((B, P), np.int32),
                          np.zeros((B, N), np.int32), 0.5, 0.3, 0.0, 1.0)
    steps = [0.010 + 0.001 * j for j in range(P + N - 1)]
    summary = trace.Summary(window_s=1.0, busy_s=0.9, steps_s=steps,
                            ops=[], idle=[])
    window = harness.Window([batch, batch], 0.0, 2.0)
    return run.Reading(cell, 1.0, 0.01, window, summary, CPU_PEAK, scopes)


def test_latent_core_readers():
    core = [0.002, 0.004, 0.003]
    scopes = [{"attention/core": c, "attention/proj": 0.001, "ffn": 0.005}
              for c in core]
    r = _reading(scopes)
    assert run.read_metric("latent_core_step_ms", r) == pytest.approx(3.0)
    least = sum(max(f / CPU_PEAK["bf16_flops_per_s"],
                    b / CPU_PEAK["hbm_bytes_per_s"])
                for f, b in (FAM.core_counts(r.model, B, filled)
                             for filled, _ in r.decode_steps()))
    assert [f for f, _ in r.decode_steps()] == [P + 1, P + 2, P + 3]
    assert run.read_metric("latent_core_roofline", r) == pytest.approx(
        100 * least / sum(core))


@pytest.mark.parametrize("scopes", [None, [], [{"ffn": 0.005}] * 3,
                                    [{"attention/core": 0.001}] * 2])
def test_latent_core_readers_fall_silent(scopes):
    """No trace by scope, no core scope, or executions that do not pair
    with the decode phase's."""
    r = _reading(scopes)
    assert run.read_metric("latent_core_roofline", r) is None
    if scopes != [{"attention/core": 0.001}] * 2:
        assert run.read_metric("latent_core_step_ms", r) is None


def test_latent_core_roofline_silent_for_a_family_without_core_counts():
    cell, _ = small_cell("qwen2.5-3b.chat-b16-ctx1k")
    r = _reading([{"attention/core": 0.001}] * 3)
    r.cell = cell
    assert run.read_metric("latent_core_roofline", r) is None


def test_reference_refuses_unnormalised_gates():
    """Program and reference renormalise the chosen gates; a configuration
    that does not is refused rather than run wrong."""
    with pytest.raises(ValueError, match="renormalised"):
        FAM.layout(dict(MODEL, norm_topk_prob=False))
