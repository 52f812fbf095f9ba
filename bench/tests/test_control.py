"""The check's control at a size a test run holds: the reference put in the
program's place at float8, read on three seeds at the served positions,
has to fail the configuration's limits.  The model keeps the published
widths of the benchmark's configuration in 16384 vocabulary rows and few
layers (4 dense, 2 with experts, 16 of those held), with 32 ids a request;
at the reduced widths float8 moves the logits too little to stand for the
published model."""
import pytest

from bench import check, harness

from conftest import CELLS, small_cell

SEEDS = [2 ** 31 + 31, 5, 77]
WIDTHS = ("d_model", "n_heads", "n_kv_heads", "d_ff", "head_dim",
          "moe_d_ff", "experts_per_token", "n_shared_experts")
CUTS = {"dense": {"n_layers": 4}, "moe": {"n_layers": 2, "n_experts": 16}}


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("workload", CELLS)
def test_control_is_not_correct(workload, seed):
    model = harness.load_cell(workload).model
    full = harness.model_config(model)
    cell, cfg = small_cell(workload, traffic={"output_tokens": 32,
                                              "slab": 80},
                           vocab_size=16384, **CUTS[model["family"]],
                           **{f: getattr(full, f) for f in WIDTHS})
    server = harness.setup(cell, seed, cfg)
    window = harness.serve_window(server, cell, seed, 0.2)
    s = check.sample(window.batches, cell.traffic, seed)
    control = check.reference_gaps(cell.model, seed, s, control="fp8")
    v = check.verdict(cell.model, control)
    assert v["compared"], "the configuration sets no limit"
    assert not v["ok"], v
