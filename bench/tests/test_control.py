"""The check's control at a size a test run holds: the reference put in the
program's place at float8, read on three seeds at the served positions,
has to fail the configuration's limits.  The model keeps the published
widths of the benchmark's configuration in 16384 vocabulary rows and the
few layers (and experts) its family's ``TEST_CUT`` leaves, with 32 ids a
request; at the reduced widths float8 moves the logits too little to stand
for the published model."""
import dataclasses

import pytest

from bench import check, harness, reference

from conftest import CELLS, small_cell

SEEDS = [2 ** 31 + 31, 5, 77]


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("workload", CELLS)
def test_control_is_not_correct(workload, seed):
    model = harness.load_cell(workload).model
    cut = dataclasses.replace(harness.model_config(model), vocab_size=16384,
                              **reference.family(model).TEST_CUT)
    cell, cfg = small_cell(workload, traffic={"output_tokens": 32,
                                              "slab": 80}, cfg=cut)
    server = harness.setup(cell, seed, cfg)
    window = harness.serve_window(server, cell, seed, 0.2)
    s = check.sample(window.batches, cell.traffic, seed)
    control = check.reference_gaps(cell.model, seed, s, control="fp8")
    v = check.verdict(cell.model, control)
    assert v["compared"], "the configuration sets no limit"
    assert not v["ok"], v
