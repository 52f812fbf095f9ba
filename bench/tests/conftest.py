"""Cells of the benchmark at a size the CPU runs in seconds: the model at
the program's ``reduced()`` widths and a short traffic mix, with the
configuration file's keys rewritten to match so that the reference and the
counts read the same sizes."""
import dataclasses
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("REPRO_PLANNER_WORKERS", "1")

import pytest  # noqa: E402

from bench import harness  # noqa: E402

# (configuration, traffic) of the benchmark's cells, one per family the
# references cover
FAMILIES = [("qwen2.5-3b", "chat-b16-ctx1k"),
            ("deepseek-moe-16b-8L", "chat-b8-ctx1k")]
CELLS = [f"{c}.{t}" for c, t in FAMILIES]
SMALL_TRAFFIC = {"batch": 4, "slab": 48, "output_tokens": 8,
                 "prompt_len": {"dist": "lognormal", "median": 12,
                                "sigma": 0.5, "scale": 1.0,
                                "quantiles": 4},
                 "check_requests": 4}
# made-up peaks for readers that need a row of bench/peaks.json
CPU_PEAK = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def small_cell(workload: str, traffic=None, **sizes):
    """(cell, ModelConfig) of ``workload`` (``<config>.<traffic>``) at the
    reduced size, with ``sizes`` over the reduced config and ``traffic``
    over the mix."""
    config, mix = next((c, t) for c, t in FAMILIES
                       if workload == f"{c}.{t}")
    cell = harness.Cell(workload, 1,
                        harness.load_json(harness.BENCH / "configs"
                                          / f"{config}.json"),
                        harness.load_json(harness.BENCH / "traffic"
                                          / f"{mix}.json"))
    cfg = harness.model_config(cell.model).reduced(**sizes)
    keys = {**harness.MODEL_KEYS["common"],
            **harness.MODEL_KEYS[cell.model["family"]]}
    model = dict(cell.model, **{k: getattr(cfg, f) for k, f in keys.items()})
    traffic = {**cell.traffic, **SMALL_TRAFFIC, **(traffic or {})}
    return dataclasses.replace(cell, model=model, traffic=traffic), cfg


@pytest.fixture(params=CELLS)
def small(request):
    """Each of the benchmark's cells at the reduced size."""
    return small_cell(request.param)
