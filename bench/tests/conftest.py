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

from bench import harness, reference  # noqa: E402

# the first cell of each configuration in BENCHMARK.json
_WORKLOADS = harness.load_json(ROOT / "BENCHMARK.json")["workloads"]
CELLS = [w["name"] for i, w in enumerate(_WORKLOADS)
         if w["config"] not in {v["config"] for v in _WORKLOADS[:i]}]
SMALL_TRAFFIC = {"batch": 4, "slab": 48, "output_tokens": 8,
                 "prompt_len": {"dist": "lognormal", "median": 12,
                                "sigma": 0.5, "scale": 1.0,
                                "quantiles": 4},
                 "check_requests": 4}
# made-up peaks for readers that need a row of bench/peaks.json
CPU_PEAK = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def small_cell(workload: str, traffic=None, cfg=None, **sizes):
    """(cell, ModelConfig) of ``workload`` at the reduced size, with
    ``sizes`` over the reduced config, or at ``cfg``; ``traffic`` over the
    mix."""
    cell = harness.load_cell(workload)
    cfg = cfg or harness.model_config(cell.model).reduced(**sizes)
    model = dict(cell.model, **{k: getattr(cfg, f) for k, f
                                in harness.model_keys(cell.model).items()})
    traffic = {**cell.traffic, **SMALL_TRAFFIC, **(traffic or {})}
    return dataclasses.replace(cell, model=model, traffic=traffic), cfg


@pytest.fixture(params=CELLS)
def small(request):
    """Each of the benchmark's cells at the reduced size."""
    return small_cell(request.param)


@pytest.fixture
def family_dir(tmp_path, monkeypatch):
    """A directory searched for family modules after ``bench/reference``;
    the modules imported from it are forgotten after the test."""
    monkeypatch.setattr(reference, "__path__",
                        [*reference.__path__, str(tmp_path)])
    yield tmp_path
    for name, mod in list(sys.modules.items()):
        if str(getattr(mod, "__file__", None)).startswith(str(tmp_path)):
            del sys.modules[name]
