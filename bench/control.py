"""Readings that a cell's limit on ``max_logit_gap`` is set from, on the chip.

    python3 bench/control.py --workload <name> --seeds 1 2 3 ... [--seconds s]

For each seed, in one process: the run's set-up and a window of the run's
length (``run_seconds``), so that the sample is a run's, then the check's
sample of what it served, read against the reference as served (the
program's reading) and with the ids that the reference at each lower
precision of ``reference.common.CONTROLS`` puts first (the controls'
readings).  One JSON line per seed.  The lower reading of a limit is the
largest program reading over a dozen seeds or more, the upper the smallest
control reading; see ``PERF.md``.
"""
import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:] = [str(ROOT), str(ROOT / "src")] + [
    p for p in sys.path if Path(p or ".").resolve() != ROOT / "bench"]

from bench import check, harness  # noqa: E402
from bench.reference import common  # noqa: E402
from bench import run as bench_run  # noqa: E402  (cache and planner env)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=None,
                    help="window length (default: BENCHMARK.json's "
                         "run_seconds, so the sample is a run's)")
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    seconds = args.seconds or harness.load_json(
        ROOT / "BENCHMARK.json")["run_seconds"]
    bench_run.device_check(cell.chips)
    import jax
    from repro.launch import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    for seed in args.seeds:
        t0 = time.perf_counter()
        server = harness.setup(cell, seed)
        window = harness.serve_window(server, cell, seed, seconds)
        del server
        gc.collect()
        s = check.sample(window.batches, cell.traffic, seed)
        program = check.reference_gaps(cell.model, seed, s)
        out = {"workload": cell.name, "seed": seed,
               "program": check.numbers(program),
               "program_per_request": program.max(-1).tolist()}
        for name in common.CONTROLS:
            out[name] = check.numbers(check.reference_gaps(
                cell.model, seed, s, control=name))
        out.update(batches=len(window.batches),
                   seconds=time.perf_counter() - t0)
        print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
