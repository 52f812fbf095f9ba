"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``'s ``workloads``) names a configuration file
under ``bench/configs/`` and a traffic mix under ``bench/traffic/``.  The run
sets up the server, measures for ``--seconds``, checks a sample of what
the window served against the plain reference, and prints one JSON object
as the last line of standard output.  With ``--trace 0`` its metrics are
the cell's end-to-end ones; with ``--trace 1`` one batch of the window runs
under the profiler and the metrics are the per-layer ones.  Each metric is
read by ``bench/metrics/<name>.py``.  Off a TPU, or with fewer chips than
the cell asks for, it exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Dict, List, Optional, Tuple  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# import the harness as the ``bench`` package, never its modules bare
sys.path[:] = [str(ROOT), str(ROOT / "src")] + [
    p for p in sys.path if Path(p or ".").resolve() != ROOT / "bench"]
# The compile cache lives in the checkout at a fixed path (the path is part
# of the cache key); set before JAX is imported, over any inherited value.
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
os.environ["REPRO_PLANNER_WORKERS"] = "1"      # no planner worker processes
os.environ.setdefault("TPU_LOG_DIR", "disabled")   # libtpu logs to /tmp else

from bench import check, harness, scopes, trace  # noqa: E402


@dataclasses.dataclass
class Reading:
    """What a metric reader reads."""
    cell: harness.Cell
    setup_s: float
    plan_s: float
    window: harness.Window
    trace: Optional[trace.Summary]
    peak: Dict[str, float]
    # leaf-op device seconds by scope path, one dict per execution of the
    # decode step in the decode phase of the traced batch
    # (``scopes.decode_scopes``); None without a trace
    decode_scopes: Optional[List[Dict[str, float]]] = None

    @property
    def model(self) -> Dict[str, Any]:
        return self.cell.model

    def traced_batch(self) -> harness.Batch:
        return self.window.batches[harness.TRACED_BATCH]

    def decode_steps(self) -> Optional[List[Tuple[int, float]]]:
        """(filled positions, device seconds) of each execution of the
        decode step in the decode phase of the traced batch, in order:
        the last N - 1 executions, of which the i-th feeds generated id i
        to caches that then hold P + i + 1 positions.  None without a
        trace, or where the trace holds fewer executions than the phase
        has ids (a loop that no longer runs one step an id)."""
        if self.trace is None:
            return None
        b = self.traced_batch()
        P, N = b.prompts.shape[1], b.generated.shape[1]
        steps = self.trace.steps_s[len(self.trace.steps_s) - (N - 1):]
        if N < 2 or len(steps) != N - 1:
            return None
        return [(P + i + 1, s) for i, s in enumerate(steps)]


def metric_specs(cell: str, traced: bool) -> List[Dict[str, Any]]:
    spec = harness.load_json(ROOT / "BENCHMARK.json")
    return [m for m in spec["per_layer" if traced else "end_to_end"]
            if cell in m.get("workloads", [cell])]


def read_metric(name: str, r: Reading) -> Optional[float]:
    path = harness.BENCH / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                      path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    value = mod.read(r)
    return None if value is None else float(value)


def device_check(chips: int):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"bench: no TPU: JAX's first device is "
                 f"{devices[0].platform} ({devices[0].device_kind})")
    if len(devices) < chips:
        sys.exit(f"bench: the cell needs {chips} TPU chips, "
                 f"{len(devices)} found")
    return devices


def peaks_of(kind: str) -> Dict[str, float]:
    table = harness.load_json(harness.BENCH / "peaks.json")["devices"]
    if kind not in table:
        raise SystemExit(f"bench: no peaks for device kind {kind!r} in "
                         f"bench/peaks.json")
    return table[kind]


def run(cell: harness.Cell, seed: int, seconds: float, traced: bool,
        devices, cfg=None, peak=None) -> Dict[str, Any]:
    """One run of ``cell``: set-up, window, check, metrics.  Returns the
    result object.  Tests pass ``cfg`` in place of the file's model config
    and ``peak`` in place of the device's row of ``bench/peaks.json``."""
    import jax
    from repro.launch import use_compile_cache
    from repro.obs.scopes import scope_map
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    peak = peak or peaks_of(devices[0].device_kind)

    t_setup = time.perf_counter()
    server = harness.setup(cell, seed, cfg)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if traced else None
    try:
        window = harness.serve_window(server, cell, seed, seconds, trace_dir)
        memory = harness.memory_peak_bytes()
        plan_s = server.plan_s
        setup_parts = dict(start=t_setup - T_START, **server.phases)
        # which scope each instruction of the one-token step lies in
        step_scopes = scope_map(server.decode.as_text()) if traced else None
        del server
        gc.collect()
        summary = by_scope = None
        if traced and len(window.batches) > harness.TRACED_BATCH:
            xplane, program = (trace.find_xplane(trace_dir),
                               f"jit_{harness.STEP_NAME}")
            summary = trace.reduce(xplane, program, harness.WINDOW_SPAN)
            b = window.batches[harness.TRACED_BATCH]
            by_scope = scopes.decode_scopes(xplane, step_scopes, program,
                                            harness.WINDOW_SPAN,
                                            b.generated.shape[1] - 1)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    with jax.profiler.TraceAnnotation("bench.check"):
        s = check.sample(window.batches, cell.traffic, seed)
        gaps = check.reference_gaps(cell.model, seed, s)
    verdict = check.verdict(cell.model, gaps)

    reading = Reading(cell, window.start - T_START, plan_s, window, summary,
                      peak, by_scope)
    metrics = {}
    for m in metric_specs(cell.name, traced):
        value = read_metric(m["name"], reading)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": memory}
    out = {"correct": verdict["ok"],
           "attempted": sum(b.generated.shape[0] for b in window.batches),
           "failed": 0, "metrics": metrics, "device": device}
    if summary is not None:
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        out["breakdown"] = {"device_ops": [list(o) for o in summary.ops],
                            "idle_gaps": [list(g) for g in summary.idle]}
    if by_scope:
        out["decode_scope_ms"] = scopes.top_level_ms(by_scope)
    out["check"] = dict(verdict["numbers"],
                        max_gap_per_request=gaps.max(-1).tolist())
    out["setup_parts"] = setup_parts
    out["batches"] = [[b.prompts.shape[1], b.prefill_s, b.decode_s]
                      for b in window.batches]
    out["compared"] = verdict["compared"]
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    devices = device_check(cell.chips)
    out = run(cell, args.seed, args.seconds, bool(args.trace), devices)
    for name, c in out["compared"].items():
        print(f"{name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
