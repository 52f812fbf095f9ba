"""One run of a cell through ``bench/run.py``, with what its result line
does not carry yet.

    python3 bench/probe.py --workload <name> --seed <n> --seconds <s> \
        [--trace 1] [--out <dir>]

It calls ``run.run`` as ``bench/run.py`` does (set-up, window, check,
metrics) and adds to the result: under ``probe``, each batch's
compilations (``repro.obs.metrics.COMPILES``) and whether the loop's
spans were on (``REPRO_TRACE``); with ``--trace 1`` also, for the decode
phase of the traced batch, device milliseconds a step by named scope
(``bench/scopes.py``: the median of each scope, and of scoped plus
unscoped time, beside ``step_device_ms``) and the unscoped share.  With
``--out`` the line is also appended to ``<dir>/probe.jsonl``.  To be
removed once ``bench/run.py`` reports these itself (PERF.md section 7).
"""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:] = [str(ROOT), str(ROOT / "src")] + [
    p for p in sys.path if Path(p or ".").resolve() != ROOT / "bench"]

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
from unittest import mock  # noqa: E402

import numpy as np  # noqa: E402

from bench import run  # noqa: E402  (sets the run's environment first)
from bench import harness, scopes, trace  # noqa: E402


def scope_readings(per_step):
    """Milliseconds a decode step by scope, and the unscoped share."""
    paths = sorted({k for step in per_step for k in step})
    tops = sorted({p.split("/")[0] for p in paths})
    return {"step_ms": {p: scopes.step_ms(per_step, p)
                        for p in tops + [p for p in paths if "/" in p]},
            "scoped_plus_unscoped_ms": 1e3 * float(np.median(
                [sum(s.values()) for s in per_step])),
            "unscoped_share": scopes.unscoped_share(per_step),
            "device_scopes_s": scopes.totals(per_step)}


def probe(cell, seed: int, seconds: float, traced: bool, devices,
          cfg=None, peak=None):
    """``run.run``'s result, with the ``probe`` key added."""
    from repro.obs import metrics
    from repro.obs import trace as spans
    from repro.obs.scopes import scope_map
    seen = {}
    setup, serve_window, reduce = (harness.setup, harness.serve_window,
                                   trace.reduce)

    def setup_keeping_the_step(*a, **k):
        server = setup(*a, **k)
        if traced:
            seen["hlo"] = server.decode.as_text()
        return server

    def serve_window_kept(*a, **k):
        seen["window"] = serve_window(*a, **k)
        return seen["window"]

    def reduce_by_scope(path, program, window_span, *a, **k):
        b = seen["window"].batches[harness.TRACED_BATCH]
        per_step = scopes.decode_scopes(
            path, scope_map(seen["hlo"]), program, window_span,
            b.generated.shape[1] - 1)
        seen["scopes"] = per_step and scope_readings(per_step)
        return reduce(path, program, window_span, *a, **k)

    with mock.patch.object(harness, "setup", setup_keeping_the_step), \
            mock.patch.object(harness, "serve_window", serve_window_kept), \
            mock.patch.object(trace, "reduce", reduce_by_scope):
        out = run.run(cell, seed, seconds, traced, devices, cfg, peak)
    window, compiles = seen["window"], metrics.COMPILES
    out["probe"] = {
        "spans_on": spans.enabled(),
        "batch_compiles": [len(compiles.between(b.start, b.end))
                           for b in window.batches],
        "window_compiles": [c[1:] for c in
                            compiles.between(window.start, window.end)],
        "scopes": seen.get("scopes")}
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    out = probe(cell, args.seed, args.seconds, bool(args.trace),
                run.device_check(cell.chips))
    line = json.dumps(out)
    if args.out:
        os.makedirs(args.out, exist_ok=True)
        with open(os.path.join(args.out, "probe.jsonl"), "a") as f:
            f.write(line + "\n")
    print(line, flush=True)


if __name__ == "__main__":
    main()
