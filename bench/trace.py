"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's device numbers.

The window is one host span that the harness opens (``window_span``); only
what lies inside it counts.  From the device's own timeline it takes:

- busy time: the union of the intervals in which an operation ran, per
  chip, averaged over the chips that ran any;
- device time per operation, summed by name over leaf operations (an
  operation that encloses others, such as a loop, is busy time but not a
  row of its own);
- the executions of one compiled program, found by its stable name
  (``jit_<function name>``), in order, with their device durations;
- the idle gaps, each put down to the innermost host span open at its
  middle on the thread that opened the window.

On a TPU the device timeline is the ``XLA Ops`` and ``XLA Modules`` lines of
each ``/device:`` plane.  A trace taken on the CPU has no device plane; its
operations are the host events that carry an ``hlo_op`` stat, and a
program's executions are its operations grouped by ``run_id``.  That form is
what the checked-in test trace holds.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from collections import defaultdict
from typing import List, Optional, Sequence, Tuple

Interval = Tuple[int, int]          # start, end in ns


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float
    steps_s: List[float]                     # program executions, in order
    ops: List[Tuple[str, float]]             # leaf op seconds, largest first
    idle: List[Tuple[str, float]]            # idle seconds by host span


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"{len(paths)} .xplane.pb under {log_dir}")
    return paths[0]


def _events(line):
    """(name, start, end, event); a TPU op's name is its HLO text, of
    which the instruction's own name is kept."""
    for e in line.events:
        yield (e.name.split(" = ", 1)[0].lstrip("%"), int(e.start_ns),
               int(e.start_ns + e.duration_ns), e)


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def leaves(ops: Sequence[Tuple[str, int, int]]) -> List[Tuple[str, int, int]]:
    """The operations that enclose no other operation of the same
    timeline."""
    ops = sorted(ops, key=lambda o: (o[1], -o[2]))
    out = []
    for i, op in enumerate(ops):
        nxt = ops[i + 1] if i + 1 < len(ops) else None
        if nxt is None or nxt[1] >= op[2]:
            out.append(op)
    return out


def _device_timelines(pd, program: str):
    """Per chip: (ops, program executions), each a list of
    (name, start, end)."""
    chips = []
    for plane in pd.planes:
        if not plane.name.startswith("/device:"):
            continue
        lines = {ln.name: ln for ln in plane.lines}
        if "XLA Ops" not in lines:
            continue
        ops = [(n, s, e) for n, s, e, _ in _events(lines["XLA Ops"])]
        runs = ([(n, s, e) for n, s, e, _ in _events(lines["XLA Modules"])
                 if n.startswith(program)]
                if "XLA Modules" in lines else [])
        if ops:
            chips.append((ops, runs))
    if chips:
        return chips
    ops, by_run = [], defaultdict(list)
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for n, s, e, ev in _events(line):
                st = dict(ev.stats)
                if "hlo_op" not in st:
                    continue
                ops.append((n, s, e))
                if st.get("hlo_module") == program:
                    by_run[st.get("run_id")].append((s, e))
    runs = [(program, min(s for s, _ in v), max(e for _, e in v))
            for v in by_run.values()]
    return [(ops, runs)] if ops else []


def _host_spans(pd, window_span: str):
    """The events of the host thread that opened ``window_span``, and the
    window itself."""
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = [(n, s, e) for n, s, e, _ in _events(line)]
            for n, s, e in evs:
                if n == window_span:
                    return evs, (s, e)
    raise ValueError(f"no host span {window_span!r} in the trace")


def _innermost(spans, t: int) -> str:
    best = None
    for n, s, e in spans:
        if s <= t <= e and (best is None or e - s < best[2] - best[1]):
            best = (n, s, e)
    return best[0] if best else "(no host span)"


def reduce(path: str, program: str, window_span: str,
           top: int = 10) -> Optional[Summary]:
    """The device numbers of ``window_span``; None where no operation ran
    on a device inside it."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    spans, window = _host_spans(pd, window_span)
    chips = _device_timelines(pd, program)
    if not chips:
        return None
    busy_total, op_time, gaps = 0.0, defaultdict(float), []
    steps: List[float] = []
    for i, (ops, runs) in enumerate(chips):
        ops = [(n, max(s, window[0]), min(e, window[1])) for n, s, e in ops
               if e > window[0] and s < window[1]]
        busy = union([(s, e) for _, s, e in ops])
        busy_total += sum(e - s for s, e in busy)
        if i == 0:
            for n, s, e in leaves(ops):
                op_time[n] += (e - s) / 1e9
            edges = [window[0]] + [t for iv in busy for t in iv] + [window[1]]
            gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
            steps = [(e - s) / 1e9 for _, s, e in sorted(runs,
                                                         key=lambda r: r[1])
                     if s >= window[0] and e <= window[1]]
    busy_s = busy_total / len(chips) / 1e9
    if busy_s <= 0:
        return None
    idle = defaultdict(float)
    for a, b in gaps:
        idle[_innermost(spans, (a + b) // 2)] += (b - a) / 1e9
    rank = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]
    return Summary(window_s=(window[1] - window[0]) / 1e9, busy_s=busy_s,
                   steps_s=steps, ops=rank(op_time), idle=rank(idle))
