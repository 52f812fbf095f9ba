"""Observability-layer contract (DESIGN_OBS.md): the tracer and metrics
registry only *observe* — instrumented searches select bit-identical plans
at any worker count — the exported trace is schema-valid Chrome JSON with
properly nested spans (including spans merged from worker processes), the
explain CLI renders kernel and pipeline cells, and golden regeneration is
refused while tracing."""
import json
import os
import time

import pytest

from repro.core import SearchBudget, get_hw, matmul_program, \
    plan_kernel_multi, simulate
from repro.obs import metrics, trace


@pytest.fixture(autouse=True)
def _clean_obs():
    """Every test starts and ends with tracing off and an empty buffer (the
    tracer and registry are process-global)."""
    trace.disable()
    trace.clear()
    yield
    trace.disable()
    trace.clear()


def _mk_programs():
    return [matmul_program(640, 640, 512, bm=bm, bn=bn, bk=64)
            for bm in (32, 64) for bn in (32, 64, 128)]


def _key(res):
    return [(c.plan.describe(), c.index, c.cost.total_s,
             c.sim.total_s if c.sim else None) for c in res.topk]


# --------------------------------------------------------------------- trace
def test_span_noop_when_disabled():
    with trace.span("x.y", foo=1):
        pass
    assert trace.events() == []
    # the disabled path returns one shared null object (no allocation)
    assert trace.span("a") is trace.span("b")


def test_span_records_complete_events():
    trace.enable()
    with trace.span("outer", cat="t", k="v"):
        with trace.span("inner", cat="t"):
            pass
    evs = trace.events()
    assert [e["name"] for e in evs] == ["inner", "outer"]  # close order
    for e in evs:
        for k in trace.REQUIRED_KEYS:
            assert k in e
        assert e["ph"] == "X" and e["pid"] == os.getpid()
    assert evs[1]["args"] == {"k": "v"}
    # inner nests inside outer on the same track
    assert trace.validate_chrome_trace({"traceEvents": evs}) == []


def test_traced_decorator_and_drain():
    with trace.span("drained.fn", cat="t"):
        pass
    assert trace.drain() == []           # disabled: zero events
    trace.enable()
    with trace.span("drained.fn", cat="t"):
        pass
    drained = trace.drain()
    assert [e["name"] for e in drained] == ["drained.fn"]
    assert trace.events() == []          # drain clears


def _profiled(tmp_path, body):
    """Names of the events of a profiler capture of ``body()``, each with
    the name of the host line (thread) it is on."""
    import glob
    import jax
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    return {e.name: line.name for plane in ProfileData.from_file(path).planes
            for line in plane.lines for e in line.events}


def test_span_lands_on_the_profiler_timeline(tmp_path):
    import jax

    def body():
        assert trace.span("obs.off") is trace.span("obs.off2")
        with trace.span("obs.off"):      # tracing off: no annotation
            pass
        trace.enable()
        with jax.profiler.TraceAnnotation("obs.marker"):
            with trace.span("obs.on", cat="t"):
                pass

    lines = _profiled(tmp_path, body)
    assert lines["obs.on"] == lines["obs.marker"]     # the caller's thread
    assert "obs.off" not in lines
    assert [e["name"] for e in trace.events()] == ["obs.on"]


def test_compile_counter_counts_new_programs_only():
    import jax
    import jax.numpy as jnp

    def seven(x):
        return x * 7 + 1

    metrics.watch_compiles()
    metrics.watch_compiles()             # once per process
    f, x = jax.jit(seven), jnp.ones(5)
    total = lambda: metrics.counter("jax_compiles_total").total()
    before = total()
    f(x).block_until_ready()
    assert total() == before + 1
    assert metrics.COMPILES.log[-1][2] == "jit(seven)"
    t0 = time.perf_counter()
    f(x).block_until_ready()             # a cached call: no event
    assert total() == before + 1
    assert metrics.COMPILES.between(t0, time.perf_counter()) == []


def test_ingest_preserves_worker_identity():
    trace.enable()
    foreign = [{"name": "w", "cat": "worker", "ph": "X", "ts": 5.0,
                "dur": 2.0, "pid": 99999, "tid": 1}]
    trace.ingest(foreign)
    assert trace.events()[0]["pid"] == 99999


def test_write_and_validate_chrome_trace(tmp_path):
    trace.enable()
    with trace.span("a"):
        pass
    path = tmp_path / "trace.json"
    assert trace.write(str(path)) == str(path)
    doc = json.loads(path.read_text())
    assert "traceEvents" in doc and doc["displayTimeUnit"] == "ms"
    assert trace.validate_chrome_trace(doc) == []


def test_validate_rejects_malformed_and_overlapping():
    assert trace.validate_chrome_trace({"nope": 1})
    missing = {"traceEvents": [{"ph": "X", "ts": 0.0, "dur": 1.0,
                                "pid": 1}]}  # no tid/name
    assert any("missing key" in p
               for p in trace.validate_chrome_trace(missing))
    # partial overlap on one (pid, tid) track is not legal span nesting
    overlap = {"traceEvents": [
        {"name": "a", "ph": "X", "ts": 0.0, "dur": 10.0, "pid": 1, "tid": 1},
        {"name": "b", "ph": "X", "ts": 5.0, "dur": 10.0, "pid": 1, "tid": 1},
    ]}
    assert any("overlap" in p for p in trace.validate_chrome_trace(overlap))


def test_refresh_from_env_round_trip(monkeypatch, tmp_path):
    path = str(tmp_path / "t.json")
    monkeypatch.setenv(trace.TRACE_ENV, path)
    trace.refresh_from_env()
    assert trace.enabled()
    monkeypatch.delenv(trace.TRACE_ENV)
    trace.refresh_from_env()             # env withdrawn -> tracing off
    assert not trace.enabled()


# ------------------------------------------------------------------- metrics
def test_metrics_counter_gauge_histogram():
    metrics.inc("t_obs_counter", 2.0, phase="a")
    metrics.inc("t_obs_counter", phase="b")
    c = metrics.counter("t_obs_counter")
    assert c.value(phase="a") == 2.0 and c.total() == 3.0
    metrics.set_gauge("t_obs_gauge", 7.5)
    assert metrics.gauge("t_obs_gauge").value() == 7.5
    metrics.observe("t_obs_hist", 0.05, kind="x")
    s = metrics.histogram("t_obs_hist").series(kind="x")
    assert s.count == 1 and s.min == s.max == 0.05
    snap = metrics.snapshot()
    assert snap["t_obs_counter"]["type"] == "counter"
    assert {tuple(sorted(d["labels"].items()))
            for d in snap["t_obs_counter"]["series"]} == {
                (("phase", "a"),), (("phase", "b"),)}
    assert metrics.counter_totals(snap)["t_obs_counter"] == 3.0
    with pytest.raises(TypeError):
        metrics.gauge("t_obs_counter")   # type of first registration wins


def test_metrics_diff_counters():
    before = metrics.snapshot()
    metrics.inc("t_obs_diff", 3.0, phase="est")
    d = metrics.diff_counters(before, metrics.snapshot())
    assert d["t_obs_diff"] == {json.dumps({"phase": "est"}): 3.0}
    # a second diff against the newer snapshot is empty (zero deltas drop)
    assert "t_obs_diff" not in metrics.diff_counters(metrics.snapshot(),
                                                     metrics.snapshot())


def test_metrics_dump(tmp_path, monkeypatch):
    metrics.inc("t_obs_dump")
    monkeypatch.delenv(metrics.METRICS_ENV, raising=False)
    assert metrics.dump() is None        # no destination known
    path = tmp_path / "metrics.json"
    monkeypatch.setenv(metrics.METRICS_ENV, str(path))
    assert metrics.dump() == str(path)
    assert "t_obs_dump" in json.loads(path.read_text())


def test_planner_publishes_phase_and_search_metrics():
    before = metrics.snapshot()
    plan_kernel_multi(_mk_programs(), get_hw("wormhole_8x8"),
                      budget=SearchBudget(top_k=3, workers=1))
    d = metrics.diff_counters(before, metrics.snapshot())
    assert d["planner_searches_total"]
    assert d["planner_candidates_total"]
    phases = {json.loads(k)["phase"]: v
              for k, v in d["planner_phase_seconds_total"].items()}
    assert {"enumerate", "estimate"} <= set(phases)
    assert all(v > 0 for v in phases.values())


# ----------------------------------------------- bit-identity traced/untraced
@pytest.mark.parametrize("workers", [1, 2])
def test_traced_search_bit_identical(workers):
    """The hard invariant: tracing on vs off selects identical top-k
    (same plans, same canonical indices, costs equal to the bit) at any
    worker count — instrumentation must only observe."""
    hw = get_hw("wormhole_8x8")
    budget = SearchBudget(top_k=5, workers=workers)
    untraced = plan_kernel_multi(_mk_programs(), hw, budget=budget)
    trace.enable()
    traced = plan_kernel_multi(_mk_programs(), hw, budget=budget)
    events = trace.events()
    trace.disable()
    assert _key(traced) == _key(untraced)
    assert events, "tracing was on but no spans were recorded"
    assert trace.validate_chrome_trace({"traceEvents": events}) == []
    names = {e["name"] for e in events}
    assert "planner.plan_kernel_multi" in names
    if workers > 1:
        worker_evs = [e for e in events if e.get("cat") == "worker"]
        assert worker_evs, "sharded run must merge worker spans"
        assert all(e["pid"] != os.getpid() for e in worker_evs)


def test_sharded_trace_merges_multiple_worker_processes():
    """A sharded search at workers=4 lands spans from >= 2 distinct worker
    pids in the parent buffer, and the merged trace still validates."""
    hw = get_hw("wormhole_8x8")
    progs = [matmul_program(1024, 1024, 1024, bm=bm, bn=bn, bk=bk)
             for bm in (32, 64) for bn in (32, 64, 128)
             for bk in (64, 128)]
    trace.enable()
    plan_kernel_multi(progs, hw, budget=SearchBudget(top_k=3, workers=4))
    events = trace.events()
    trace.disable()
    assert trace.validate_chrome_trace({"traceEvents": events}) == []
    worker_pids = {e["pid"] for e in events if e.get("cat") == "worker"}
    assert len(worker_pids) >= 2, f"worker pids: {sorted(worker_pids)}"


def test_traced_pipeline_bit_identical(fast_search):
    from repro.pipeline import mlp2_graph, plan_pipeline
    hw = get_hw("wormhole_8x8")
    budget = SearchBudget(top_k=2, max_plans_per_mapping=8, workers=1)
    mk = lambda: mlp2_graph(4096, 128, 256,
                            blocks=((64, 64, 64), (128, 128, 64)))
    base = plan_pipeline(mk(), hw, budget=budget)
    trace.enable()
    traced = plan_pipeline(mk(), hw, budget=budget)
    events = trace.events()
    trace.disable()
    assert traced.total_s == base.total_s
    assert traced.describe() == base.describe()
    names = {e["name"] for e in events}
    assert {"pipeline.node_pools", "pipeline.graph_bnb"} <= names


def test_simulate_record_does_not_change_result():
    hw = get_hw("wormhole_8x8")
    res = plan_kernel_multi(_mk_programs(), hw,
                            budget=SearchBudget(top_k=1, workers=1))
    plan = res.best.plan
    bare = simulate(plan, hw)
    rec = []
    recorded = simulate(plan, hw, record=rec)
    assert recorded == bare              # bit-identical, frozen dataclass
    assert len(rec) == bare.n_wave_classes
    assert sum(r["population"] for r in rec) == bare.n_waves


# ------------------------------------------------------------------- explain
def test_explain_gemm_cell(fast_search):
    from repro.obs import explain
    text = explain.explain("gemm/wormhole_8x8/M1024_N1024_K4096",
                           cache=None)
    assert "wave-class timeline" in text
    assert "mesh utilization" in text
    assert "winner vs runner-up" in text
    assert "resource" in text and "dram" in text


def test_explain_pipeline_cell(fast_search):
    from repro.obs import explain
    text = explain.explain("pipeline/mlp2/M16384_d128_f512", cache=None)
    assert "edges forwarded" in text
    assert "flip_delta" in text          # per-edge forward-vs-spill delta
    assert "forward[" in text            # at least one forwarded edge
    assert "per-node edge-adjusted simulations" in text


def test_explain_rejects_unknown_cell():
    from repro.obs import explain
    with pytest.raises(explain.CellError):
        explain.resolve_kernel_cell("nope/such/cell")
    with pytest.raises(explain.CellError):
        explain.resolve_pipeline_cell("pipeline/nope/M1_d2_f3")


def test_explain_cli_list(capsys):
    from repro.obs.__main__ import main
    assert main(["explain", "--list"]) == 0
    out = capsys.readouterr().out
    assert "gemm/wormhole_8x8/M1024_N1024_K4096" in out
    assert "pipeline/mlp2/M16384_d128_f512" in out


# ----------------------------------------------------------- golden refusal
def test_write_golden_refused_while_tracing(tmp_path):
    from benchmarks import plan_speed
    trace.enable()
    with pytest.raises(RuntimeError, match="refusing to write"):
        plan_speed.write_golden({"cell": {"best": "x"}},
                                str(tmp_path / "g.json"))
    trace.disable()
    trace.clear()
    # untraced write succeeds
    plan_speed.write_golden({"cell": {"best": "x"}},
                            str(tmp_path / "g.json"))
    doc = json.loads((tmp_path / "g.json").read_text())
    assert doc["best_plans"] == {"cell": "x"}


def test_run_update_golden_refused_under_env(monkeypatch, capsys, tmp_path):
    import sys

    from benchmarks import run
    monkeypatch.setenv(trace.TRACE_ENV, str(tmp_path / "t.json"))
    monkeypatch.setattr(sys, "argv", ["run.py", "--update-golden"])
    with pytest.raises(SystemExit) as exc:
        run.main()
    assert exc.value.code == 2
    assert "--update-golden is refused" in capsys.readouterr().err


# --------------------------------------------------------- fallback dedup
def test_fallback_warns_once_per_cause_but_counts_all(caplog):
    import logging

    from repro.core import lower_jax
    lower_jax.clear_block_caches()
    before = lower_jax.planner_fallback_count()
    assert before == 0
    with caplog.at_level(logging.WARNING, logger=lower_jax.log.name):
        lower_jax._note_fallback("gemm_blocks", (64, 64, 64),
                                 RuntimeError("boom"), (32, 32, 32))
        lower_jax._note_fallback("gemm_blocks", (64, 64, 64),
                                 RuntimeError("boom"), (32, 32, 32))
        lower_jax._note_fallback("gemm_blocks", (64, 64, 64),
                                 RuntimeError("other"), (32, 32, 32))
    assert lower_jax.planner_fallback_count() == 3
    assert lower_jax.planner_fallback_count("gemm_blocks") == 3
    warned = [r for r in caplog.records
              if "planner fallback" in r.getMessage()]
    assert len(warned) == 2              # one per distinct (template, cause)
    lower_jax.clear_block_caches()
    assert lower_jax.planner_fallback_count() == 0


# ------------------------------------------------------- plancache metrics
def test_plancache_metrics_mirror_stats(tmp_path, monkeypatch, fast_search):
    from repro.plancache import PlanCache
    from repro.plancache.store import PlanCacheStore
    store = PlanCacheStore(root=tmp_path / "pc")
    cache = PlanCache(store)
    before = metrics.snapshot()
    hw = get_hw("wormhole_8x8")
    progs = [matmul_program(512, 512, 512, bm=64, bn=64, bk=64)]
    budget = SearchBudget(top_k=2, workers=1)
    r1 = plan_kernel_multi(progs, hw, budget=budget, cache=cache)
    r2 = plan_kernel_multi(progs, hw, budget=budget, cache=cache)
    assert r2.best.plan.describe() == r1.best.plan.describe()
    d = metrics.diff_counters(before, metrics.snapshot())
    gets = {json.loads(k)["result"]: v
            for k, v in d["plancache_get_total"].items()}
    assert gets.get("miss") == 1 and gets.get("hit_mem", 0) >= 1
    puts = {json.loads(k)["result"]: v
            for k, v in d["plancache_put_total"].items()}
    assert puts.get("stored") == 1
    phases = {json.loads(k)["phase"]: v
              for k, v in d["planner_phase_seconds_total"].items()}
    assert phases.get("cache", 0) > 0


def test_plancache_stats_json_cli(capsys):
    from repro.plancache.__main__ import main
    assert main(["stats", "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert "store" in doc and "metrics" in doc
    assert "entries" in doc["store"]


# ------------------------------------------- serving-layer additions (PR 10)
def test_snapshot_meta_block():
    from repro.plancache.keying import SCHEMA_VERSION
    snap = metrics.snapshot()
    meta = snap["_meta"]
    assert meta["pid"] == os.getpid()
    assert meta["start_time"] > 0 and meta["uptime_s"] >= 0
    assert meta["plancache_schema"] == SCHEMA_VERSION
    # existing consumers skip the block: it has no "type" key and never
    # lands in counter aggregations
    assert "type" not in meta
    assert "_meta" not in metrics.counter_totals(snap)
    assert metrics.diff_counters(snap, metrics.snapshot()) == {}


def test_metric_exemplar_rid():
    from repro.obs import context

    def series(snap):
        [s] = snap["t_obs_exemplar"]["series"]
        return s

    metrics.inc("t_obs_exemplar", case="x")
    assert "rid" not in series(metrics.snapshot())   # uncorrelated: no key
    with context.correlate("req") as rid:
        metrics.inc("t_obs_exemplar", case="x")
    assert series(metrics.snapshot())["rid"] == rid
    # an uncorrelated increment never erases the last-seen exemplar
    metrics.inc("t_obs_exemplar", case="x")
    assert series(metrics.snapshot())["rid"] == rid


def test_span_carries_rid():
    from repro.obs import context
    trace.enable()
    with context.correlate("req") as rid:
        with trace.span("corr.span", k="v"):
            pass
    with trace.span("plain.span"):
        pass
    by_name = {e["name"]: e for e in trace.events()}
    assert by_name["corr.span"]["args"] == {"k": "v", "rid": rid}
    assert "args" not in by_name["plain.span"]


def test_sharded_search_propagates_rid_to_workers(fast_search, monkeypatch):
    """Worker processes attach the parent's correlation ID per task, so
    worker spans of a correlated resolve land on the same request ID."""
    from repro.obs import context
    from repro.parallel import search_exec
    hw = get_hw("wormhole_8x8")
    progs = [matmul_program(1024, 1024, 1024, bm=bm, bn=bn, bk=bk)
             for bm in (32, 64) for bn in (32, 64, 128)
             for bk in (64, 128)]
    trace.enable()
    try:
        with context.correlate("req") as rid:
            plan_kernel_multi(progs, hw,
                              budget=SearchBudget(top_k=3, workers=2))
        worker_evs = [e for e in trace.events() if e.get("cat") == "worker"]
        assert worker_evs, "sharded run must merge worker spans"
        assert all(e["args"]["rid"] == rid for e in worker_evs)
        assert all(e["pid"] != os.getpid() for e in worker_evs)
    finally:
        search_exec.shutdown_pool()


def test_killed_worker_trace_and_flightrec(fast_search, monkeypatch,
                                           tmp_path):
    """A worker hard-exiting mid-search must not tear the observability
    stream: the search still succeeds, the merged Chrome trace validates,
    and the flight recorder holds the ``pool_failure`` event."""
    from repro.obs import flightrec
    from repro.parallel import search_exec
    from repro.runtime.faults import FaultSchedule, FaultSpec
    hw = get_hw("wormhole_4x8")
    progs = [matmul_program(256, 256, 256, bm=bm, bn=bn, bk=64)
             for bm in (32, 64) for bn in (32, 64, 128)]
    inline = plan_kernel_multi(progs, hw, profile=True)

    search_exec.shutdown_pool()      # fresh workers must see the marker env
    sched = FaultSchedule([FaultSpec("worker_crash")])
    marker = sched.arm_worker_crash(directory=str(tmp_path))
    flightrec.clear()
    flightrec.enable()
    trace.enable()
    try:
        monkeypatch.setenv("REPRO_PLANNER_WORKERS", "2")
        res = plan_kernel_multi(progs, hw, profile=True)
        assert not os.path.exists(marker)        # a worker really died
        assert res.best.plan.describe() == inline.best.plan.describe()
        assert res.best.final_s == inline.best.final_s
        evs = trace.events()
        assert evs and trace.validate_chrome_trace(
            {"traceEvents": evs}) == []          # not a torn buffer
        fails = [e for e in flightrec.events()
                 if e["kind"] == "pool_failure"]
        assert fails, "worker death must land a pool_failure event"
        assert fails[0]["error"] == "BrokenProcessPool"
        assert {"t", "seq", "attempt", "where"} <= set(fails[0])
    finally:
        FaultSchedule.disarm_worker_crash()
        search_exec.shutdown_pool()
        flightrec.disable()
        flightrec.clear()


def test_hist_quantile_boundary_grid():
    """Satellite (b): ``hist_quantile`` over the boundary grid — empty /
    missing series, q<=0, q>=1, one observation, single occupied bucket,
    and interpolation staying inside [min, max]."""
    def snap_series(kind):
        for s in metrics.snapshot()["t_obs_hq"]["series"]:
            if s["labels"] == {"kind": kind}:
                return s
        return None

    assert metrics.hist_quantile(None, 0.5) is None
    assert metrics.hist_quantile({}, 0.5) is None
    assert metrics.hist_quantile({"count": 0}, 0.5) is None

    metrics.observe("t_obs_hq", 0.2, kind="one")
    s1 = snap_series("one")
    for q in (-1.0, 0.0, 0.25, 0.5, 0.99, 1.0, 2.0):
        assert metrics.hist_quantile(s1, q) == pytest.approx(0.2)

    for v in (0.5, 0.5, 0.5):                    # lo == hi, count > 1
        metrics.observe("t_obs_hq", v, kind="flat")
    assert metrics.hist_quantile(snap_series("flat"), 0.5) \
        == pytest.approx(0.5)

    # a foreign/minimal series without buckets degrades to lerp(min, max)
    bare = {"count": 2, "min": 1.0, "max": 3.0}
    assert metrics.hist_quantile(bare, 0.5) == pytest.approx(2.0)

    for v in (1.0, 2.0, 4.0, 8.0):
        metrics.observe("t_obs_hq", v, kind="spread")
    s = snap_series("spread")
    assert metrics.hist_quantile(s, 0.0) == pytest.approx(1.0)   # exact min
    assert metrics.hist_quantile(s, 1.0) == pytest.approx(8.0)   # exact max
    for q in (0.1, 0.25, 0.5, 0.75, 0.9):
        v = metrics.hist_quantile(s, q)
        assert 1.0 <= v <= 8.0
    # quantiles are monotone in q
    qs = [metrics.hist_quantile(s, q / 20) for q in range(21)]
    assert qs == sorted(qs)
