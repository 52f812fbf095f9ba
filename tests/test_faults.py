"""Degraded-mesh planning: fault injection, fault-aware search, elastic
re-planning (DESIGN_FAULTS.md acceptance).

Covers the whole degradation story with *injected* faults:

* the fault overlay on :class:`HardwareModel` (validation, df_text keys,
  link composition, byte-identical fault-free path);
* fault-aware enumeration + simulator masking, bit-identical between the
  scalar and batched engines on degraded fabrics;
* seeded :class:`FaultSchedule` determinism and the ``REPRO_FAULTS`` syntax;
* the re-plan ladder: detection -> re-plan -> resume on a wormhole_8x8
  single-core kill, warmed fault pools answering at rung 1 with zero cold
  searches, and the <=1.35x degraded/healthy quality bound;
* the search pool surviving a killed worker;
* the v3 -> v4 plan-cache schema bump.
"""
import math
import os

import pytest

from repro import plancache
from repro.core import (SearchBudget, block_shape_candidates, get_hw,
                        matmul_program, plan_kernel_multi, simulate,
                        simulate_plans, simulate_reference)
from repro.core.planner import PLAN_CALLS, iter_plan_stream
from repro.obs import metrics
from repro.runtime.faults import FaultSchedule, FaultSpec, parse_faults
from repro.runtime.replan import (ReplanOrchestrator, best_submesh,
                                  plan_degraded)

BUDGET = SearchBudget(top_k=3, max_mappings=16, max_plans_per_mapping=10,
                      max_candidates=500)


@pytest.fixture()
def fresh_store(tmp_path, monkeypatch):
    """An isolated plan store for ladder tests (same idiom as
    tests/test_plancache.py)."""
    monkeypatch.setenv(plancache.ENV_DIR, str(tmp_path))
    monkeypatch.delenv(plancache.ENV_TOGGLE, raising=False)
    plancache.reset_store()
    yield plancache.get_store()
    plancache.reset_store()


def _gemm_progs(M=256, N=256, K=256):
    return [matmul_program(M, N, K, bm=bm, bn=bn, bk=bk)
            for bm, bn, bk in block_shape_candidates(M, N, K)]


# ------------------------------------------------------------ hw overlay
def test_with_faults_validation():
    hw = get_hw("wormhole_4x8")
    with pytest.raises(ValueError):
        hw.with_faults(disabled_cores=[(99, 0)])          # out of range
    with pytest.raises(ValueError):
        hw.with_faults(disabled_cores=[(0,)])             # wrong arity
    with pytest.raises(ValueError):
        hw.with_faults(degraded_links=[("nope", 0.5)])    # unknown link
    with pytest.raises(ValueError):
        hw.with_faults(degraded_links=[("noc_h", 0.0)])   # factor not in (0,1]
    every = [(x, y) for x in range(4) for y in range(8)]
    with pytest.raises(ValueError):
        hw.with_faults(disabled_cores=every)              # nothing left


def test_fault_free_path_is_byte_identical():
    hw = get_hw("wormhole_8x8")
    assert hw.with_faults().df_text() == hw.df_text()
    assert not hw.is_degraded
    assert plancache.hw_digest(hw.with_faults()) == plancache.hw_digest(hw)


def test_fault_overlay_forks_df_text_and_digest():
    hw = get_hw("wormhole_8x8")
    deg = hw.with_faults(disabled_cores=[(3, 5)],
                         degraded_links=[("noc_h", 0.5)])
    assert deg.is_degraded
    assert "df.fault disable %cores[3, 5]" in deg.df_text()
    assert "df.fault degrade %noc_h {factor=0.5}" in deg.df_text()
    assert plancache.hw_digest(deg) != plancache.hw_digest(hw)
    assert deg.healthy_cores == 63
    assert deg.is_disabled({"x": 3, "y": 5})
    assert not deg.is_disabled({"x": 3, "y": 4})


def test_link_degradation_composes_multiplicatively():
    hw = get_hw("wormhole_8x8")
    bw0 = next(ic.bandwidth_gbps for ic in hw.interconnects
               if ic.name == "noc_h")
    deg = hw.with_faults(degraded_links=[("noc_h", 0.5)]) \
            .with_faults(degraded_links=[("noc_h", 0.5)])
    assert dict(deg.degraded_links)["noc_h"] == pytest.approx(0.25)
    bw = next(ic.bandwidth_gbps for ic in deg.interconnects
              if ic.name == "noc_h")
    assert bw == pytest.approx(bw0 * 0.25)


# ------------------------------------------- enumeration + simulator mask
def test_enumeration_routes_around_disabled_cores():
    """No enumerated mapping on a degraded mesh ever activates a disabled
    core, and the scalar / batched / reference simulators agree exactly on
    the masked fabric."""
    hw = get_hw("wormhole_4x8").with_faults(disabled_cores=[(1, 3)])
    prog = matmul_program(320, 192, 256, bm=32, bn=32, bk=64)
    n = 0
    for _, plan in iter_plan_stream(prog, hw, BUDGET):
        assert not plan.mapping.conflicts_with_faults(hw)
        fast = simulate(plan, hw)
        (got,) = simulate_plans([plan], hw)
        assert got.total_s == fast.total_s          # bit-identical engines
        assert got.dram_bytes == fast.dram_bytes
        ref = simulate_reference(plan, hw, max_waves_exact=10 ** 9)
        assert fast.total_s == pytest.approx(ref.total_s, rel=1e-12)
        n += 1
        if n >= 8:
            break
    assert n >= 4


def test_healthy_enumeration_unchanged_by_overlay_support():
    """The fault-free search space is untouched: the overlay-aware
    enumerator yields the identical plan list for an empty overlay."""
    hw = get_hw("wormhole_4x8")
    prog = matmul_program(256, 256, 256, bm=64, bn=64, bk=64)
    a = [p.describe() for _, p in iter_plan_stream(prog, hw, BUDGET)]
    b = [p.describe() for _, p in
         iter_plan_stream(prog, hw.with_faults(), BUDGET)]
    assert a == b and a


# ------------------------------------------------------- fault schedules
def test_parse_faults_syntax():
    s = parse_faults("core:3,5;link:noc_h:0.5@2;straggler:1;crash")
    kinds = [f.kind for f in s]
    assert sorted(kinds) == ["core_kill", "host_straggler", "link_slow",
                             "worker_crash"]
    hw = get_hw("wormhole_8x8")
    assert s.degraded_hw(hw, 0).degraded_links == ()     # link fault @2
    assert s.degraded_hw(hw, 2).degraded_links == (("noc_h", 0.5),)
    assert s.degraded_hw(hw, 0).disabled_cores == ((3, 5),)
    assert s.straggler_factor(1, 0) == 3.0
    assert s.straggler_factor(0, 0) == 1.0
    assert s.worker_crashes() == 1
    with pytest.raises(ValueError):
        parse_faults("core:banana")
    with pytest.raises(ValueError):
        FaultSpec("not_a_kind")


def test_seeded_schedules_are_deterministic():
    hw = get_hw("wormhole_8x8")
    a = FaultSchedule.seeded(7, hw=hw, n_steps=5, n_hosts=4, n_faults=4)
    b = FaultSchedule.seeded(7, hw=hw, n_steps=5, n_hosts=4, n_faults=4)
    assert a.describe() == b.describe()
    c = FaultSchedule.seeded(8, hw=hw, n_steps=5, n_hosts=4, n_faults=4)
    assert a.describe() != c.describe()
    assert FaultSchedule.seeded(1, hw=hw, kinds=["core_kill"]).faults[0] \
        .kind == "core_kill"
    # without hw/hosts only worker crashes are drawable
    assert all(f.kind == "worker_crash" for f in FaultSchedule.seeded(1))
    with pytest.raises(ValueError):
        FaultSchedule.seeded(1, kinds=["bogus"])
    with pytest.raises(ValueError):
        FaultSchedule.seeded(1, kinds=[])        # nothing drawable


def test_fault_free_schedule_passthrough():
    hw = get_hw("wormhole_8x8")
    s = FaultSchedule([FaultSpec("worker_crash")])
    assert s.degraded_hw(hw) is hw               # no hw faults -> same object


def test_schedule_skips_faults_that_do_not_fit_the_mesh():
    # one REPRO_FAULTS setting is applied across benchmark sweeps over many
    # mesh shapes: faults outside a given fabric are skipped, not raised
    s = parse_faults("core:3,5;link:noc_h:0.5")
    small = get_hw("wormhole_1x8")
    deg = s.degraded_hw(small)                   # core (3,5) out of range
    assert not deg.disabled_cores
    big = s.degraded_hw(get_hw("wormhole_8x8"))
    assert big.disabled_core_set() == {(3, 5)}
    # a schedule that would kill every core leaves the fabric alone
    wipe = FaultSchedule([FaultSpec("core_kill", core=(0, c))
                          for c in range(8)])
    assert wipe.degraded_hw(small) is small


# ------------------------------------------------------ submesh fallback
def test_best_submesh_drops_the_cheapest_axis():
    hw = get_hw("wormhole_8x8")
    sub = best_submesh(hw.with_faults(disabled_cores=[(3, 5)]))
    assert sub.mesh_dims in ((("x", 7), ("y", 8)), (("x", 8), ("y", 7)))
    assert sub.n_cores == 56 and not sub.is_degraded
    # two holes in one column still cost only that column
    sub2 = best_submesh(hw.with_faults(disabled_cores=[(2, 5), (6, 5)]))
    assert sub2.n_cores == 56
    # the submesh still has a full interconnect set to plan against
    assert len(sub.interconnects) == len(hw.interconnects)


# -------------------------------------------------------- re-plan ladder
def test_detection_replan_resume_on_single_core_kill(fast_search,
                                                     fresh_store):
    """The headline path: a host heartbeat goes silent on wormhole_8x8, the
    orchestrator disables its cores, walks the ladder, and hands back a
    runnable plan for the surviving fabric — then the *same* failure
    re-plans as a pure cache hit (zero cold searches), as a warmed fault
    pool would."""
    from repro.runtime.fault_tolerance import HeartbeatRegistry
    hw = get_hw("wormhole_8x8")
    progs = _gemm_progs(512, 512, 512)
    reg = HeartbeatRegistry(2, timeout_s=10.0, now=0.0)
    orch = ReplanOrchestrator(hw, progs, registry=reg,
                              cache=plancache.PlanCache(),
                              host_cores={1: [(3, 5)]})
    reg.beat(0, 0, 1.0, now=0.0)
    reg.beat(1, 0, 1.0, now=0.0)
    assert orch.poll(now=5.0) is None            # everyone healthy
    reg.beat(0, 1, 1.0, now=20.0)                # host 1 went silent
    out = orch.poll(now=20.0)
    assert out is not None and out.cause == "core_kill"
    assert out.rung in ("bounded_search", "warm_search", "submesh_fallback")
    assert orch.current_hw.disabled_cores == ((3, 5),)
    # resume: the chosen plan simulates on its target model
    sim = simulate(out.plan, out.hw)
    assert sim.total_s == out.result.best.final_s > 0
    # second identical failure: rung-1 hit, zero planner searches
    calls = dict(PLAN_CALLS)
    hits = metrics.REGISTRY.counter("plancache_get_total")
    h0 = hits.value(result="hit_mem") + hits.value(result="hit_disk")
    again = plan_degraded(progs, orch.current_hw, healthy_hw=hw,
                          cache=plancache.PlanCache(), cause="core_kill")
    assert again.rung == "cache_hit"
    assert dict(PLAN_CALLS) == calls             # no cold search at all
    assert hits.value(result="hit_mem") + hits.value(result="hit_disk") > h0
    assert again.result.best.final_s == out.result.best.final_s
    m = metrics.counter_totals(metrics.snapshot(), ["replan_total"])
    assert m.get("replan_total", 0) >= 2


def test_degraded_plan_quality_within_bound(fast_search):
    """Acceptance: geomean(degraded / healthy simulated time) <= 1.35 over
    the gemm suite for a single dead core on wormhole_8x8 — the submesh
    quality floor is what keeps the full-mesh hole-avoiding plans from
    dominating."""
    hw = get_hw("wormhole_8x8")
    deg = hw.with_faults(disabled_cores=[(3, 5)])
    ratios = []
    for (M, N, K) in ((256, 256, 256), (512, 512, 512), (512, 1024, 512)):
        progs = _gemm_progs(M, N, K)
        out = plan_degraded(progs, deg, healthy_hw=hw, cause="bench")
        healthy = plan_kernel_multi(progs, hw, profile=True)
        ratios.append(out.result.best.final_s / healthy.best.final_s)
    geo = math.exp(sum(math.log(r) for r in ratios) / len(ratios))
    assert geo <= 1.35, f"degraded/healthy geomean {geo:.3f} > 1.35x"


def test_plan_degraded_rejects_healthy_mesh():
    with pytest.raises(ValueError):
        plan_degraded(_gemm_progs(), get_hw("wormhole_4x8"))


def test_replan_latency_budget_falls_back_to_submesh(fast_search):
    """An already-blown latency budget skips the bounded search and goes
    straight to the guaranteed submesh fallback (and says so)."""
    hw = get_hw("wormhole_8x8").with_faults(disabled_cores=[(0, 0)])
    out = plan_degraded(_gemm_progs(), hw, latency_budget_s=0.0,
                        cause="core_kill")
    assert out.rung == "submesh_fallback"
    assert not out.within_budget
    assert any("skipping" in line for line in out.log)
    m = metrics.counter_totals(metrics.snapshot(),
                               ["replan_budget_exceeded_total"])
    assert m.get("replan_budget_exceeded_total", 0) >= 1


def test_orchestrator_straggler_and_link_paths(fast_search, fresh_store):
    from repro.runtime.fault_tolerance import (HeartbeatRegistry,
                                               StragglerTracker)
    hw = get_hw("wormhole_4x8")
    reg = HeartbeatRegistry(3, timeout_s=1e9, now=0.0)
    for step in range(10):
        for h in range(3):
            reg.beat(h, step, 4.0 if h == 2 else 1.0, now=float(step))
    orch = ReplanOrchestrator(hw, _gemm_progs(), registry=reg,
                              tracker=StragglerTracker(reg),
                              host_cores={2: [(0, 7)]})
    out = orch.poll(now=9.0)
    assert out is not None and out.cause == "straggler"
    assert (0, 7) in orch.current_hw.disabled_core_set()
    assert orch.poll(now=9.5) is None            # handled hosts don't repeat
    out2 = orch.degrade_links([("noc_h", 0.5)])
    assert out2.cause == "link_slow"
    assert dict(orch.current_hw.degraded_links)["noc_h"] == 0.5
    assert len(orch.outcomes) == 2


# ------------------------------------------------- pool worker hardening
def test_killed_search_worker_does_not_fail_plan(fast_search, monkeypatch,
                                                 tmp_path):
    """Acceptance: a search worker hard-exiting mid-shard no longer fails
    plan_kernel_multi — the pool is rebuilt and the result is identical to
    the inline search."""
    from repro.parallel import search_exec
    hw = get_hw("wormhole_4x8")
    progs = _gemm_progs(256, 256, 256)
    inline = plan_kernel_multi(progs, hw, profile=True)

    # workers of a pool an earlier test left were started before the crash
    # is armed and never see it: start from no pool
    search_exec.shutdown_pool()
    sched = FaultSchedule([FaultSpec("worker_crash")])
    marker = sched.arm_worker_crash(directory=str(tmp_path))
    try:
        monkeypatch.setenv("REPRO_PLANNER_WORKERS", "2")
        fails = metrics.REGISTRY.counter("search_pool_failures_total")
        f0 = fails.total()
        res = plan_kernel_multi(progs, hw, profile=True)
        assert not os.path.exists(marker)        # a worker really died
        assert fails.total() > f0
        assert res.best.plan.describe() == inline.best.plan.describe()
        assert res.best.final_s == inline.best.final_s
    finally:
        FaultSchedule.disarm_worker_crash()
        search_exec.shutdown_pool()


def test_degraded_hw_ships_to_pool_workers(fast_search, monkeypatch):
    """The preset_faults transport: a degraded preset round-trips into
    worker processes and the sharded search matches inline exactly."""
    from repro.parallel import search_exec
    hw = get_hw("wormhole_4x8").with_faults(disabled_cores=[(1, 3)])
    spec = search_exec.hw_spec(hw)
    assert spec is not None and spec[0] == "preset_faults"
    assert search_exec.hw_from_spec(spec).df_text() == hw.df_text()
    progs = _gemm_progs(256, 256, 256)
    inline = plan_kernel_multi(progs, hw, profile=True)
    try:
        monkeypatch.setenv("REPRO_PLANNER_WORKERS", "2")
        sharded = plan_kernel_multi(progs, hw, profile=True)
        assert sharded.best.plan.describe() == inline.best.plan.describe()
        assert sharded.best.final_s == inline.best.final_s
    finally:
        search_exec.shutdown_pool()


# -------------------------------------------------- schema compatibility
def test_v3_schema_entries_are_misses_after_fault_overlay_bump(tmp_path,
                                                               monkeypatch):
    """Backward compat across the v3 -> v4 schema bump (fault-overlay hw
    keys): pre-bump entries read as misses — counted, never deserialized —
    mirroring the v1 -> v2 and v2 -> v3 cases; and a degraded fabric keys
    differently from its healthy twin."""
    import json
    assert plancache.keying.SCHEMA_VERSION >= 4
    store = plancache.PlanCacheStore(tmp_path, enabled=True)
    hw = get_hw("wormhole_8x8")
    deg = hw.with_faults(disabled_cores=[(0, 0)])
    prog = matmul_program(256, 256, 256, bm=64, bn=64, bk=64)
    k_h = plancache.kernel_key([prog], hw, BUDGET)
    k_d = plancache.kernel_key([prog], deg, BUDGET)
    assert k_h != k_d                            # fault overlay forks the key
    store.put(k_d, {"result": {"kernel": "stale-v3-layout"}}, {})
    p = store._path(k_d)
    data = json.loads(p.read_text())
    data["schema"] = 3                           # a real pre-bump entry
    p.write_text(json.dumps(data))
    store.clear_memory()
    misses = store.stats.misses
    assert store.get(k_d) is None
    assert store.stats.misses == misses + 1
