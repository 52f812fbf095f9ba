"""Fast-search equivalence proofs (DESIGN_SEARCHPERF.md acceptance).

The cold-path optimizations must not change what the planner selects:

* wave-equivalence-class simulation == the wave-by-wave reference loop,
  exactly (same totals, traffic, wave counts) across ragged waves,
  broadcasts, and hoisted loads;
* branch-and-bound ranking picks the identical best/top-k as exhaustive
  ranking (every candidate estimated);
* the lower bound is admissible (never exceeds the true model cost), which
  is the property the pruning proof rests on;
* the batched (SoA / numpy) cost engine reproduces the scalar model
  bit-for-bit — estimates, simulations, and the selected top-k — and the
  process-sharded search merges to the identical top-k as the inline one
  for any worker count.
"""
import math
import os

import pytest

try:                    # numpy is optional (the planner degrades to the
    import numpy as np  # scalar engine); only the batch tests need it
except ImportError:     # pragma: no cover - numpy ships in CI
    np = None

needs_numpy = pytest.mark.skipif(
    np is None, reason="numpy unavailable (batch engine disabled)")

from repro.core import (MappingBatch, SearchBudget, estimate,
                        flash_attention_program, get_hw, matmul_program,
                        plan_kernel, plan_kernel_multi, plan_lower_bound,
                        simulate, simulate_plans, simulate_reference)
from repro.core.plan import DataflowPlan
from repro.core.planner import _filtered_mappings, iter_plan_stream
from repro.core.program import LoopDim, TileProgram
from repro.core.reuse import memop_choices_with_stores


def _plan_grid():
    """A grid of small plans covering ragged final waves, broadcasts, and
    hoisted loads on all three mesh shapes."""
    cases = [
        # (program, hw): ragged extents force partially-active waves
        (matmul_program(320, 192, 256, bm=32, bn=32, bk=64),
         get_hw("wormhole_8x8")),
        (matmul_program(1000, 520, 260, bm=64, bn=32, bk=32),
         get_hw("wormhole_4x8")),
        (matmul_program(768, 768, 512, bm=64, bn=64, bk=64),
         get_hw("wormhole_1x8")),
        (flash_attention_program(9, 640, 640, 64, bq=64, bkv=32),
         get_hw("wormhole_8x8")),
        (flash_attention_program(64, 1024, 1024, 64, bq=64, bkv=64),
         get_hw("wormhole_4x8")),
    ]
    budget = SearchBudget(max_mappings=16, max_plans_per_mapping=10)
    for prog, hw in cases:
        n = 0
        for _, plan in iter_plan_stream(prog, hw, budget):
            yield plan, hw
            n += 1
            if n >= 25:
                break


def test_simulate_matches_reference():
    """The class-based simulator is exact: identical totals and traffic to
    the wave-by-wave loop (at stride 1) for every plan in the grid."""
    checked = broadcasts = hoisted = ragged = 0
    for plan, hw in _plan_grid():
        fast = simulate(plan, hw)
        ref = simulate_reference(plan, hw, max_waves_exact=10 ** 9)
        assert fast.total_s == pytest.approx(ref.total_s, rel=1e-12)
        assert fast.dram_bytes == pytest.approx(ref.dram_bytes, rel=1e-12)
        assert fast.noc_bytes == pytest.approx(ref.noc_bytes, rel=1e-12)
        assert fast.flops == ref.flops
        assert fast.n_waves == ref.n_waves
        assert 1 <= fast.n_wave_classes <= max(1, fast.n_waves)
        checked += 1
        broadcasts += any(c.bcast_axes for c in plan.loads)
        n_loops = len(plan.mapping.temporal) + len(plan.program.seq_dims)
        hoisted += any(c.hoist.level < n_loops for c in plan.loads)
        ragged += plan.mapping.utilization() < 1.0
    # the grid must actually exercise the features it claims to cover
    assert checked >= 50
    assert broadcasts > 0 and hoisted > 0 and ragged > 0


def test_wave_class_compression():
    """Large wave spaces collapse into a handful of classes — the reason the
    max_waves_exact sampling cut could be retired."""
    hw = get_hw("wormhole_8x8")
    res = plan_kernel(matmul_program(16384, 16384, 4096,
                                     bm=128, bn=128, bk=64), hw,
                      budget=SearchBudget(top_k=1))
    sim = res.best.sim
    assert sim.n_waves >= 256
    assert sim.n_wave_classes <= 16
    assert res.n_wave_classes == sim.n_wave_classes


def test_lower_bound_admissible():
    """plan_lower_bound(plan) <= estimate(plan).total_s in both overlap
    modes — the admissibility obligation of the branch-and-bound proof."""
    n = 0
    for plan, hw in _plan_grid():
        for pol in (False, True):
            lb = plan_lower_bound(plan, hw, pipeline_outer_levels=pol)
            cost = estimate(plan, hw, pipeline_outer_levels=pol)
            assert lb <= cost.total_s * (1 + 1e-12), plan.describe()
            assert lb > 0
            n += 1
    assert n >= 100


def _keyed(res):
    return [(c.plan.describe(), c.cost.total_s,
             c.sim.total_s if c.sim else None) for c in res.topk]


@pytest.mark.parametrize("seed_shape", [(512, 512, 512), (640, 384, 512),
                                        (1024, 1024, 1024)])
def test_bnb_matches_exhaustive_single(seed_shape):
    M, N, K = seed_shape
    hw = get_hw("wormhole_8x8")
    budget = SearchBudget(top_k=4)
    mk = lambda: matmul_program(M, N, K, bm=64, bn=64, bk=64)
    fast = plan_kernel(mk(), hw, budget=budget, use_bound=True)
    slow = plan_kernel(mk(), hw, budget=budget, use_bound=False)
    assert fast.best.plan == slow.best.plan
    assert _keyed(fast) == _keyed(slow)
    # n_candidates counts *ranked* candidates: whole-mapping pruning keeps
    # the fast path from even materializing provably-worse plans
    assert fast.n_candidates <= slow.n_candidates
    assert fast.n_estimated <= slow.n_estimated
    assert slow.n_pruned == 0 and slow.n_mappings_pruned == 0


def test_bnb_matches_exhaustive_multi():
    """Pooled block-shape search: identical best/top-k with and without
    pruning, across a seeded grid of programs."""
    hw = get_hw("wormhole_4x8")
    budget = SearchBudget(top_k=5, max_plans_per_mapping=24)
    mk = lambda: [matmul_program(768, 768, 768, bm=bm, bn=bn, bk=64)
                  for bm in (32, 64, 128) for bn in (32, 64, 128)]
    fast = plan_kernel_multi(mk(), hw, budget=budget, use_bound=True)
    slow = plan_kernel_multi(mk(), hw, budget=budget, use_bound=False)
    assert fast.best.plan == slow.best.plan
    assert _keyed(fast) == _keyed(slow)
    assert fast.n_pruned + fast.n_mappings_pruned > 0  # pruning did engage


def test_multi_counts_infeasible_and_reraises():
    hw = get_hw("wormhole_8x8")
    ok = matmul_program(512, 512, 512, bm=64, bn=64, bk=64)
    # capacity-infeasible: no memory-op combination fits the 1.5MB L1
    too_big = matmul_program(8192, 8192, 8192, bm=1024, bn=1024, bk=1024)
    res = plan_kernel_multi([too_big, ok], hw,
                            budget=SearchBudget(top_k=2), profile=False)
    assert res.n_infeasible_programs == 1
    assert any("no feasible plan" in line for line in res.log)
    assert res.best.plan.program.name == ok.name

    # a genuine bug (TypeError from a malformed program) must propagate,
    # not be swallowed as "infeasible"
    broken = TileProgram(name="broken",
                         grid_dims=(LoopDim("gx", None), LoopDim("gy", 8)),
                         seq_dims=(LoopDim("k", 8),),
                         loads=ok.loads, stores=ok.stores, body=ok.body)
    with pytest.raises(TypeError):
        plan_kernel_multi([broken, ok], hw, budget=SearchBudget(top_k=1),
                          profile=False)


def test_floor_pruned_program_is_not_infeasible():
    """A feasible program whose every mapping the compute floor skips
    (provably worse than the incumbent top-k) must not be reported as
    infeasible — pruned and infeasible are different outcomes."""
    hw = get_hw("wormhole_8x8")
    good = matmul_program(1024, 1024, 1024, bm=64, bn=64, bk=64)
    # same shape but 8x the K reduction: strictly more compute everywhere,
    # so with top_k=1 its mappings all fall below the incumbent's floor
    worse = matmul_program(1024, 1024, 8192, bm=64, bn=64, bk=64)
    res = plan_kernel_multi([good, worse], hw,
                            budget=SearchBudget(top_k=1), profile=False)
    assert res.n_infeasible_programs == 0
    assert res.log == []
    assert res.best.plan.program.name == good.name


# --------------------------------------------------------------------------
# Batched (SoA) cost engine vs the scalar oracle
# --------------------------------------------------------------------------
def _mapping_grid():
    """(mapping, stores, combos, demands, hw) cells spanning all three mesh
    shapes, both kernels, ragged grids, broadcasts, and hoisted loads."""
    cases = [
        (matmul_program(320, 192, 256, bm=32, bn=32, bk=64),
         get_hw("wormhole_8x8")),
        (matmul_program(1000, 520, 260, bm=64, bn=32, bk=32),
         get_hw("wormhole_4x8")),
        (matmul_program(768, 768, 512, bm=64, bn=64, bk=64),
         get_hw("wormhole_1x8")),
        (flash_attention_program(9, 640, 640, 64, bq=64, bkv=32),
         get_hw("wormhole_8x8")),
    ]
    budget = SearchBudget(max_mappings=16, max_plans_per_mapping=12)
    for prog, hw in cases:
        for mapping in _filtered_mappings(prog, hw, budget)[:6]:
            demands = {}
            combos, stores = memop_choices_with_stores(
                mapping, hw, max_per_load=budget.max_per_load,
                demands=demands)
            combos = combos[:12]
            if combos:
                yield mapping, stores, combos, demands, hw


@needs_numpy
def test_batch_estimates_bit_identical_to_scalar():
    """MappingBatch.estimate_rows == estimate() field-for-field (exact
    float equality, not just 1e-12): the SoA engine mirrors the scalar
    operation order, which is what makes engine choice selection-invariant.
    """
    n = 0
    for mapping, stores, combos, demands, hw in _mapping_grid():
        for pol in (False, True):
            batch = MappingBatch(mapping, stores, hw, combos,
                                 pipeline_outer_levels=pol, demands=demands)
            costs = batch.estimate_rows(np.arange(len(combos)))
            for j, combo in enumerate(combos):
                plan = DataflowPlan(mapping, combo, stores)
                ref = estimate(plan, hw, pipeline_outer_levels=pol)
                got = costs.cost(j)
                assert got == ref, (plan.describe(), pol)
                n += 1
    assert n >= 100


@needs_numpy
def test_batch_bounds_admissible_and_match_scalar():
    """Batched lower bounds stay admissible (<= the true model cost, the
    branch-and-bound obligation) and agree with the scalar BoundContext to
    1e-12 (summation-order rounding is all that may differ)."""
    n = 0
    for mapping, stores, combos, demands, hw in _mapping_grid():
        for pol in (False, True):
            batch = MappingBatch(mapping, stores, hw, combos,
                                 pipeline_outer_levels=pol, demands=demands)
            lbs = batch.lower_bounds()
            for j, combo in enumerate(combos):
                plan = DataflowPlan(mapping, combo, stores)
                ref_lb = plan_lower_bound(plan, hw,
                                          pipeline_outer_levels=pol)
                assert lbs[j] == pytest.approx(ref_lb, rel=1e-12)
                cost = estimate(plan, hw, pipeline_outer_levels=pol)
                assert lbs[j] <= cost.total_s * (1 + 1e-9)
                n += 1
    assert n >= 100


@needs_numpy
def test_simulate_plans_bit_identical_to_scalar():
    """The vectorized wave-class simulator == simulate() exactly: totals,
    traffic, wave and class counts."""
    checked = 0
    for plan, hw in _plan_grid():
        (got,) = simulate_plans([plan], hw)
        ref = simulate(plan, hw)
        assert (got.total_s, got.dram_bytes, got.noc_bytes, got.flops,
                got.n_waves, got.n_wave_classes) == \
               (ref.total_s, ref.dram_bytes, ref.noc_bytes, ref.flops,
                ref.n_waves, ref.n_wave_classes), plan.describe()
        checked += 1
    assert checked >= 50


@needs_numpy
def test_batch_engine_selects_identically_to_scalar():
    """plan_kernel / plan_kernel_multi pick the identical top-k (same
    candidate indices, same costs to the bit) under engine="batch" and
    engine="scalar"."""
    hw = get_hw("wormhole_4x8")
    budget = SearchBudget(top_k=5, max_plans_per_mapping=24)
    mk = lambda: [matmul_program(768, 768, 768, bm=bm, bn=bn, bk=64)
                  for bm in (32, 64, 128) for bn in (32, 64, 128)]
    b = plan_kernel_multi(mk(), hw, budget=budget, engine="batch")
    s = plan_kernel_multi(mk(), hw, budget=budget, engine="scalar")
    key = lambda r: [(c.plan.describe(), c.index, c.cost.total_s,
                      c.sim.total_s if c.sim else None) for c in r.topk]
    assert key(b) == key(s)
    single_b = plan_kernel(matmul_program(640, 384, 512, bm=64, bn=64,
                                          bk=64), hw, budget=budget,
                           engine="batch")
    single_s = plan_kernel(matmul_program(640, 384, 512, bm=64, bn=64,
                                          bk=64), hw, budget=budget,
                           engine="scalar")
    assert key(single_b) == key(single_s)


# --------------------------------------------------------------------------
# Process-sharded search vs inline
# --------------------------------------------------------------------------
@pytest.mark.parametrize("workers", [1, 2, 4])
def test_parallel_matches_inline(workers):
    """The sharded search merges per-chunk top-k by (cost, canonical
    index) into the exact inline result: identical candidate indices,
    identical tie-breaking, costs equal to the bit — for any worker count.
    """
    hw = get_hw("wormhole_8x8")
    mk = lambda: [matmul_program(640, 640, 512, bm=bm, bn=bn, bk=64)
                  for bm in (32, 64) for bn in (32, 64, 128)]
    inline = plan_kernel_multi(mk(), hw,
                               budget=SearchBudget(top_k=5, workers=1))
    sharded = plan_kernel_multi(mk(), hw,
                                budget=SearchBudget(top_k=5,
                                                    workers=workers))
    key = lambda r: [(c.plan.describe(), c.index, c.cost.total_s,
                      c.sim.total_s if c.sim else None) for c in r.topk]
    assert key(sharded) == key(inline)
    assert sharded.best.plan == inline.best.plan


def _worker_backend(_):
    import jax
    return (os.environ.get("JAX_PLATFORMS"), jax.config.jax_platforms,
            jax.devices()[0].platform)


def test_pool_workers_never_reach_the_accelerator(monkeypatch):
    """A planning process that has imported JAX (and so may hold the chip)
    starts pool workers that can only reach JAX's CPU backend, even when
    the parent's environment asks for the TPU."""
    import jax  # noqa: F401 - the parent process holds JAX
    from repro.parallel import search_exec
    search_exec.shutdown_pool()
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    try:
        got = search_exec.map_jobs(_worker_backend, [0, 1], workers=2)
    finally:
        search_exec.shutdown_pool()
    assert got == [("cpu", "cpu", "cpu")] * 2


def test_parallel_env_knob_and_infeasible_accounting(monkeypatch):
    """REPRO_PLANNER_WORKERS engages sharding; infeasible programs are
    counted identically, and planner bugs still propagate across the
    process boundary."""
    monkeypatch.setenv("REPRO_PLANNER_WORKERS", "2")
    hw = get_hw("wormhole_8x8")
    ok = matmul_program(512, 512, 512, bm=64, bn=64, bk=64)
    too_big = matmul_program(8192, 8192, 8192, bm=1024, bn=1024, bk=1024)
    res = plan_kernel_multi([too_big, ok], hw,
                            budget=SearchBudget(top_k=2), profile=False)
    assert res.n_infeasible_programs == 1
    assert any("no feasible plan" in line for line in res.log)
    assert res.best.plan.program.name == ok.name
    broken = TileProgram(name="broken",
                         grid_dims=(LoopDim("gx", None), LoopDim("gy", 8)),
                         seq_dims=(LoopDim("k", 8),),
                         loads=ok.loads, stores=ok.stores, body=ok.body)
    with pytest.raises(TypeError):
        plan_kernel_multi([broken, ok], hw, budget=SearchBudget(top_k=1),
                          profile=False)


def test_streamed_enumeration_matches_caps():
    """iter_plan_stream honors max_plans_per_mapping/max_candidates exactly
    like the historical list builder."""
    hw = get_hw("wormhole_8x8")
    prog = matmul_program(1024, 1024, 1024, bm=64, bn=64, bk=64)
    small = SearchBudget(max_plans_per_mapping=3, max_candidates=17)
    plans = [p for _, p in iter_plan_stream(prog, hw, small)]
    assert len(plans) == 17
    per_mapping = {}
    for p in plans:
        per_mapping[p.mapping] = per_mapping.get(p.mapping, 0) + 1
    assert max(per_mapping.values()) <= 3
