"""Batched serving driver: prefill + decode loop over a KV cache.

``python -m repro.launch.serve --arch qwen2.5-3b`` greedy-decodes a batch of
synthetic prompts (batch 4, prompt 128, 32 new tokens by default) at the
architecture's published width, with random weights from ``--seed`` kept in
the compute dtype; ``--reduced`` serves the small same-family config.  The
mesh planner's decode ranking is printed; the sharded serve step under that
plan is ``train/serve_step.jit_serve_step``.  Set-up (planning, weight init,
compilation) is reported apart from prefill and decode time.

Serving-layer observability (DESIGN_OBS.md): ``--introspect-port`` starts
a read-only HTTP endpoint (``/metrics`` Prometheus text, ``/healthz``,
``/slo``, ``/plans``, ``/tenants``) before any planning happens;
``--flightrec PATH`` (or ``REPRO_FLIGHTREC``) dumps the structured event
ring buffer at exit for ``python -m repro.obs incident PATH``.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import plancache
from repro.configs import get_config
from repro.obs import expo, flightrec, metrics, slo
from repro.configs.base import ModelConfig, ShapeConfig, TrainConfig
from repro.data import DataConfig, make_source
from repro.launch import use_compile_cache
from repro.models import build_model
from repro.planservice import PlanService


def _plans_view() -> dict:
    """``/plans`` payload: the registry's cross-process stats blob plus
    this serving process's live lookup counters."""
    store = plancache.get_store()
    s = store.stats
    blob = plancache.stats_blob(store)
    blob["process"] = {"hits_mem": s.hits_mem, "hits_disk": s.hits_disk,
                       "misses": s.misses, "puts": s.puts}
    return blob


def _tenants_view(state: dict) -> dict:
    """``/tenants`` payload from the live :class:`TenancyPlan` (filled in
    by :func:`_run_tenants`; empty in single-model mode)."""
    plan = state.get("plan")
    if plan is None:
        return {"mode": "model", "tenants": []}
    return {
        "hw": plan.hw.name,
        "layout_score": plan.layout_score,
        "n_layouts": plan.n_layouts,
        "free_cells": sorted(plan.free_cells()),
        "tenants": [{
            "tenant": p.tenant.name, "qos": p.tenant.qos,
            "rect": p.rect.describe(), "hw": p.hw.name, "rung": p.rung,
            "digest": p.digest, "sim_us": p.sim_s * 1e6,
        } for p in plan.placements],
        "incidents": list(state.get("incidents", [])),
    }


def _setup_observability(args) -> dict:
    """Arm the flight recorder / SLO tracker and (with
    ``--introspect-port``) start the read-only HTTP endpoint *before* any
    planning happens, so the earliest rung decisions are observable."""
    flightrec.refresh_from_env()             # REPRO_FLIGHTREC=<path>
    if args.flightrec:
        flightrec.enable(args.flightrec)
    obs = {"server": None, "plan": None, "incidents": []}
    if args.introspect_port is None and not flightrec.enabled():
        return obs
    slo.enable()                             # honors REPRO_SLO_* knobs
    if args.introspect_port is not None:
        server = expo.IntrospectionServer(port=args.introspect_port)
        server.add_provider("/plans", _plans_view)
        server.add_provider("/tenants", lambda: _tenants_view(obs))
        server.start()
        obs["server"] = server
        # the smoke lane parses this line for the bound (ephemeral) port
        print(f"[serve] introspection at {server.url} "
              f"(/metrics /healthz /slo /plans /tenants)", flush=True)
    return obs


def _finish_observability(args, obs: dict) -> None:
    if flightrec.enabled():
        path = flightrec.dump(reason="serve_done")
        if path:
            print(f"[serve] flight recorder dump: {path}")
    server = obs.get("server")
    if server is not None:
        if args.introspect_hold > 0:
            print(f"[serve] holding introspection open "
                  f"{args.introspect_hold:.1f}s at {server.url}", flush=True)
            time.sleep(args.introspect_hold)
        server.stop()


def _run_tenants(args, obs) -> None:
    """Multi-tenant serving mode (``--tenants k``): plan k concurrent
    kernel tenants onto disjoint partitions of one fabric through the
    tenancy layer, optionally inject a core kill, and *assert* the
    containment contract — the CI tenancy-smoke lane runs exactly this.
    """
    from repro.core import (block_shape_candidates, get_hw, matmul_program)
    from repro.core.planner import SearchBudget
    from repro.tenancy import (IsolationValidator, MeshPartitioner,
                               TenantAdmission, TenantRuntime, TenantSpec)

    hw = get_hw(args.tenant_hw)
    shapes = [(256, 256, 256), (128, 512, 256), (512, 128, 256),
              (256, 512, 128)]
    tenants = []
    for i in range(args.tenants):
        m, n, k = shapes[i % len(shapes)]
        progs = [matmul_program(m, n, k, bm=bm, bn=bn, bk=bk)
                 for bm, bn, bk in block_shape_candidates(m, n, k)][:6]
        qos = "guaranteed" if i % 2 == 0 else "best_effort"
        tenants.append(TenantSpec(f"tenant{i}", progs, qos=qos))

    service = PlanService()
    budget = SearchBudget(top_k=3, max_mappings=16,
                          max_plans_per_mapping=10, max_candidates=500)
    admission = TenantAdmission()
    partitioner = MeshPartitioner(plan_layouts=2)
    # admission gates each tenant's resolve deadline; the joint search
    # receives the per-tenant outcome as its budget override
    tenant_ms = {}
    for t in tenants:
        with admission.admit(t, args.plan_budget_ms) as ms:
            if ms is not None:
                tenant_ms[t.name] = ms
    plan = partitioner.plan(hw, tenants, service=service, budget=budget,
                            budget_ms=float("inf"),
                            tenant_budget_ms=tenant_ms or None)
    bad = IsolationValidator().validate(plan)
    if bad:
        raise SystemExit(f"[serve] isolation validation failed: {bad}")
    obs["plan"] = plan                   # /tenants now serves the live view
    print(f"[serve] {args.tenants} tenants on {hw.name}: "
          f"{plan.describe()}")

    if args.tenant_kill:
        core = tuple(int(v) for v in args.tenant_kill.split(","))
        runtime = TenantRuntime(plan, service=service, cache=service.cache,
                                budget=budget, partitioner=partitioner)
        ev = runtime.kill_core(core)
        obs["plan"] = runtime.plan       # containment may repartition
        obs["incidents"].append({
            "cause": ev.cause, "cell": core, "owner": ev.owner,
            "rung": ev.rung, "blast_radius": ev.blast_radius,
            "seconds": ev.seconds, "within_budget": ev.within_budget,
        })
        print(f"[serve] core_kill {core}: owner={ev.owner} rung={ev.rung} "
              f"blast_radius={ev.blast_radius} "
              f"seconds={ev.seconds * 1e3:.1f}ms "
              f"within_budget={ev.within_budget}")
        for line in ev.log:
            print(f"[serve]   {line}")
        if not ev.contained():
            raise SystemExit("[serve] CONTAINMENT VIOLATED: an untouched "
                             "tenant's plan digest changed")
        if ev.owner is not None and not ev.within_budget:
            raise SystemExit("[serve] deadline exceeded: the degraded "
                             "tenant did not resolve within its budget")
        print(f"[serve] containment ok: untouched={list(ev.untouched)} "
              f"digests unchanged")
    plancache.get_store().flush_stats()
    counts = metrics.counter_totals(metrics.snapshot())
    if counts:
        print("[serve] metrics: " + " ".join(
            f"{k}={v:g}" for k, v in sorted(counts.items())
            if k.startswith(("tenancy", "replan", "planservice"))))
    dumped = metrics.dump()              # honors REPRO_METRICS=<path>
    if dumped:
        print(f"[serve] metrics snapshot written to {dumped}")


def serving_config(arch: str, *, reduced: bool = False) -> ModelConfig:
    """The architecture's config as the server holds it: weights stored in
    the compute dtype.  Float32 weights do not fit one 16 GB chip at
    qwen2.5-3b's width: XLA hoists the per-step float32->bfloat16 casts of
    the scanned block weights out of the layer loop as whole-stack
    converts, on top of the float32 stacks themselves."""
    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced()
    return dataclasses.replace(cfg, param_dtype=cfg.compute_dtype)


def init_params(api, seed: int, shardings=None):
    """Random weights from ``seed``, made on the device (placed by
    ``shardings`` when given) and waited for."""
    init = jax.jit(api.init, out_shardings=shardings)
    return jax.block_until_ready(init(jax.random.PRNGKey(seed)))


def synthetic_prompts(cfg: ModelConfig, batch: int, prompt_len: int,
                      seed: int) -> np.ndarray:
    """Host-side prompt ids (batch, prompt_len), as requests arrive."""
    source = make_source(DataConfig(seed=seed, vocab_size=cfg.vocab_size),
                         cfg)
    return np.asarray(source.batch_at(0, batch, prompt_len)["tokens"],
                      np.int32)


@dataclasses.dataclass
class GreedyRun:
    """One greedy generation: generated ids (B, n), the logits each id was
    picked from (B, n, vocab; row 0 is the last prompt position), and the
    host-clock seconds of each phase (each ends in ``block_until_ready``)."""
    generated: jax.Array
    logits: jax.Array
    prefill_s: float
    decode_s: float


def _pick(logits: jax.Array, vocab_size: int):
    """Greedy choice from a decode step's logits: the last position's row
    over the real vocabulary, and its argmax as the next (B, 1) tokens."""
    row = logits[:, -1, :vocab_size]
    return row, jnp.argmax(row, axis=-1)[:, None].astype(jnp.int32)


def compile_greedy(step, params, tokens, cache, vocab_size: int):
    """AOT-compile ``step`` (a jitted ``decode_step``) and the greedy pick
    for these arguments, so that the timed loop compiles nothing.
    Returns ``(decode, pick)``."""
    decode = step.lower(params, tokens, cache).compile()
    pick = jax.jit(functools.partial(_pick, vocab_size=vocab_size)).lower(
        decode.out_info[0]).compile()
    return decode, pick


def greedy_generate(decode, pick, params, prompts, cache,
                    n_tokens: int) -> GreedyRun:
    """Feed the prompt (host ids, B x P) token by token through ``decode``,
    then greedy-decode ``n_tokens`` ids; ``decode``/``pick`` come from
    :func:`compile_greedy`."""
    prompts = np.asarray(prompts)
    t0 = time.perf_counter()
    for t in range(prompts.shape[1]):
        logits, cache = decode(params, prompts[:, t:t + 1], cache)
    row, tok = pick(logits)
    jax.block_until_ready(tok)
    prefill_s = time.perf_counter() - t0

    rows, out = [row], [tok]
    t0 = time.perf_counter()
    for _ in range(n_tokens - 1):
        logits, cache = decode(params, tok, cache)
        row, tok = pick(logits)
        rows.append(row)
        out.append(tok)
    jax.block_until_ready(tok)
    decode_s = time.perf_counter() - t0
    return GreedyRun(jnp.concatenate(out, axis=1), jnp.stack(rows, axis=1),
                     prefill_s, decode_s)


def serve_model(arch: str, *, reduced: bool = False, batch: int = 4,
                prompt_len: int = 128, tokens: int = 32, seed: int = 0,
                plan_budget_ms: Optional[float] = None):
    """Serve one batch of synthetic prompts on the default device.

    Returns ``(api, params, prompts, run)``.  Set-up (mesh planning, weight
    init, compilation) is timed apart from prefill and decode."""
    cfg = serving_config(arch, reduced=reduced)
    api = build_model(cfg)
    t_setup = time.perf_counter()
    shape = ShapeConfig("serve", seq_len=prompt_len + tokens,
                        global_batch=batch, kind="decode")
    # the serving loop never stalls on planning: the deadline-bounded
    # service answers from cache / family / bounded search / fallback
    resp = PlanService().resolve_mesh(api, shape, TrainConfig(),
                                      budget_ms=plan_budget_ms)
    ranking = resp.ranking or []
    print(f"[serve] {cfg.name}: decode plan ranking "
          f"(rung={resp.rung} {resp.seconds * 1e3:.1f}ms): "
          + ", ".join(r.plan.name for r in ranking[:3]))
    plancache.get_store().flush_stats()
    plan_s = time.perf_counter() - t_setup

    t0 = time.perf_counter()
    params = init_params(api, seed)
    prompts = synthetic_prompts(cfg, batch, prompt_len, seed)
    cache = api.init_cache(cfg, batch, prompt_len + tokens + 1)
    init_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    decode, pick = compile_greedy(
        jax.jit(api.decode_step, donate_argnums=(2,)), params,
        prompts[:, :1], cache, cfg.vocab_size)
    compile_s = time.perf_counter() - t0
    setup_s = time.perf_counter() - t_setup
    print(f"[serve] set-up {setup_s:.2f}s: plan {plan_s:.2f}s, "
          f"init {init_s:.2f}s, compile {compile_s:.2f}s "
          f"({api.n_params():,} params, {cfg.param_dtype})")

    run = greedy_generate(decode, pick, params, prompts, cache, tokens)
    print(f"[serve] prefill {prompt_len} tok x{batch}: {run.prefill_s:.3f}s; "
          f"decode {tokens} tok x{batch}: {run.decode_s:.3f}s "
          f"({tokens * batch / run.decode_s:.1f} tok/s)")
    return api, params, prompts, run


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=128)
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and synthetic prompts")
    ap.add_argument("--plan-budget-ms", type=float, default=None,
                    help="plan-service deadline (default "
                         "$REPRO_PLAN_DEADLINE_MS / 10ms)")
    ap.add_argument("--tenants", type=int, default=0,
                    help="multi-tenant mode: partition the fabric for k "
                         "concurrent kernel tenants instead of serving "
                         "one model")
    ap.add_argument("--tenant-hw", default="wormhole_8x8",
                    help="fabric preset for --tenants mode")
    ap.add_argument("--tenant-kill", default="",
                    help="inject a core kill at mesh coords 'R,C' after "
                         "partitioning and assert containment")
    ap.add_argument("--introspect-port", type=int, default=None,
                    metavar="PORT",
                    help="serve read-only introspection HTTP on PORT "
                         "(0 = ephemeral; prints the bound URL): /metrics "
                         "(Prometheus text), /healthz, /slo, /plans, "
                         "/tenants")
    ap.add_argument("--introspect-hold", type=float, default=0.0,
                    metavar="SECONDS",
                    help="keep the introspection endpoint up SECONDS after "
                         "the run finishes (scrape window for smoke tests)")
    ap.add_argument("--flightrec", default="",
                    metavar="PATH",
                    help="arm the flight recorder and dump its ring buffer "
                         "to PATH at exit (same as REPRO_FLIGHTREC=PATH); "
                         "render with `python -m repro.obs incident PATH`")
    args = ap.parse_args(argv)

    use_compile_cache()
    obs = _setup_observability(args)
    if args.tenants > 0:
        _run_tenants(args, obs)
        _finish_observability(args, obs)
        return

    *_, run = serve_model(args.arch, reduced=args.reduced,
                          batch=args.batch, prompt_len=args.prompt_len,
                          tokens=args.tokens, seed=args.seed,
                          plan_budget_ms=args.plan_budget_ms)
    print(f"[serve] sample generation (ids): "
          f"{run.generated[0, :16].tolist()}")
    counts = metrics.counter_totals(metrics.snapshot())
    if counts:
        print("[serve] metrics: " + " ".join(
            f"{k}={v:g}" for k, v in sorted(counts.items())))
    dumped = metrics.dump()              # honors REPRO_METRICS=<path>
    if dumped:
        print(f"[serve] metrics snapshot written to {dumped}")
    _finish_observability(args, obs)


if __name__ == "__main__":
    main()
