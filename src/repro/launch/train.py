"""End-to-end training driver.

``python -m repro.launch.train --arch qwen2.5-3b --steps 300 --reduced``
trains a reduced config on the host; on a real pod the same driver runs the
full config with the TileLoom-planned sharding.  Integrates every substrate:
planned sharding, microbatched train step, deterministic data, checkpoint
manager with auto-resume, heartbeat/straggler tracking, resilient step
retry.
"""
from __future__ import annotations

import argparse
import time
from pathlib import Path

import jax
import jax.numpy as jnp

from repro import plancache
from repro.configs import get_config
from repro.configs.base import ShapeConfig, TrainConfig
from repro.ckpt import CheckpointManager
from repro.data import DataConfig, make_source
from repro.models import build_model
from repro.obs import metrics as obs_metrics
from repro.parallel.planner_bridge import plan_mesh
from repro.runtime import (HeartbeatRegistry, ResilientDriver,
                           StragglerTracker)
from repro.runtime.faults import env_schedule
from repro.train import train_step as TS
from . import use_compile_cache


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--grad-compression", default="none",
                    choices=("none", "int8"))
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--save-every", type=int, default=50)
    ap.add_argument("--reduced", action="store_true",
                    help="train the reduced config (CPU-friendly)")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    use_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    tcfg = TrainConfig(learning_rate=args.lr, total_steps=args.steps,
                       warmup_steps=max(1, args.steps // 20),
                       microbatches=args.microbatches,
                       grad_compression=args.grad_compression)
    api = build_model(cfg)
    shape = ShapeConfig("cli", seq_len=args.seq, global_batch=args.batch,
                        kind="train")

    # TileLoom mesh planning (informational on a 1-device host; resolves
    # from the persistent plan registry when `repro.plancache warm` ran)
    store = plancache.get_store()
    with plancache.lookup_source(store) as probe:
        ranking = plan_mesh(api, shape, tcfg)
    print(f"[train] {cfg.name}: {api.n_params():,} params; planner ranking "
          f"({probe['source']}): "
          + ", ".join(f"{r.plan.name}({r.cost.dominant})" for r in ranking[:3]))
    store.flush_stats()

    step_fn = jax.jit(TS.make_train_step(api, tcfg))
    mgr = CheckpointManager(Path(args.ckpt_dir) / cfg.name,
                            save_every=args.save_every, keep=3)
    template = TS.abstract_state(api, tcfg)
    state, start = mgr.restore_latest(target_tree=template)
    if state is None:
        state = TS.init_state(api, tcfg, jax.random.PRNGKey(tcfg.seed))
        start = 0
        print("[train] fresh start")
    else:
        print(f"[train] resumed from step {start}")

    source = make_source(DataConfig(vocab_size=cfg.vocab_size), cfg)
    reg = HeartbeatRegistry(1)
    straggler = StragglerTracker(reg)

    # fault injection (REPRO_FAULTS): host-straggler factors scale the step
    # wall-times reported into the heartbeat registry so detection paths run
    # under injected load; hw faults apply inside the planner/benchmarks
    sched = env_schedule()
    if sched is not None:
        print(f"[train] injected faults: {sched.describe()}")

    def timed_step(state, batch):
        out_state, metrics = step_fn(state, batch)
        jax.block_until_ready(metrics["loss"])
        return out_state, metrics

    def batches(step):
        return jax.tree.map(jnp.asarray,
                            source.batch_at(step, args.batch, args.seq))

    def restore_fn():
        tree, at = mgr.restore_latest(target_tree=template)
        if tree is None:
            return TS.init_state(api, tcfg, jax.random.PRNGKey(tcfg.seed)), 0
        return tree, at

    def on_step(step, state, metrics, dt):
        if (step - 1) % args.log_every == 0 or step == args.steps:
            tok_s = args.batch * args.seq / max(dt, 1e-9)
            print(f"[train] step {step - 1:5d} "
                  f"loss={float(metrics['loss']):.4f} "
                  f"gnorm={float(metrics['grad_norm']):.3f} "
                  f"lr={float(metrics['lr']):.2e} {tok_s:,.0f} tok/s")

    drv = ResilientDriver(
        timed_step, mgr, registry=reg, tracker=straggler,
        step_time_scale=(None if sched is None
                         else lambda s: sched.straggler_factor(0, s)))
    t_start = time.perf_counter()
    state, _, _ = drv.run(state, batches, start_step=start,
                          n_steps=args.steps - start,
                          restore_fn=restore_fn, on_step=on_step)
    mgr.wait()
    total = time.perf_counter() - t_start
    print(f"[train] done: {args.steps - start} steps in {total:.1f}s; "
          f"stragglers={straggler.stragglers()}")
    for ev in drv.events:
        print(f"[train] recovery: step {ev.step} {ev.kind}: {ev.detail}")
    counts = obs_metrics.counter_totals(obs_metrics.snapshot())
    if counts:
        print("[train] metrics: " + " ".join(
            f"{k}={v:g}" for k, v in sorted(counts.items())))
    dumped = obs_metrics.dump()          # honors REPRO_METRICS=<path>
    if dumped:
        print(f"[train] metrics snapshot written to {dumped}")


if __name__ == "__main__":
    main()
