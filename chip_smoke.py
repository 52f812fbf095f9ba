"""On-chip smoke run: the serving path and every Pallas kernel on a TPU.

    python chip_smoke.py                 # one chip
    python chip_smoke.py --four-chips    # the sharded serve path, four chips

One chip, in one process:

(a) device check: JAX's first device must be a TPU (no CPU fallback);
(b) serve qwen2.5-3b at its published width (batch 4, prompt 128, 32 new
    tokens) through ``repro.launch.serve``, the prompt fed in chunks and
    the ids decoded one step each, and check the cached logits and greedy
    ids against a float32 full-sequence forward (``api.logits_fn``) at
    highest matmul precision;
(c) run each kernel of ``kernels/ops.py`` at real widths with
    planner-chosen blocks, check that it compiled to a Mosaic kernel, and
    compare it with ``kernels/ref.py``.

``--four-chips`` runs only the sharded path: qwen2.5-3b under the mesh
planner's top-ranked ``ShardingPlan`` through ``train/serve_step`` on a 1x4
host mesh, compared with the same weights on one device; then gemma-7b,
which no single 16 GB chip holds, under the same path with the check of (b).

Weights and prompts are random, made from ``--seed``.  A failed check exits
non-zero.  The last line of a passing run is one JSON object naming the
device.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
ARCH = "qwen2.5-3b"
BIG_ARCH = "gemma-7b"                 # ~8.5B params: four chips only
BATCH, PROMPT, TOKENS = 4, 128, 32

# Served logits (bfloat16 weights and activations) against the float32
# reference: bfloat16 keeps 8 significant bits, so each rounding is within
# 0.4% of its value; 36 residual layers of such errors measured 0.7% of
# max|logit| at 8 layers on the host.  The bound allows 3%.
LOGIT_RTOL = 0.03
# Kernels against kernels/ref.py, as a share of the output's scale: the
# bfloat16 kernels round float32 accumulators to bfloat16 (attention also
# its probabilities), a few bfloat16 ulps.
KERNEL_RTOL = 2e-2


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def device_check(n_chips: int):
    """Phase (a): the accelerator is a TPU, with enough chips."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found: JAX's first device is "
                 f"{devices[0].platform!r} ({devices[0].device_kind})")
    if len(devices) < n_chips:
        sys.exit(f"chip_smoke: {n_chips} TPU chips needed, "
                 f"{len(devices)} found")
    print(f"[smoke] device: {devices[0].device_kind} x{len(devices)}",
          flush=True)
    return devices


def reference_logits(api, params, seq, first_row: int, plan=None,
                     mesh=None):
    """Float32 forward over ``seq`` at highest matmul precision, rows
    ``first_row`` onward, on the weights' devices.  The weights stay in
    their stored dtype and are upcast per layer inside the scan."""
    import jax
    from repro.models import build_model
    from repro.parallel.sharding import use_plan
    cfg = dataclasses.replace(api.cfg, compute_dtype="float32")
    ref = build_model(cfg)

    def fwd(p, s):
        with (use_plan(plan, mesh) if plan is not None
              else contextlib.nullcontext()):
            logits = ref.logits_fn(p, {"tokens": s})
        return logits[:, first_row:, :cfg.vocab_size]

    with jax.default_matmul_precision("highest"):
        return jax.block_until_ready(jax.jit(fwd)(params, seq))


def compare_greedy(label: str, ids, logits, ref_rows) -> None:
    """Check served logits rows against reference rows computed on the same
    context, and the served greedy ids against the reference's choice.

    Logits: max |served - ref| <= LOGIT_RTOL * max |ref|.  Ids: equal
    wherever the reference's top-two gap exceeds twice that bound (below
    it, both ids are within the tolerance of the best), and everywhere the
    served id's reference logit is within twice the bound of the best."""
    import numpy as np
    served = np.asarray(logits, np.float32)
    ref = np.asarray(ref_rows, np.float32)
    ids = np.asarray(ids)
    n = min(served.shape[1], ref.shape[1])
    served, ref, ids = served[:, :n], ref[:, :n], ids[:, :n]
    tol = LOGIT_RTOL * float(np.abs(ref).max())
    err = float(np.abs(served - ref).max())
    top2 = np.sort(ref, axis=-1)[..., -2:]
    gap = top2[..., 1] - top2[..., 0]
    equal = ref.argmax(-1) == ids
    decisive = gap > 2 * tol
    picked = np.take_along_axis(ref, ids[..., None], axis=-1)[..., 0]
    regret = float((top2[..., 1] - picked).max())
    print(f"[smoke] {label}: {n} positions x{ids.shape[0]}: max|logit err| "
          f"{err:.5f} (tol {tol:.5f}); greedy ids equal "
          f"{int(equal.sum())}/{ids.size}, decisive {int(decisive.sum())} "
          f"all equal: {bool(equal[decisive].all())}; max regret "
          f"{regret:.5f}", flush=True)
    check(err <= tol, f"{label}: logits error {err} > {tol}")
    check(bool(equal[decisive].all()),
          f"{label}: greedy ids differ where the reference is decisive")
    check(regret <= 2 * tol, f"{label}: served id {regret} below the best")


def serve_phase(seed: int, reduced: bool = False) -> None:
    """Phase (b): the serving driver at full width (``reduced`` serves the
    small config, for a rehearsal on the host), then the reference."""
    import jax
    import jax.numpy as jnp
    from repro.launch import serve
    api, params, prompts, run = serve.serve_model(
        ARCH, reduced=reduced, batch=BATCH, prompt_len=PROMPT,
        tokens=TOKENS, seed=seed)
    check(bool(jnp.isfinite(run.logits).all()), "served logits not finite")
    check(run.generated.shape == (BATCH, TOKENS),
          f"generated shape {run.generated.shape}")
    seq = jnp.concatenate([prompts, run.generated[:, :-1]], axis=1)
    t0 = time.perf_counter()
    ref = reference_logits(api, params, seq, PROMPT - 1)
    print(f"[smoke] float32 reference forward (incl. compile): "
          f"{time.perf_counter() - t0:.2f}s", flush=True)
    compare_greedy(f"{api.cfg.name} cached vs float32 full-sequence",
                   run.generated, run.logits, ref)
    stats = jax.devices()[0].memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        print(f"[smoke] peak device memory: "
              f"{stats['peak_bytes_in_use'] / 1e9:.3f} GB", flush=True)


def kernel_cases():
    """(name, ops call, reference call, input shapes, input maker) at the
    widths of the configurations the repo serves."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops, ref

    def normal(key, shapes, dtype=jnp.bfloat16):
        keys = jax.random.split(key, len(shapes))
        return [jax.random.normal(k, s, jnp.float32).astype(dtype)
                for k, s in zip(keys, shapes)]

    def wkv_inputs(key, shapes):
        r, k, v, w, u = normal(key, shapes, jnp.float32)
        return [r, k, v, -jnp.exp(w * 0.5 - 1.0), u * 0.5]

    def attention_map(causal):
        # one head at a time: a dense (16, 8192, 8192) score tensor would
        # not fit beside its softmax
        def run(q, k, v):
            return jax.lax.map(lambda h: ref.attention_ref(
                h[0][None], h[1][None], h[2][None], causal=causal)[0],
                (q, k, v))
        return run

    mm = (ops.matmul, ref.gemm_ref)
    return [
        ("matmul qwen2.5-3b mlp 2048x11008x2048", *mm,
         [(2048, 2048), (2048, 11008)], normal),
        ("matmul qwen2.5-3b down 8192x2048x11008", *mm,
         [(8192, 11008), (11008, 2048)], normal),
        ("matmul qwen2.5-3b head 512x151936x2048", *mm,
         [(512, 2048), (2048, 151936)], normal),
        ("attention causal 32x4096x128",
         lambda q, k, v: ops.attention(q, k, v, causal=True),
         attention_map(True), [(32, 4096, 128)] * 3, normal),
        ("attention 16x8192x256",
         lambda q, k, v: ops.attention(q, k, v, causal=False),
         attention_map(False), [(16, 8192, 256)] * 3, normal),
        ("flash_decode 32x8192x128", ops.flash_decode, ref.decode_ref,
         [(32, 1, 128), (32, 8192, 128), (32, 8192, 128)], normal),
        ("flash_decode 32x32768x128", ops.flash_decode, ref.decode_ref,
         [(32, 1, 128), (32, 32768, 128), (32, 32768, 128)], normal),
        ("grouped_matmul 8x512x2048->1408", ops.grouped_matmul,
         ref.grouped_matmul_ref, [(8, 512, 2048), (8, 2048, 1408)], normal),
        ("wkv6 rwkv6-3b 160x1024x64", ops.wkv6, ref.wkv6_ref,
         [(160, 1024, 64)] * 4 + [(160, 64)], wkv_inputs),
    ]


def kernel_phase(seed: int) -> None:
    """Phase (c): each Pallas kernel on the chip against its oracle."""
    import jax
    import numpy as np
    from repro.core.lower_jax import planner_fallback_count
    from repro.kernels import ops
    check(ops.on_tpu(), "kernels would run in interpret mode: not on a TPU")
    key = jax.random.PRNGKey(seed)
    for i, (name, fn, ref_fn, shapes, make) in enumerate(kernel_cases()):
        args = make(jax.random.fold_in(key, i), shapes)
        t0 = time.perf_counter()
        compiled = jax.jit(fn).lower(*args).compile()
        compile_s = time.perf_counter() - t0
        check("tpu_custom_call" in compiled.as_text(),
              f"{name}: no Mosaic kernel in the compiled program")
        out = jax.block_until_ready(compiled(*args))
        t0 = time.perf_counter()
        out = jax.block_until_ready(compiled(*args))
        run_s = time.perf_counter() - t0
        with jax.default_matmul_precision("highest"):
            want = jax.jit(ref_fn)(*args)
        out = np.asarray(out, np.float32)
        want = np.asarray(want, np.float32)
        check(out.shape == want.shape, f"{name}: shape {out.shape} "
              f"!= {want.shape}")
        check(bool(np.isfinite(out).all()), f"{name}: non-finite output")
        scale = max(1.0, float(np.abs(want).max()))
        err = float(np.abs(out - want).max())
        print(f"[smoke] kernel {name}: compile {compile_s:.2f}s, "
              f"run {run_s * 1e3:.3f}ms, max|err| {err:.5f} "
              f"(tol {KERNEL_RTOL * scale:.5f})", flush=True)
        check(err <= KERNEL_RTOL * scale, f"{name}: error {err}")
    fallbacks = planner_fallback_count()
    check(fallbacks == 0, f"planner_fallbacks_total = {fallbacks}")
    print("[smoke] planner_fallbacks_total = 0", flush=True)


def weight_bytes(params, devices):
    """Bytes of the weights' shards that each device holds."""
    import jax
    held = {d: 0 for d in devices}
    for leaf in jax.tree.leaves(params):
        for shard in leaf.addressable_shards:
            held[shard.device] += shard.data.nbytes
    return [held[d] for d in devices]


def sharded_serve(arch: str, seed: int, mesh, reduced: bool = False):
    """Serve ``arch`` under the mesh planner's top-ranked plan through
    ``serve_step.jit_serve_step``, and check that the weights are spread
    over the mesh.  Returns (api, params, prompts, run, plan)."""
    import jax
    from repro.configs.base import ShapeConfig, TrainConfig
    from repro.launch import serve
    from repro.models import build_model
    from repro.parallel.planner_bridge import plan_mesh
    from repro.train import serve_step as SS
    cfg = serve.serving_config(arch, reduced=reduced)
    api = build_model(cfg)
    max_len = PROMPT + TOKENS + 1
    t0 = time.perf_counter()
    shape = ShapeConfig("serve", seq_len=PROMPT + TOKENS,
                        global_batch=BATCH, kind="decode")
    plan = plan_mesh(api, shape, TrainConfig())[0].plan
    cache_abs = jax.eval_shape(lambda: api.init_cache(cfg, BATCH, max_len))
    params = serve.init_params(api, seed, SS.param_shardings(api, plan, mesh))
    cache = jax.jit(lambda: api.init_cache(cfg, BATCH, max_len),
                    out_shardings=SS.cache_shardings(api, cache_abs, plan,
                                                     mesh))()
    prompts = serve.synthetic_prompts(cfg, BATCH, PROMPT, seed)
    decode, pick = serve.compile_greedy(
        SS.jit_serve_step(api, plan, mesh, cache_abs, (BATCH, 1)), params,
        prompts[:, :1], cache, cfg.vocab_size)
    print(f"[smoke] {cfg.name} plan {plan.name} on mesh "
          f"{dict(mesh.shape)}: set-up {time.perf_counter() - t0:.2f}s "
          f"({api.n_params():,} params)", flush=True)
    run = serve.greedy_generate(decode, pick, params, prompts, cache,
                                TOKENS)
    print(f"[smoke] {cfg.name} sharded: prefill {PROMPT} tok x{BATCH}: "
          f"{run.prefill_s:.3f}s; decode {TOKENS} tok x{BATCH}: "
          f"{run.decode_s:.3f}s", flush=True)
    devices = list(mesh.devices.flat)
    held = weight_bytes(params, devices)
    in_use = [(d.memory_stats() or {}).get("bytes_in_use", 0)
              for d in devices]
    print(f"[smoke] {cfg.name} per device (id: weights GB / in use GB): "
          + " ".join(f"{d.id}: {h / 1e9:.3f} / {u / 1e9:.3f}"
                     for d, h, u in zip(devices, held, in_use)), flush=True)
    check(max(held) < 0.6 * sum(held) and min(held) > 0,
          f"{cfg.name} weights are not spread over the mesh: {held}")
    return api, params, prompts, run, plan


def first_difference(a, b) -> int:
    """Number of leading generated positions on which two batches of ids
    agree in every sequence."""
    import numpy as np
    differs = (np.asarray(a) != np.asarray(b)).any(axis=0)
    return int(differs.argmax()) if differs.any() else differs.size


def four_chip_phase(seed: int, reduced: bool = False) -> None:
    """The sharded serve path: qwen2.5-3b against the same weights on one
    device, then gemma-7b against the float32 reference.  ``reduced``
    serves the small configs, for a rehearsal on host devices."""
    import jax
    import jax.numpy as jnp
    from repro.launch import serve
    from repro.launch.mesh import make_host_mesh
    mesh = make_host_mesh(1, 4)
    check(mesh.devices.size == 4, f"mesh {dict(mesh.shape)}")

    api, params, prompts, run, _ = sharded_serve(ARCH, seed, mesh, reduced)
    one = jax.devices()[0]
    params = jax.device_put(params, one)
    cache = jax.device_put(
        api.init_cache(api.cfg, BATCH, PROMPT + TOKENS + 1), one)
    decode, pick = serve.compile_greedy(
        jax.jit(api.decode_step, donate_argnums=(2,)), params,
        prompts[:, :1], cache, api.cfg.vocab_size)
    base = serve.greedy_generate(decode, pick, params, prompts, cache,
                                 TOKENS)
    # the two runs share their context up to and including the first
    # differing id, so their logits compare up to there
    same = first_difference(run.generated, base.generated)
    print(f"[smoke] {api.cfg.name} 4-chip vs 1-chip greedy ids: first "
          f"{same}/{TOKENS} positions equal", flush=True)
    rows = min(same + 1, TOKENS)
    compare_greedy(f"{api.cfg.name} 4-chip vs 1-chip",
                   run.generated[:, :rows], run.logits[:, :rows],
                   base.logits[:, :rows])
    del params, cache, decode, pick, run, base

    api, params, prompts, run, plan = sharded_serve(BIG_ARCH, seed, mesh,
                                                    reduced)
    check(bool(jnp.isfinite(run.logits).all()), "served logits not finite")
    seq = jnp.concatenate([prompts, run.generated[:, :-1]], axis=1)
    ref = reference_logits(api, params, seq, PROMPT - 1, plan, mesh)
    compare_greedy(f"{api.cfg.name} cached vs float32 full-sequence",
                   run.generated, run.logits, ref)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded serve path on four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = device_check(4 if args.four_chips else 1)
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        sys.exit(f"chip_smoke: no repro package under {src}")
    sys.path.insert(0, str(src))
    # plan inline: this process holds the chip, and planning needs no
    # worker processes at these sizes
    os.environ.setdefault("REPRO_PLANNER_WORKERS", "1")
    from repro.launch import use_compile_cache
    print(f"[smoke] compile cache: {use_compile_cache()}", flush=True)

    t0 = time.perf_counter()
    try:
        if args.four_chips:
            four_chip_phase(args.seed)
        else:
            serve_phase(args.seed)
            kernel_phase(args.seed)
    except SmokeFailure as e:
        print(f"[smoke] FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(f"[smoke] all phases passed in {time.perf_counter() - t0:.1f}s",
          flush=True)
    import jax
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
