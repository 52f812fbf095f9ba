"""Integration tests: end-to-end training with checkpoint/restart, sharded
execution on a small host mesh, decode consistency vs teacher forcing."""
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import ARCHS
from repro.configs.base import ShapeConfig, TrainConfig
from repro.ckpt import CheckpointManager
from repro.data import DataConfig, SyntheticLM
from repro.models import build_model
from repro.parallel.sharding import megatron_tp_plan
from repro.train import train_step as TS

CFG = ARCHS["qwen2.5-3b"].reduced()
TCFG = TrainConfig(total_steps=50, warmup_steps=2, learning_rate=1e-3)


def _stream(cfg, batch=4, seq=32):
    d = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size, seed=3), cfg)
    return lambda step: jax.tree.map(jnp.asarray, d.batch_at(step, batch, seq))


def test_train_loss_decreases():
    api = build_model(CFG)
    state = TS.init_state(api, TCFG, jax.random.PRNGKey(0))
    step_fn = jax.jit(TS.make_train_step(api, TCFG))
    data = _stream(CFG)
    losses = []
    for i in range(12):
        state, m = step_fn(state, data(i))
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.3, losses


def test_checkpoint_restart_bitwise_resume(tmp_path):
    """Training interrupted at step 6 and resumed from the step-4 checkpoint
    replays to the same final loss as an uninterrupted run (deterministic
    data pipeline => recovery is exact)."""
    api = build_model(CFG)
    data = _stream(CFG)
    step_fn = jax.jit(TS.make_train_step(api, TCFG))

    def run(n, mgr=None, state=None, start=0):
        if state is None:
            state = TS.init_state(api, TCFG, jax.random.PRNGKey(0))
        loss = None
        for i in range(start, n):
            state, m = step_fn(state, data(i))
            if mgr and mgr.should_save(i + 1):
                mgr.save(state, i + 1, block=True)
            loss = float(m["loss"])
        return state, loss

    # uninterrupted reference
    _, ref_loss = run(8)
    # interrupted: save every 4, crash after 6, restore, resume
    mgr = CheckpointManager(tmp_path, save_every=4, keep=2, async_save=False)
    state, _ = run(6, mgr=mgr)
    del state                                          # "crash"
    template = TS.abstract_state(api, TCFG)
    restored, step = mgr.restore_latest(target_tree=template)
    assert step == 4
    _, resumed_loss = run(8, state=restored, start=step)
    np.testing.assert_allclose(resumed_loss, ref_loss, rtol=1e-5)


@pytest.mark.slow
@pytest.mark.skipif(len(jax.devices()) < 1, reason="needs a device")
def test_sharded_train_step_matches_unsharded():
    """The plan-sharded jitted step computes the same loss as the local step
    (on a 1x1 mesh the constraints are no-ops but the full path runs)."""
    from repro.launch.mesh import make_host_mesh
    api = build_model(CFG)
    mesh = make_host_mesh(1, 1)
    plan = megatron_tp_plan()
    data = _stream(CFG)
    state = TS.init_state(api, TCFG, jax.random.PRNGKey(0))
    batch = data(0)
    specs = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), batch)
    with mesh:
        jitted = TS.jit_train_step(api, TCFG, plan, mesh, specs)
        state2, m2 = jitted(state, batch)
    plain = TS.make_train_step(api, TCFG)
    state_ref = TS.init_state(api, TCFG, jax.random.PRNGKey(0))
    _, m1 = plain(state_ref, batch)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                               rtol=1e-4)


def test_decode_matches_teacher_forcing():
    """Greedy decode logits at position t equal full-forward logits at t."""
    cfg = ARCHS["qwen2.5-3b"].reduced()
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(1))
    toks = jax.random.randint(jax.random.PRNGKey(2), (1, 8), 1,
                              cfg.vocab_size)
    full = api.logits_fn(params, {"tokens": toks, "labels": toks})
    cache = api.init_cache(cfg, 1, 16)
    outs = []
    for t in range(8):
        lg, cache = api.decode_step(params, toks[:, t:t + 1], cache)
        outs.append(lg[:, 0])
    dec = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec, np.float32),
                               np.asarray(full, np.float32),
                               rtol=0.05, atol=0.05)


@pytest.mark.slow
def test_rwkv_decode_matches_teacher_forcing():
    """The recurrent decode path agrees with the chunked training path."""
    cfg = ARCHS["rwkv6-3b"].reduced()
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(1))
    toks = jax.random.randint(jax.random.PRNGKey(2), (1, 8), 1,
                              cfg.vocab_size)
    full = api.logits_fn(params, {"tokens": toks, "labels": toks})
    cache = api.init_cache(cfg, 1, 16)
    outs = []
    for t in range(8):
        lg, cache = api.decode_step(params, toks[:, t:t + 1], cache)
        outs.append(lg[:, 0])
    dec = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec, np.float32),
                               np.asarray(full, np.float32),
                               rtol=0.05, atol=0.05)


def test_serve_model_keeps_weights_in_compute_dtype():
    """The serving driver stores weights in the compute dtype and each
    greedy id is the argmax of the logits row it was picked from."""
    from repro.launch import serve
    api, params, prompts, run = serve.serve_model(
        "qwen2.5-3b", reduced=True, batch=2, prompt_len=4, tokens=3)
    dtypes = {leaf.dtype for leaf in jax.tree.leaves(params)}
    assert dtypes == {jnp.dtype(api.cfg.compute_dtype)}
    assert prompts.shape == (2, 4)
    assert run.generated.shape == (2, 3)
    assert run.logits.shape == (2, 3, api.cfg.vocab_size)
    np.testing.assert_array_equal(np.asarray(run.generated),
                                  np.asarray(run.logits.argmax(-1)))


_CACHE_PROBE = """
import sys
sys.path.insert(0, sys.argv[1])
from repro.launch import use_compile_cache
print(use_compile_cache())
import jax, jax.numpy as jnp
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.block_until_ready(jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)))
"""


def test_compile_cache_location(tmp_path, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR, when set, is where compiles are cached;
    unset, the entry points use <repo>/.jax_cache, which git ignores."""
    from repro.launch import REPO_ROOT, use_compile_cache
    env_dir = tmp_path / "jax_cache"
    out = subprocess.run(
        [sys.executable, "-c", _CACHE_PROBE, str(REPO_ROOT / "src")],
        env={**os.environ,
             "JAX_COMPILATION_CACHE_DIR": str(env_dir),
             "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=120, check=True)
    assert out.stdout.split("\n")[0] == str(env_dir)
    assert any(env_dir.iterdir())

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    was = jax.config.jax_compilation_cache_dir
    try:
        assert use_compile_cache() == str(REPO_ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(
            REPO_ROOT / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
    ignored = (REPO_ROOT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
