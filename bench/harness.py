"""One cell of the benchmark: set-up, the measured window, and what the
window produced.

Set-up makes the calls that ``repro.launch.serve.serve_model`` makes, in its
order (serving config, model, mesh plan, weights from the seed, cache,
compiled step and pick), then serves one warm-up batch; the weights are the
benchmark's own (``bench/weights.py``), not ``serve.init_params``'s.  The
window hands fixed batches to ``repro.launch.serve.greedy_generate`` in a
closed loop, one per prompt length of the mix's schedule in turn, until
``seconds`` have passed and the schedule has run whole a number of times:
so every window serves the mix's distribution of lengths.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

import numpy as np

from bench import loadgen, reference, weights

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
STEP_NAME = "serve_decode_step"          # the decode step's stable name
TRACED_BATCH = 1                         # index of the batch a trace covers
WINDOW_SPAN = "bench.traced_batch"

# configuration file key -> ModelConfig field, for every family; a family
# adds its own (``MODEL_KEYS`` of its ``bench/reference/<family>.py``)
COMMON_KEYS = {"vocab_size": "vocab_size", "hidden_size": "d_model",
               "num_hidden_layers": "n_layers",
               "num_attention_heads": "n_heads",
               "num_key_value_heads": "n_kv_heads",
               "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
               "tie_word_embeddings": "tie_embeddings",
               "hidden_act": "mlp_activation"}


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    model: Dict[str, Any]         # bench/configs/<config>.json
    traffic: Dict[str, Any]       # bench/traffic/<traffic>.json


def load_json(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    spec = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"known: {sorted(cells)}")
    w = cells[workload]
    return Cell(workload, w["chips"],
                load_json(BENCH / "configs" / f"{w['config']}.json"),
                load_json(BENCH / "traffic" / f"{w['traffic']}.json"))


def model_keys(model: Dict[str, Any]) -> Dict[str, str]:
    """Configuration file key -> ModelConfig field, for ``model``'s
    family."""
    return {**COMMON_KEYS, **reference.family(model).MODEL_KEYS}


def model_config(model: Dict[str, Any]):
    """The program's serving config for this configuration file: the
    architecture's own, with every size the file states put over it."""
    from repro.launch import serve
    cfg = serve.serving_config(model["arch"])
    return dataclasses.replace(cfg, **{field: model[k] for k, field
                                       in model_keys(model).items()})


@dataclasses.dataclass
class Server:
    """What set-up leaves for the window."""
    cfg: Any
    api: Any
    params: Any
    decode: Any
    pick: Any
    phases: Dict[str, float]      # seconds of each step of set-up

    @property
    def plan_s(self) -> float:
        return self.phases["plan"]


def setup(cell: Cell, seed: int, cfg=None) -> Server:
    """Set-up of one run; ``cfg`` replaces the file's config (tests)."""
    import jax
    from repro.configs.base import ShapeConfig, TrainConfig
    from repro.launch import serve
    from repro.models import build_model
    from repro.planservice import PlanService

    t = cell.traffic
    B, slab, N = t["batch"], t["slab"], t["output_tokens"]
    phases, t0 = {}, time.perf_counter()

    def phase(name):
        nonlocal t0
        now = time.perf_counter()
        phases[name], t0 = now - t0, now

    cfg = cfg or model_config(cell.model)
    api = build_model(cfg)
    phase("model")
    PlanService().resolve_mesh(api, ShapeConfig("serve", seq_len=slab,
                                                global_batch=B,
                                                kind="decode"),
                               TrainConfig())
    phase("plan")
    params = weights.for_program(weights.make(cell.model, seed),
                                 api.abstract_params())
    phase("weights")
    cache = api.init_cache(cfg, B, slab)

    def serve_decode_step(params, tokens, cache):
        return api.decode_step(params, tokens, cache)

    # the loop's shapes depend on the batch and the output length alone:
    # a one-token prompt warms them all
    warm = loadgen.batch_prompts(seed, -1, B, 1, cfg.vocab_size)
    decode, pick = serve.compile_greedy(
        jax.jit(serve_decode_step, donate_argnums=(2,)), params,
        warm[:, :1], cache, cfg.vocab_size)
    phase("compile")
    run = serve.greedy_generate(decode, pick, params, warm, cache, N)
    # nothing of set-up runs on into the window: wait for the warm-up's
    # last outputs, then free them and set-up's garbage
    jax.block_until_ready((run.generated, run.logits))
    del run, cache
    gc.collect()
    phase("warm_up")
    return Server(cfg, api, params, decode, pick, phases)


@dataclasses.dataclass
class Batch:
    index: int
    prompts: np.ndarray           # (B, P) ids
    generated: np.ndarray         # (B, N) ids
    prefill_s: float
    decode_s: float
    start: float                  # host clock at hand-off
    end: float                    # host clock when its ids were on the host


@dataclasses.dataclass
class Window:
    batches: List[Batch]
    start: float
    end: float

    @property
    def seconds(self) -> float:
        return self.end - self.start


def serve_window(server: Server, cell: Cell, seed: int, seconds: float,
                 trace_dir: Optional[str] = None) -> Window:
    """Hand batches to ``greedy_generate`` until ``seconds`` have passed
    and the schedule of prompt lengths has run whole.  With ``trace_dir``,
    batch ``TRACED_BATCH`` runs under the profiler."""
    import jax
    from repro.launch import serve

    t = cell.traffic
    B, slab, N = t["batch"], t["slab"], t["output_tokens"]
    if loadgen.longest_request(t) > slab:
        raise ValueError(f"{t['name']}: the longest request does not fit "
                         f"a slab of {slab}")
    lengths = loadgen.prompt_lengths(t["prompt_len"])
    cfg, batches = server.cfg, []
    start = time.perf_counter()
    while (not batches or batches[-1].end - start < seconds
           or len(batches) % len(lengths)):
        b = len(batches)
        prompts = loadgen.batch_prompts(seed, b, B, lengths[b % len(lengths)],
                                        cfg.vocab_size)
        traced = trace_dir is not None and b == TRACED_BATCH
        if traced:
            jax.profiler.start_trace(trace_dir, profiler_options=_options())
        with jax.profiler.TraceAnnotation(WINDOW_SPAN if traced
                                          else "bench.batch"):
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.handoff"):
                cache = jax.block_until_ready(
                    server.api.init_cache(cfg, B, slab))
            with jax.profiler.TraceAnnotation("bench.greedy_generate"):
                run = serve.greedy_generate(server.decode, server.pick,
                                            server.params, prompts, cache, N)
                generated = np.asarray(run.generated)
            t1 = time.perf_counter()
        if traced:
            jax.profiler.stop_trace()
        batches.append(Batch(b, prompts, generated, run.prefill_s,
                             run.decode_s, t0, t1))
        del run, cache
    return Window(batches, batches[0].start, batches[-1].end)


def _options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def memory_peak_bytes() -> int:
    import jax
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.local_devices())
