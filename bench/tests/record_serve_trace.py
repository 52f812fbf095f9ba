"""Record ``data/cpu_serve_trace.xplane.pb`` and ``data/cpu_serve_scopes.json``,
the small CPU trace of the serving loop that ``test_scopes.py`` reduces.

    JAX_PLATFORMS=cpu python3 bench/tests/record_serve_trace.py

Inside the window span: ``serve.greedy_generate`` of the reduced
qwen2.5-3b cell (2 layers, batch 2, slab 16), a prompt of ``P`` ids and
``N`` generated ids, under a profiler capture, so the loop's ``serve.*``
spans are in it.  The JSON holds ``P``, ``N`` and the scope map of the
compiled step (``repro.obs.scopes``).
"""
import glob
import json
import os
import shutil
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent
P, N, B, SLAB = 3, 4, 2, 16


def main() -> None:
    sys.path[:0] = [str(HERE), str(HERE.parents[1]),
                    str(HERE.parents[1] / "src")]
    import jax
    import numpy as np
    from bench import harness
    from conftest import small_cell
    from repro.launch import serve
    from repro.models import build_model
    from repro.obs.scopes import scope_map

    _, cfg = small_cell("qwen2.5-3b.chat-b16-ctx1k")
    api = build_model(cfg)
    params = serve.init_params(api, 0)

    def serve_decode_step(params, tokens, cache):
        return api.decode_step(params, tokens, cache)

    prompts = np.arange(B * P, dtype=np.int32).reshape(B, P) % cfg.vocab_size
    decode, pick = serve.compile_greedy(
        jax.jit(serve_decode_step, donate_argnums=(2,)), params,
        prompts[:, :1], api.init_cache(cfg, B, SLAB), cfg.vocab_size)
    jax.block_until_ready(serve.greedy_generate(
        decode, pick, params, prompts, api.init_cache(cfg, B, SLAB),
        N).generated)                       # the collect ops compile here
    cache = jax.block_until_ready(api.init_cache(cfg, B, SLAB))
    tmp = tempfile.mkdtemp()
    try:
        jax.profiler.start_trace(tmp, profiler_options=harness._options())
        with jax.profiler.TraceAnnotation(harness.WINDOW_SPAN):
            run = serve.greedy_generate(decode, pick, params, prompts,
                                        cache, N)
            np.asarray(run.generated)
        jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                            recursive=True)
        # the trace names this script by its absolute path: blank the
        # checkout's prefix, keeping its length so the protobuf stays valid
        prefix = str(HERE.parents[1]).encode() + b"/"
        data = Path(path).read_bytes().replace(
            prefix, b"." * (len(prefix) - 1) + b"/")
        (HERE / "data" / "cpu_serve_trace.xplane.pb").write_bytes(data)
    finally:
        shutil.rmtree(tmp)
    with open(HERE / "data" / "cpu_serve_scopes.json", "w") as f:
        json.dump({"P": P, "N": N,
                   "scopes": scope_map(decode.as_text())}, f,
                  indent=0, sort_keys=True)
        f.write("\n")


if __name__ == "__main__":
    main()
