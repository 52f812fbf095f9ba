"""Record ``data/cpu_trace.xplane.pb``, the small CPU trace that
``test_trace.py`` reduces.

    JAX_PLATFORMS=cpu python3 bench/tests/record_trace.py

Inside the window span: a hand-off that sleeps 20 ms, then five executions
of a jitted ``serve_decode_step``, each followed by a 5 ms host pause.
"""
import glob
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
STEPS = 5


def main() -> None:
    import jax
    import jax.numpy as jnp
    sys.path[:0] = [str(HERE.parents[1])]
    from bench import harness

    def serve_decode_step(x, w):
        return jnp.tanh(x @ w) @ w

    f = jax.jit(serve_decode_step)
    x, w = jnp.ones((64, 256)), jnp.full((256, 256), 0.01)
    f(x, w).block_until_ready()
    tmp = tempfile.mkdtemp()
    try:
        jax.profiler.start_trace(tmp, profiler_options=harness._options())
        with jax.profiler.TraceAnnotation(harness.WINDOW_SPAN):
            with jax.profiler.TraceAnnotation("bench.handoff"):
                time.sleep(0.02)
            for _ in range(STEPS):
                f(x, w).block_until_ready()
                with jax.profiler.TraceAnnotation("host.pause"):
                    time.sleep(0.005)
        jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                            recursive=True)
        # the trace names this script by its absolute path: blank the
        # checkout's prefix, keeping its length so the protobuf stays valid
        prefix = str(HERE.parents[1]).encode() + b"/"
        data = Path(path).read_bytes().replace(
            prefix, b"." * (len(prefix) - 1) + b"/")
        (HERE / "data" / "cpu_trace.xplane.pb").write_bytes(data)
    finally:
        shutil.rmtree(tmp)


if __name__ == "__main__":
    main()
