"""Compile a cell's decode step for a described TPU v5e, with no chip, and
print what the compiler says of its memory.

    JAX_PLATFORMS=cpu python3 bench/compile_check.py --workload <name>

The weights, cache and ids are shapes on one described chip of a
``v5e:2x2`` topology; nothing runs.  A step that does not fit the chip's
memory is refused here as it would be on the chip.
"""
import argparse
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:] = [str(ROOT), str(ROOT / "src")] + [
    p for p in sys.path if Path(p or ".").resolve() != ROOT / "bench"]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from bench import harness
    from repro.models import build_model

    jax.config.update("jax_enable_compilation_cache", False)
    cell = harness.load_cell(args.workload)
    t = cell.traffic
    cfg = harness.model_config(cell.model)
    api = build_model(cfg)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
            tree)

    params = on_chip(api.abstract_params())
    cache = on_chip(jax.eval_shape(
        lambda: api.init_cache(cfg, t["batch"], t["slab"])))
    tokens = jax.ShapeDtypeStruct((t["batch"], 1), jnp.int32, sharding=one)

    def serve_decode_step(params, tokens, cache):
        return api.decode_step(params, tokens, cache)

    compiled = jax.jit(serve_decode_step, donate_argnums=(2,)).lower(
        params, tokens, cache).compile()
    m = compiled.memory_analysis()
    gb = lambda n: f"{n / 1e9:.3f} GB"
    print(f"{cell.name}: arguments {gb(m.argument_size_in_bytes)}, "
          f"outputs {gb(m.output_size_in_bytes)}, "
          f"aliased {gb(m.alias_size_in_bytes)}, "
          f"temporaries {gb(m.temp_size_in_bytes)}, "
          f"generated code {gb(m.generated_code_size_in_bytes)}")


if __name__ == "__main__":
    main()
