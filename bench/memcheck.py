"""Compile each configuration's served decode step for a described TPU
v5e chip, without the chip, and print the compiler's memory analysis.

    JAX_PLATFORMS=cpu python3 bench/memcheck.py [config ...]

It compiles ``Model.decode_step`` at the configuration's slots x positions
with the cache donated, as ``ServeEngine`` runs it. Nothing runs, so it
gives sizes and no times.
"""
from __future__ import annotations

import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(names: list[str]) -> int:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench.harness import spec
    from repro.models.transformer import Model

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    names = names or sorted(p.stem for p in
                            (spec.BENCH_DIR / "configs").glob("*.json"))
    for name in names:
        config = spec.load_json(spec.BENCH_DIR / "configs" / f"{name}.json")
        model = Model(spec.model_config(config))
        slots, max_len = config["serve"]["slots"], config["serve"]["max_len"]
        place = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,  # noqa
                                               sharding=one)
        params = jax.tree.map(place, model.abstract_params())
        cache = jax.tree.map(place, model.init_cache(slots, max_len,
                                                     abstract=True))
        tokens = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one)
        mem = jax.jit(model.decode_step, donate_argnums=(1,)).lower(
            params, cache, tokens).compile().memory_analysis()
        total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                 - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
        print(f"{name} decode_step {slots} slots x {max_len} positions "
              f"(compile for a described v5e): arguments "
              f"{mem.argument_size_in_bytes}, outputs "
              f"{mem.output_size_in_bytes}, aliased "
              f"{mem.alias_size_in_bytes}, temporaries "
              f"{mem.temp_size_in_bytes}, total {total} bytes", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
