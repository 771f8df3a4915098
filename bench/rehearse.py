"""Compile each cell's programs for a described TPU v5e, without a chip.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py [cell ...]

For every cell (all of ``BENCHMARK.json`` by default) it lowers the
batcher's fused prefill at each bucket the cell's traffic reaches, and its
fused decode step, with the cell's slots and ``max_len``, against one chip
of a described ``v5e:2x2``, and prints ``memory_analysis()`` per program:
the bytes of arguments, outputs, temporaries and generated code.  Nothing
runs and nothing is allocated at full size, so this finds a program the
chip's compiler refuses, or one that cannot fit, at no chip time.  It is
run by hand, not on the chip.
"""
import json
import os
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT), str(ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def rehearse(cell_name: str, device) -> list:
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from bench import harness
    from repro.models import transformer
    from repro.serving.engine import ContinuousBatcher
    cell = harness.load_cell(ROOT, cell_name)
    arch = harness.program_config(cell.config_name, cell.config)
    n, max_len = int(cell.spec["n_slots"]), int(cell.spec["max_len"])
    shard = SingleDeviceSharding(device)

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=shard), tree)

    params = on_chip(jax.eval_shape(
        lambda k: transformer.init_params(arch, k), jax.random.PRNGKey(0)))
    cache = jax.eval_shape(lambda: transformer.init_cache(
        arch, n, max_len, jnp.dtype(arch.dtype)))
    cache["pos"] = jax.ShapeDtypeStruct((n,), jnp.int32)
    cache = on_chip(cache)
    b = ContinuousBatcher.__new__(ContinuousBatcher)
    b.cfg, b.n_slots, b.max_len = arch, n, max_len
    b._sampler = lambda lg: jnp.argmax(lg, axis=-1)
    b._build_jits()
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=shard)
    mask = jax.ShapeDtypeStruct((n,), jnp.bool_, sharding=shard)
    rows = []
    for bucket in harness.buckets_for(cell.mix, max_len):
        c = b._prefill_fused.lower(params, i32(n, bucket), i32(n), i32(n),
                                   mask, cache).compile()
        rows.append((f"prefill {n}x{bucket}", c.memory_analysis()))
    c = b._decode_fused.lower(params, i32(n), mask, cache).compile()
    rows.append((f"decode {n}", c.memory_analysis()))
    return rows


def main(argv):
    import jax
    from jax.experimental import topologies
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    names = argv or [w["name"] for w in json.load(
        open(ROOT / "BENCHMARK.json"))["workloads"]]
    gib = 2 ** 30
    for name in names:
        for prog, m in rehearse(name, topo.devices[0]):
            total = (m.argument_size_in_bytes + m.output_size_in_bytes
                     - m.alias_size_in_bytes + m.temp_size_in_bytes
                     + m.generated_code_size_in_bytes)
            print(f"{name} | {prog} | args {m.argument_size_in_bytes / gib:.3f}"
                  f" GiB | out {m.output_size_in_bytes / gib:.3f} | alias "
                  f"{m.alias_size_in_bytes / gib:.3f} | temp "
                  f"{m.temp_size_in_bytes / gib:.3f} | total "
                  f"{total / gib:.3f} GiB", flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
