#!/usr/bin/env python3
"""Compile each cell's real step for a DESCRIBED `v5e:2x2` (no chip) and
print `memory_analysis()`: what the chip's compiler would refuse, it refuses
here, at no chip time.  A compile is not a chip run.

    JAX_PLATFORMS=cpu python3 benchmark/tests/compile_cells.py [cell ...]
"""
import contextlib
import json
import math
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [os.path.dirname(BENCH), BENCH]


def compile_cell(name, topo):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

    import harness
    import traffic
    import weights
    import tensorflowonspark_tpu.ops as ops
    from tensorflowonspark_tpu.parallel import mesh as mesh_mod
    from tensorflowonspark_tpu.parallel import train as train_mod

    ops.default_interpret = lambda: False     # compile the kernels for real
    spec = harness.load_spec(name, 0, 1, 0)
    cfg = spec.config
    if cfg["program"]["model"].get("attention_impl") == "auto":
        cfg["program"]["model"]["attention_impl"] = "flash"   # backend is cpu
    family = harness.load_module("families", cfg["family"])
    devs = topo.devices[:spec.chips]
    mesh = None
    if spec.cell.get("mesh"):
        mesh = mesh_mod.build_mesh(mesh_mod.MeshSpec(**spec.cell["mesh"]),
                                   devices=devs)
        place = NamedSharding(mesh, PartitionSpec())
        batch_sh = mesh_mod.batch_sharding(mesh)
    else:
        place = batch_sh = SingleDeviceSharding(devs[0])
    shapes = family.param_shapes(cfg)
    params = weights.nest({p: jax.ShapeDtypeStruct(s, jnp.float32,
                                                   sharding=place)
                           for p, (s, _) in shapes.items()})
    loss_fn, opt = family.build(cfg)
    key_dtype = jax.random.key(0).dtype      # eager: before the described mesh
    with jax.set_mesh(mesh) if mesh is not None else contextlib.nullcontext():
        state = jax.eval_shape(
            lambda p: train_mod.create_train_state(p, opt), params)
        state = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=place),
            state)
        b = spec.traffic["batch"]
        batch = tuple(jax.ShapeDtypeStruct((b,) + shape, dtype,
                                           sharding=batch_sh)
                      for dtype, shape, _, _ in
                      traffic.fields(spec.traffic, cfg))
        batch = batch[0] if len(batch) == 1 else batch
        rng = jax.ShapeDtypeStruct((), key_dtype, sharding=place)
        step = train_mod.make_train_step(loss_fn, opt, mesh=mesh, donate=True)
        t0 = time.perf_counter()
        compiled = step.lower(state, batch, rng).compile()
    text = compiled.as_text()
    mem = compiled.memory_analysis()
    return {"cell": name, "compile_s": time.perf_counter() - t0,
            "n_params": sum(math.prod(s) for s, _ in shapes.values()),
            "argument_bytes": mem.argument_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "tpu_custom_calls": text.count("tpu_custom_call"),
            "all_reduces": text.count("all-reduce")}


def main(argv):
    from jax.experimental import topologies

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        cells = argv or [w["name"] for w in json.load(f)["workloads"]]
    for name in cells:
        print(json.dumps(compile_cell(name, topo)), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
