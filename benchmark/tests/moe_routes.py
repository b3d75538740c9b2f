#!/usr/bin/env python3
"""Builder's look at the routing of a `moe_lm` cell on the chip, one process
(the node owns the chip, so JAX is imported here):

    chiprun -- python3 benchmark/tests/moe_routes.py <cell> <seed> <steps> [embedding_std=<x>] [zero_expert=<e>]

Prints, step by step, what the step's `moe.*` counters counted (the share of the picks
that fell on held experts, the fullest held expert's tokens over the mean
one's) and the step's time; then, for the first batch, the share of (token,
pick) pairs on which the program (bfloat16 activations) and the plain
float32 reference choose another expert, layer by layer: near-ties at the
k-th pick flip under rounding, which is part of the noise the cell's limits
sit above.  With `zero_expert=<e>` it reads the planted fault (one held
expert's output zeroed in the program) against the reference instead.
"""
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import control  # noqa: E402
import toy  # noqa: E402,F401
import harness  # noqa: E402
import traffic  # noqa: E402
import weights  # noqa: E402


def main(argv):
    from tensorflowonspark_tpu import trace, util

    util.enable_compile_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np

    opts = dict(a.split("=") for a in argv if "=" in a)
    cell, seed, steps = [a for a in argv if "=" not in a]
    seed, steps = int(seed), int(steps)
    spec = harness.load_spec(cell, seed, 1, 0)
    cfg, tr = spec.config, spec.traffic
    if "embedding_std" in opts:
        cfg["init"]["embedding_std"] = float(opts["embedding_std"])
    devices = jax.devices()[:1]
    print(json.dumps({"device": devices[0].device_kind, "opts": opts}))
    if "zero_expert" in opts:
        cfg["program"]["zero_expert"] = int(opts["zero_expert"])
        got = control.readings(spec, seed, devices, ("program",), {})
        print(json.dumps({"zero_expert": opts["zero_expert"], "seed": seed,
                          "correct": got["program"][0],
                          **got["program"][2]}), flush=True)
        return
    family = harness.load_module("families", cfg["family"])
    built = harness.build_program(family, cfg, tr, None, devices)
    state = built.fresh_state(seed)
    compiled = built.compile(state)
    batches = traffic.first_batches(tr, cfg, seed, steps)
    before = trace.counters().snapshot()
    for i, batch in enumerate(batches):
        t0 = time.perf_counter()
        state, metrics = compiled(state, jax.device_put(batch), built.rng)
        loss = float(metrics["loss"])
        now = trace.report()["counters"]        # waits for the step's
        d = {k: now.get(k, 0) - before.get(k, 0) for k in now
             if k.startswith("moe.")}
        before = now
        pairs = d["moe.pairs.local"] + d["moe.pairs.absent"]
        print(json.dumps({
            "step": i, "loss": loss, "ms": 1e3 * (time.perf_counter() - t0),
            "local_pct": 100.0 * d["moe.pairs.local"] / pairs,
            "fullest_over_mean": d["moe.load.max"] / d["moe.load.mean"]}),
            flush=True)
    del state, compiled

    # the first batch's picks: program against reference, same weights
    from tensorflowonspark_tpu.models.transformer import (
        Transformer, TransformerConfig)

    flat = weights.make(seed, built.shapes)
    model = Transformer(TransformerConfig(**cfg["program"]["model"]))
    rows = jnp.asarray(batches[0][:, :-1])
    _, sown = jax.jit(lambda p, t: model.apply(
        {"params": p}, t, return_hidden=True, mutable=["intermediates"]))(
            weights.nest(flat), rows)
    z = family._sizes(cfg)
    mm = family.lm._matmul("f32")
    x = [flat["token_embed/embedding"][rows[r:r + 1]]
         for r in range(rows.shape[0])]
    for i, kind in enumerate(z["kinds"]):
        pre = f"layer_{i}/"
        p = {k[len(pre):]: v for k, v in flat.items() if k.startswith(pre)}
        cos, sin = family.rope_tables(cfg, kind, rows.shape[1])
        f = jax.jit(lambda p_, x_, c, s_, w=(
            z["window"] if kind == family.SLIDING else None):
            family._block(p_, x_, z, cfg["rms_norm_eps"], c, s_, w, mm,
                          picks=True))
        out = [f(p, x_r, cos, sin) for x_r in x]
        x = [o[0] for o in out]
        ref = np.concatenate([np.asarray(o[1]) for o in out])
        got = np.asarray(sown["intermediates"][f"layer_{i}"]["moe"][
            "moe_picks"][0])
        same = (got[:, :, None] == ref[:, None, :]).any(-1)
        print(json.dumps({"layer": i, "kind": kind, "pairs": int(same.size),
                          "routed_differently_pct":
                          100.0 * float(1.0 - same.mean())}), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
