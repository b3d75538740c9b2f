#!/usr/bin/env python3
"""The control and the planted faults, read against the plain reference.

For each seed: the reference in float32 (what the program is compared with),
then in its place (a) the CONTROL: the same reference with every matmul or
convolution operand rounded to the four significant bits of fp8 (e4m3), a
precision below the bfloat16 the configurations state (int8 with a scale a
row keeps seven bits at the row's largest entry, about what bfloat16 keeps
everywhere: it would separate nothing), and (b) the reference with rows left
out of every batch (half of them: `half_batch`; all but one chip's share:
`no_exchange`).  Each is compared with the float32 reference by the
harness's own `compare`, so the readings are the numbers a run prints.  A
state left unchanged reads 1 by construction and needs no run.

On the chip, at the cell's own size (the node owns the chip, so this is one
process and JAX is imported here):

    chiprun -- python3 benchmark/tests/control.py <cell> <seed> [<seed> ...] [only=program,control,...] [first=<n>] [dump=<path prefix>]

(`first=<n>`: the control and the faults on the first n seeds only, the
program alone on the rest: the lower reading wants a dozen seeds, the upper
three.)

`tests/test_control.py` runs the same function at toy size on the CPU.
"""
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import toy  # noqa: E402
import harness  # noqa: E402
import traffic  # noqa: E402
import weights  # noqa: E402


def program(spec, seed, devices, built):
    """What a run keeps of the program's first steps, read without the
    cluster: the step object `harness.build_program` gives (compiled once a
    process, kept in `built`), driven by `harness.first_steps` over the
    batches the seed makes.  For the lower readings of many seeds."""
    import jax

    cfg, tr = spec.config, spec.traffic
    if "program" not in built:
        family = harness.load_module("families", cfg["family"])
        built["program"] = harness.build_program(
            family, cfg, tr, spec.cell.get("mesh"), devices)
    b = built["program"]
    state = b.fresh_state(seed)
    if "compiled" not in built:
        built["compiled"] = b.compile(state)
    batches = iter(traffic.first_batches(tr, cfg, seed, tr["check_steps"]))
    state, prog = harness.first_steps(
        built["compiled"], state,
        lambda: jax.device_put(next(batches), b.sharding), b.rng, cfg, tr)
    prog["update_norms"] = harness._leaf_norms(
        state.params, weights.nest(weights.make(seed, b.shapes, b.repl)))
    prog["feed_rows_wrong"] = 0
    return prog


def readings(spec, seed, devices=None, which=("control", "half_batch",
                                              "no_exchange"), built=None,
             dump=None):
    """`{name: (correct, numbers)}` for the control and each fault (and for
    `program`, the program itself; for `bf16`, the plain reference at the
    precision the configuration states: looks, not controls)."""
    cfg, tr = spec.config, spec.traffic
    family = harness.load_module("families", cfg["family"])
    shapes = family.param_shapes(cfg)
    want = traffic.first_batches(tr, cfg, seed, tr["check_steps"])
    keep = bool(tr.get("keep_first_grads"))
    prog = None
    if "program" in which:      # first: its state is freed before the rest
        prog = program(spec, seed, devices, built if built is not None else {})

    def ref(**kw):
        return family.reference(cfg, lambda: weights.make(seed, shapes),
                                want, devices=devices, keep_grads=keep,
                                row_block=tr["reference_row_block"], **kw)

    truth = ref()
    b = tr["batch"]
    runs = {"control": dict(precision="fp8"),
            "bf16": dict(precision="bf16"),      # a look, not a control
            "half_batch": dict(rows=b // 2),
            "no_exchange": dict(rows=b // spec.chips)}
    out = {}
    for name in which:
        if name == "no_exchange" and spec.chips == 1:
            continue
        got = prog if name == "program" else ref(**runs[name])
        got["feed_rows_wrong"] = 0
        correct, numbers, detail = harness.compare(
            got, truth, spec.cell["limits"])
        if dump:        # every leaf's norms, for a look at other statistics
            leaves = {"ref_grad": truth["grad_norms"],
                      "got_grad": got["grad_norms"],
                      "ref_update": truth["update_norms"],
                      "got_update": got["update_norms"],
                      "losses": [got["losses"], truth["losses"]]}
            if "first_grads" in got and "first_grads" in truth:
                leaves["grad_diff"] = harness.grad_diff(
                    got["first_grads"], truth["first_grads"],
                    {k: 1.0 for k in truth["grad_norms"]})
            with open(f"{dump}_{seed}_{name}.json", "w") as f:
                json.dump(leaves, f)
        out[name] = (correct, {k: v["value"] for k, v in numbers.items()},
                     dict(detail["all"], grad_norm_leaf=detail["grad_norm_leaf"],
                          update_norm_leaf=detail["update_norm_leaf"]))
    return out


def main(argv):
    from tensorflowonspark_tpu import util

    util.enable_compile_cache()
    import jax

    which = [a[5:].split(",") for a in argv if a.startswith("only=")]
    which = tuple(which[0]) if which else ("control", "half_batch",
                                           "no_exchange")
    dump = [a[5:] for a in argv if a.startswith("dump=")]
    first = [int(a[6:]) for a in argv if a.startswith("first=")]
    argv = [a for a in argv if "=" not in a]
    cell, seeds = argv[0], [int(s) for s in argv[1:]]
    spec = harness.load_spec(cell, 0, 1, 0)
    devices = jax.devices()[:spec.chips]
    print(json.dumps({"device": devices[0].device_kind, "chips": len(devices)}))
    built = {}
    for i, seed in enumerate(seeds):
        t0 = time.time()
        got = readings(spec, seed, devices,
                       which if not first or i < first[0] else ("program",),
                       built, dump[0] if dump else None)
        print(json.dumps({"cell": cell, "seed": seed,
                          "seconds": time.time() - t0,
                          "readings": {k: {"correct": c, **every}
                                       for k, (c, n, every) in got.items()}}),
              flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
