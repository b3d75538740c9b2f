#!/usr/bin/env python3
"""The faults a family can plant in its own reference, read against the
plain reference: `control.py` for the faults it does not know.

For each seed: the reference in float32, then in its place the reference
with each of the family's `FAULTS` planted (`reference(fault=...)`: for
`lfm2_moe` the selection bias left out of the choice, the bias left in the
weights, one tap of the convolution zeroed, the query/key norm left out, one
held expert's output zeroed), each compared with the float32 reference by
the harness's own `compare`, so the readings are the numbers a run prints.
`program`, `control`, `bf16` and `half_batch` are read as `control.py` reads
them, in the same process, so that the step program is compiled once and
the float32 reference followed once a seed.

On the chip, at the cell's own size (one process, JAX is imported here):

    chiprun -- python3 benchmark/tests/faults.py <cell> <seed> [<seed> ...] [only=program,control,half_batch,<fault>,...] [first=<n>]

(without `only=`: the program, the control, half batch and every fault of
the family; `first=<n>`: that on the first n seeds, the program alone on the
rest: the lower reading wants a dozen seeds, the upper three.)  `tests/test_lfm2_moe.py` runs the same function at toy size on the
CPU.
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


def readings(spec, seed, devices=None, which=None, built=None):
    """`{name: (correct, numbers, every number)}` for each of `which`:
    `program` (the step object itself, `control.program`), `control`,
    `bf16`, `half_batch` (the reference at another precision or on half the
    rows) and the family's faults by name."""
    cfg, tr = spec.config, spec.traffic
    family = harness.load_module("families", cfg["family"])
    shapes = family.param_shapes(cfg)
    want = traffic.first_batches(tr, cfg, seed, tr["check_steps"])
    runs = {"control": dict(precision="fp8"), "bf16": dict(precision="bf16"),
            "half_batch": dict(rows=tr["batch"] // 2),
            **{name: dict(fault=name) for name in family.FAULTS}}
    which = tuple(which or ("control", "half_batch") + family.FAULTS)
    prog = None
    if "program" in which:      # first: its state is freed before the rest
        prog = control.program(spec, seed, devices,
                               built if built is not None else {})

    def ref(**kw):
        return family.reference(cfg, lambda: weights.make(seed, shapes),
                                want, devices=devices,
                                row_block=tr["reference_row_block"], **kw)

    truth = ref()
    out = {}
    for name in which:
        got = prog if name == "program" else ref(**runs[name])
        got["feed_rows_wrong"] = 0
        correct, numbers, detail = harness.compare(
            got, truth, spec.cell["limits"])
        out[name] = (correct, {k: v["value"] for k, v in numbers.items()},
                     dict(detail["all"],
                          grad_norm_leaf=detail["grad_norm_leaf"],
                          update_norm_leaf=detail["update_norm_leaf"]))
    return out


def main(argv):
    from tensorflowonspark_tpu import util

    util.enable_compile_cache()
    import jax

    which = [a[5:].split(",") for a in argv if a.startswith("only=")]
    first = [int(a[6:]) for a in argv if a.startswith("first=")]
    argv = [a for a in argv if "=" not in a]
    cell, seeds = argv[0], [int(s) for s in argv[1:]]
    spec = harness.load_spec(cell, 0, 1, 0)
    devices = jax.devices()[:spec.chips]
    print(json.dumps({"device": devices[0].device_kind,
                      "chips": len(devices)}))
    built = {}
    for i, seed in enumerate(seeds):
        t0 = time.time()
        family = harness.load_module("families", spec.config["family"])
        everything = ("program", "control", "half_batch") + family.FAULTS
        got = readings(spec, seed, devices,
                       (which[0] if which else everything)
                       if not first or i < first[0] else ("program",), built)
        print(json.dumps({"cell": cell, "seed": seed,
                          "seconds": time.time() - t0,
                          "readings": {k: {"correct": c, **every}
                                       for k, (c, n, every) in got.items()}}),
              flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
