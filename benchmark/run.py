#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is one JSON object: `correct`, `attempted`
and `failed` (steps), `metrics` (the cell's end-to-end metrics, or with
`--trace 1` its per-layer metrics), `device` (as the node process saw it),
with `--trace 1` a `breakdown`, and last `compared`: every number the
comparison with the plain reference looked at, beside its limit.  The same
numbers are the last lines of standard error.  Exit is non-zero, and no
result is printed, when the node finds no TPU or fewer chips than the cell
asks for, or the program under test is not in the checkout.
"""
import argparse
import json
import os
import statistics
import sys
import time

T_START = time.time()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [ROOT, HERE]


def end_to_end(spec, result):
    """The end-to-end metrics, all from the host's clock: all the work of
    all the steps of the window over all of its seconds."""
    w = result["window"]
    units = w["records"] * spec.traffic["units_per_record"]
    return {"setup_s": result["setup_s"],
            spec.cell["rate_metric"]: units / w["seconds"]}


def per_layer(spec, result):
    """Each per-layer metric from its own reader; a reader that finds
    nothing to read returns None and the metric is left out."""
    import harness
    import traffic

    family = harness.load_module("families", spec.config["family"])
    run = {"result": result, "spec": spec,
           "work": family.step_work(spec.config, spec.traffic["batch"]),
           "peak": spec.peaks.get(result["device"]["kind"]),
           "record_bytes": traffic.record_bytes(spec.traffic, spec.config)}
    out = {}
    for m in spec.per_layer:
        desc = traffic.load("metrics", m["name"])
        reader = harness.load_module("metrics", desc["reader"])
        value = reader.read(run, **desc.get("args", {}))
        if value is not None:
            out[m["name"]] = value
    return out


def result_line(spec, result):
    units = {m["name"]: m["unit"] for m in spec.end_to_end + spec.per_layer}
    if spec.trace:
        values = per_layer(spec, result)
    else:
        wanted = {m["name"] for m in spec.end_to_end}
        values = {k: v for k, v in end_to_end(spec, result).items()
                  if k in wanted}
    device = dict(result["device"], count=spec.chips,
                  memory_peak_bytes=result["memory"]["memory_peak_bytes"])
    line = {"correct": bool(result["correct"]),
            "attempted": result["window"]["steps"], "failed": 0,
            "metrics": {k: {"value": v, "unit": units[k]}
                        for k, v in values.items()},
            "device": device}
    tr = result.get("trace")
    if spec.trace and tr:
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        ops = list(tr["groups"].items())[:10]
        gaps = sorted(tr["gaps"].items(), key=lambda kv: -kv[1])[:10]
        line["breakdown"] = {"device_ops": [list(x) for x in ops],
                             "idle_gaps": [list(x) for x in gaps]}
    line["compared"] = result["numbers"]
    return line


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ns = ap.parse_args(argv)

    import harness

    spec = harness.load_spec(ns.workload, ns.seed, ns.seconds, ns.trace)
    spec.t_start = T_START
    result = harness.drive(spec)
    if "jax" in sys.modules:
        raise RuntimeError("the run's own process imported jax: it would "
                           "hold the chip")
    line = result_line(spec, result)
    notes = {k: result[k] for k in ("info", "memory", "cache", "detail",
                                    "program", "reference", "reference_s",
                                    "launch_s", "fed_records",
                                    "shard_devices", "trace_read_s")
             if k in result}
    notes["window"] = {k: v for k, v in result["window"].items()
                       if k != "intervals_ms"}
    iv = result["window"]["intervals_ms"]
    notes["window"]["step_ms_median"] = statistics.median(iv)
    notes["window"]["step_ms_max"] = max(iv)
    print(json.dumps({"notes": notes}), flush=True)
    for name, n in result["numbers"].items():
        print(f"compared {name}: {n['value']!r} limit {n['limit']!r}",
              file=sys.stderr)
    print(f"correct: {line['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
