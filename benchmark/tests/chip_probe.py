#!/usr/bin/env python3
"""Builder's probe for the chip: runs of cells (or of the small LM of
`toy.py`) in one call, each printed as `run.py` prints it, a kept trace with
its listing under `chiprun_out/`.

    chiprun -- python3 benchmark/tests/chip_probe.py <cell|lm_small>:<seed>:<seconds>:<trace>[:keep] ...
"""
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import toy  # noqa: E402  (puts benchmark/ and the repo on the path)
import harness  # noqa: E402
import run  # noqa: E402

OUT = os.path.join(toy.ROOT, "chiprun_out")


def one(arg):
    name, seed, seconds, trace, *keep = arg.split(":")
    t0 = time.time()
    if name == "lm_small":
        spec = toy.spec("lm_small", seed=int(seed), seconds=float(seconds),
                        trace=int(trace), platform="tpu")
        spec.peaks = json.load(open(os.path.join(
            toy.BENCH, "peaks.json")))["devices"]
    else:
        spec = harness.load_spec(name, int(seed), float(seconds), int(trace))
    spec.t_start = t0
    if keep:
        spec.keep_trace = os.path.join(OUT, f"trace_{name}")
    result = harness.drive(spec)
    line = run.result_line(spec, result) if name != "lm_small" else {
        "correct": result["correct"], "compared": result["numbers"]}
    notes = {k: result[k] for k in (
        "info", "memory", "cache", "program", "reference", "reference_s",
        "launch_s", "setup_s", "fed_records", "trace_read_s", "exit_s") if k in result}
    w = dict(result["window"])
    iv = sorted(w.pop("intervals_ms"))
    w.update(step_ms_median=iv[len(iv) // 2], step_ms_max=iv[-1],
             step_ms_min=iv[0])
    notes["window"] = w
    notes["detail"] = {k: v for k, v in result["detail"].items()
                       if k != "leaves_left_out"}
    notes["intervals_ms"] = [round(x, 1) for x in result["window"]["intervals_ms"]][:80]
    notes["left_out"] = len(result["detail"]["leaves_left_out"])
    notes["wall_s"] = time.time() - t0
    if result.get("trace"):
        tr = dict(result["trace"])
        tr.pop("ops")
        notes["trace"] = tr
    print(json.dumps({"run": arg, "notes": notes}), flush=True)
    print(json.dumps(line), flush=True)
    if keep:
        import subprocess
        import tracered

        path = tracered.find(spec.keep_trace)
        listing = subprocess.run(
            [sys.executable, os.path.join(toy.BENCH, "tracered.py"), path],
            capture_output=True, text=True,
            env=dict(os.environ, JAX_PLATFORMS="cpu"))
        with open(spec.keep_trace + ".listing.txt", "w") as f:
            f.write(listing.stdout + listing.stderr[-2000:])
        with open(spec.keep_trace + ".reduced.json", "w") as f:
            json.dump(result["trace"], f)
        if name != "lm_small":      # a cell's trace is too large to bring back
            import shutil
            shutil.rmtree(spec.keep_trace, ignore_errors=True)


if __name__ == "__main__":
    os.makedirs(OUT, exist_ok=True)
    for a in sys.argv[1:]:
        try:
            one(a)
        except Exception as e:     # go on to the next run of the call
            import traceback
            traceback.print_exc()
            print(json.dumps({"run": a, "error": str(e)[-3000:]}), flush=True)
