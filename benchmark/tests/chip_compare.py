#!/usr/bin/env python3
"""Builder's comparison on the chip: `run.py` through its command line from
two checkouts in one call (the same chip, the same session), in the order
given, each run's result line and notes kept under `chiprun_out/`.

    chiprun -- python3 benchmark/tests/chip_compare.py <name> <seconds> \\
        <dir>:<cell>:<seed>:<trace> [...]

`<dir>` is a checkout relative to the repository's root (`.` for the tree as
it stands; `.chip_archive/parent` for `git archive <parent>` unpacked there
with this tree's `BENCHMARK.json` and `benchmark/` laid over it, as the
driver measures a parent).  The two sides of one comparison share a seed;
every other run has a seed of its own.
"""
import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def main(argv):
    name, seconds, plan = argv[0], argv[1], argv[2:]
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"compare_{name}.jsonl"), "a") as log:
        for i, item in enumerate(plan):
            side, cell, seed, trace = item.split(":")
            cwd = os.path.normpath(os.path.join(ROOT, side))
            t0 = time.time()
            p = subprocess.run(
                [sys.executable, os.path.join(cwd, "benchmark", "run.py"),
                 "--workload", cell, "--seed", seed, "--seconds", seconds,
                 "--trace", trace], capture_output=True, text=True, cwd=cwd,
                env=dict(os.environ, BENCH_RUN=f"{name}-{i}"))
            lines = [x for x in p.stdout.splitlines() if x.startswith("{")]
            rec = {"side": side, "cell": cell, "seed": seed, "trace": trace,
                   "rc": p.returncode, "wall_s": time.time() - t0,
                   "line": json.loads(lines[-1]) if lines else None,
                   "notes": (json.loads(lines[-2]).get("notes")
                             if len(lines) > 1 else None),
                   "trace_log": [x for x in p.stderr.splitlines()
                                 if " trace: " in x],
                   "stderr_tail": p.stderr[-800:]}
            log.write(json.dumps(rec) + "\n")
            log.flush()
            line, notes = rec["line"] or {}, rec["notes"] or {}
            print(json.dumps({
                "side": side, "cell": cell, "seed": seed, "trace": trace,
                "rc": p.returncode, "wall_s": round(rec["wall_s"], 1),
                "correct": line.get("correct"),
                "metrics": {k: v["value"] for k, v in
                            line.get("metrics", {}).items()},
                "window": notes.get("window"),
                "err": None if lines else p.stderr[-1500:]}), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
