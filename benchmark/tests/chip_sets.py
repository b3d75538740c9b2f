#!/usr/bin/env python3
"""Builder's full sets on the chip: `run.py` through its command line, as the
driver runs it, `sets` times over the same seeds, the runs of one cell in one
call; prints each run's last line and, per metric, each set's median and
spread (quartile distance over the median, `statistics.quantiles(n=4)`).

    chiprun -- python3 benchmark/tests/chip_sets.py <cell> <seconds> <sets> <trace> <seed> [<seed> ...]
"""
import json
import os
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def spread(values):
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main(argv):
    cell, seconds, sets, trace = argv[0], argv[1], int(argv[2]), argv[3]
    seeds = argv[4:]
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    log = open(os.path.join(out, f"sets_{cell}_t{trace}.jsonl"), "a")
    table = []
    for s in range(sets):
        for seed in seeds:
            t0 = time.time()
            p = subprocess.run(
                [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
                 "--workload", cell, "--seed", seed, "--seconds", seconds,
                 "--trace", trace], capture_output=True, text=True,
                cwd=ROOT, env=dict(os.environ, BENCH_RUN=f"{s}-{seed}"))
            lines = [x for x in p.stdout.splitlines() if x.startswith("{")]
            rec = {"set": s, "seed": seed, "rc": p.returncode,
                   "wall_s": time.time() - t0,
                   "line": json.loads(lines[-1]) if lines else None,
                   "stderr_tail": p.stderr[-600:]}
            if len(lines) > 1:
                rec["notes"] = json.loads(lines[-2]).get("notes")
            log.write(json.dumps(rec) + "\n")
            log.flush()
            table.append(rec)
            line = rec["line"] or {}
            print(json.dumps({
                "set": s, "seed": seed, "rc": p.returncode,
                "wall_s": round(rec["wall_s"], 1),
                "correct": line.get("correct"),
                "metrics": {k: v["value"] for k, v in
                            line.get("metrics", {}).items()},
                "compared": {k: v["value"] for k, v in
                             line.get("compared", {}).items()},
                "err": None if lines else p.stderr[-1500:]}), flush=True)
    names = sorted({k for r in table if r["line"]
                    for k in r["line"]["metrics"]})
    for name in names:
        for s in range(sets):
            vals = [r["line"]["metrics"][name]["value"] for r in table
                    if r["set"] == s and r["line"]
                    and name in r["line"]["metrics"]]
            if len(vals) >= 3:
                print(json.dumps({"metric": name, "set": s, "n": len(vals),
                                  "median": statistics.median(vals),
                                  "spread": spread(vals),
                                  "min": min(vals), "max": max(vals)}),
                      flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
