"""`BENCHMARK.json` against the files it names, and `run.py` at its edges."""
import json
import os
import re
import shutil
import subprocess
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import toy  # noqa: E402
import harness  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def bench():
    with open(os.path.join(toy.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_every_name_has_its_files():
    b = bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    configs = {c["name"]: c for c in b["configs"]}
    for c in b["configs"]:
        assert NAME.match(c["name"]) and len(c["source"]) <= 200
        assert c["file"] == f"benchmark/configs/{c['name']}.json"
        cfg = json.load(open(os.path.join(toy.ROOT, c["file"])))
        assert cfg["reduced"] == c["reduced"] == []
        assert cfg["assumed"] and cfg["departures"]
        assert os.path.exists(os.path.join(
            toy.BENCH, "families", cfg["family"] + ".py"))
    cells = set()
    for w in b["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["name"] == f"{w['config']}.{w['traffic']}"
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert 0 < len(w["why"]) <= 200
        cell = json.load(open(os.path.join(
            toy.BENCH, "workloads", w["name"] + ".json")))
        assert cell["chips"] == w["chips"] and cell["traffic"] == w["traffic"]
        assert os.path.exists(os.path.join(
            toy.BENCH, "traffic", w["traffic"] + ".json"))
        cells.add(w["name"])
    # and no cell's file lies about without its entry
    assert {f[:-5] for f in os.listdir(os.path.join(toy.BENCH, "workloads"))
            } == cells
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= cells
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in b["per_layer"]:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        desc = json.load(open(os.path.join(
            toy.BENCH, "metrics", m["name"] + ".json")))
        assert os.path.exists(os.path.join(
            toy.BENCH, "metrics", desc["reader"] + ".py"))
        # every cell that reports the metric reports what it should move
        moved = e2e[m["moves"]]
        assert set(m["workloads"]) <= set(moved.get("workloads", cells))
    for cell in cells:
        spec = harness.load_spec(cell, 1, 1, 0)
        assert len(spec.end_to_end) >= 2 and spec.per_layer
        assert spec.cell["rate_metric"] in {m["name"] for m in spec.end_to_end}


def test_off_the_chip_no_result_and_a_nonzero_exit(tmp_path):
    """Here there is no TPU: the node refuses, the run prints no result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    p = subprocess.run(
        [sys.executable, os.path.join(toy.BENCH, "run.py"), "--workload",
         "resnet50-gn.fed_u8_b256", "--seed", "2147483659", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=280)
    assert p.returncode != 0
    assert '"metrics"' not in p.stdout
    assert "platform" in p.stderr


def test_without_the_program_no_result_and_a_nonzero_exit(tmp_path):
    """A directory that holds only BENCHMARK.json and the benchmark."""
    shutil.copy(os.path.join(toy.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(toy.BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    p = subprocess.run(
        [sys.executable, str(tmp_path / "benchmark" / "run.py"),
         "--workload", "gpt2-large.fed_b8", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, cwd=tmp_path,
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"},
        timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
