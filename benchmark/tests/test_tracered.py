"""The trace reduction: interval arithmetic on made-up events, and the whole
reduction against a trace recorded on the v5e in PR 25 (`data/`: the small LM
of `toy.py`, two steps, one chip)."""
import glob
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import toy  # noqa: E402
import tracered  # noqa: E402

DATA = os.path.join(toy.HERE, "data")


def test_union_and_subtract():
    u = tracered.union([(0, 10), (5, 20), (30, 40), (40, 45)])
    assert u == [[0, 20], [30, 45]] and tracered.length(u) == 35
    assert tracered.subtract(u, [[10, 32], [44, 50]]) == 10 + 12
    assert tracered.subtract(u, []) == 35
    assert tracered.subtract([[0, 10]], [[0, 10]]) == 0


def test_self_times_leave_out_what_an_event_holds():
    evs = [("while", 0, 100), ("a", 10, 30), ("b", 40, 60), ("c", 120, 130)]
    assert tracered.self_times(evs) == {"while": 60, "a": 20, "b": 20,
                                        "c": 10}


def test_reduce_on_made_up_planes():
    planes = {
        "/device:TPU:0": {"XLA Ops": [("fusion.1", 0, 4e6), ("all-reduce.2",
                                      4e6, 6e6), ("fusion.3", 8e6, 10e6)],
                          "Steps": [("0", 0, 10e6)]},
        "/device:TPU:1": {"XLA Ops": [("fusion.1", 0, 5e6), ("all-reduce.2",
                                      5e6, 6e6), ("fusion.3", 8e6, 10e6)]},
        "/host:CPU": {"main": [("bench.next_batch", 5.5e6, 8.5e6)]},
    }
    r = tracered.reduce(planes, spans=("bench.next_batch",))
    assert r["devices"] == 2
    assert r["window_s"] == pytest.approx(0.010)
    assert r["busy_s"] == pytest.approx(0.008)
    assert r["exposed_collective_s"] == pytest.approx(0.0015)
    assert r["ops"]["fusion.1"] == pytest.approx(0.009)
    assert r["gaps"] == {"bench.next_batch": pytest.approx(0.002)}
    assert tracered.reduce({"/host:CPU": {}}) is None


@pytest.mark.skipif(not glob.glob(os.path.join(DATA, "*.xplane.pb")),
                    reason="no recorded trace")
def test_recorded_v5e_trace():
    path = glob.glob(os.path.join(DATA, "*.xplane.pb"))[0]
    with open(os.path.join(DATA, "expected.json")) as f:
        want = json.load(f)
    planes = tracered.load(path)
    assert "/device:TPU:0" in planes and "XLA Ops" in planes["/device:TPU:0"]
    r = tracered.reduce(planes, spans=want["spans"])
    assert r["devices"] == 1
    assert r["busy_s"] == pytest.approx(want["busy_s"], rel=1e-6)
    assert r["window_s"] == pytest.approx(want["window_s"], rel=1e-6)
    assert 0 < r["busy_s"] <= r["window_s"]
    for pattern in want["kernels"]:
        import re
        assert any(re.search(pattern, n) for n in r["ops"]), pattern
    assert set(r["gaps"]) <= set(want["spans"]) | {"other"}
