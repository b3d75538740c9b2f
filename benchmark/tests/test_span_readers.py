"""The three readers of the program's own spans and counters
(`metrics/span_stat.py`, `counter_ratio.py`, `put_get_ms.py` over
`spans.py`) on a report made by hand: the numbers worked out by hand, and
nothing on a ring that lost spans of the window, on feeder tasks that
overlap, and on puts and gets that do not count the same.

The hand-made run: a window of 10 s starting at wall-clock 1000 s.  Two
feeder tasks, one after the other ([999, 1004] and [1006, 1012]: the
feeder is absent for 2 of the window's 10 s), each putting two items; one
node that gets the four.  Each process has a clock of its own: the
anchors put them on one.
"""
import copy
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import toy  # noqa: E402,F401  (puts benchmark/ on the path)
import harness  # noqa: E402
import spans as spans_mod  # noqa: E402

span_stat = harness.load_module("metrics", "span_stat")
counter_ratio = harness.load_module("metrics", "counter_ratio")
put_get_ms = harness.load_module("metrics", "put_get_ms")

RUN = {"result": {"t_window": 1000.0, "window": {"seconds": 10.0}}}


def report(source, mono0, spans, counters=None, recorded=None, dropped=0):
    """A report whose monotonic clock read `mono0` seconds at wall-clock
    0; `spans` are `(name, wall t0, wall t1, attrs)`."""
    anchor = {"wall_ns": int(500e9), "mono_ns": int((500 + mono0) * 1e9)}
    out = []
    for i, (name, t0, t1, attrs) in enumerate(spans):
        out.append({"id": i + 1, "cause": None, "name": name,
                    "t0_ms": (t0 + mono0) * 1e3, "t1_ms": (t1 + mono0) * 1e3,
                    "dur_ms": (t1 - t0) * 1e3, "attrs": attrs})
    return {"source": source, "anchor": anchor, "spans": out,
            "counters": counters or {},
            "recorded": len(out) if recorded is None else recorded,
            "dropped": dropped}


def collected():
    f1 = report("feeder:0:11", 7.0, [
        ("feed.source", 999.0, 999.5, {"records": 512}),
        ("feed.pack", 999.5, 1000.5, {"bytes": 100}),         # 0.5 s inside
        ("feed.queue_put", 1000.5, 1001.0, {"route": "queue_oversize",
                                            "bytes": 100, "item": 0}),
        ("feed.source", 1001.0, 1001.25, {"records": 512}),
        ("feed.pack", 1001.25, 1002.0, {"bytes": 100}),
        ("feed.queue_put", 1002.0, 1003.0, {"route": "queue_oversize",
                                            "bytes": 100, "item": 1}),
        ("feed.join", 1003.0, 1004.0, {}),
        ("feed.task", 999.0, 1004.0, {"records": 1024}),
    ], {"feed.bytes.queue_oversize": 200, "feed.items.queue_oversize": 2})
    f2 = report("feeder:0:12", -3.0, [
        ("feed.encode", 1006.0, 1006.5, {"bytes": 300}),
        ("feed.ring_write", 1006.5, 1008.5, {"bytes": 300,
                                             "blocked_ms": 1500.0}),
        ("feed.queue_put", 1008.5, 1008.75, {"route": "ring_ref",
                                             "bytes": 300, "item": 0}),
        ("feed.queue_put", 1009.0, 1011.0, {"route": "queue", "bytes": 100,
                                            "item": 1}),
        ("feed.task", 1006.0, 1012.0, {"records": 600}),
    ], {"feed.bytes.ring": 300, "feed.items.ring": 1,
        "feed.bytes.queue": 100, "feed.items.queue": 1})
    node = report("node:0", 100.0, [
        ("jaxpr_trace_duration", 900.0, 910.0, {}),
        ("jaxpr_trace_duration", 902.0, 904.0, {}),      # inside the first
        ("jaxpr_trace_duration", 950.0, 951.0, {}),
        ("feed.queue_get", 999.0, 1001.5, {"got": "packed", "item": 0}),
        ("feed.stack", 1001.5, 1001.75, {"bytes": 100}),
        ("feed.h2d", 1001.75, 1001.76, {"bytes": 100}),
        ("feed.queue_get", 1002.0, 1003.25, {"got": "packed", "item": 1}),
        ("feed.stack", 1003.25, 1003.75, {"bytes": 100}),
        ("feed.queue_get", 1004.0, 1008.7, {"got": "ring_ref", "item": 2}),
        ("feed.stack", 1008.7, 1009.45, {"bytes": 100}),
        ("feed.queue_get", 1009.5, 1011.5, {"got": "packed", "item": 3}),
        ("feed.queue_get", 1011.5, 1011.6, {"got": "end"}),
    ])
    boot = report("bootstrap:0", 55.0, [
        ("node.rendezvous", 800.0, 801.5, {"nodes": 1})])
    driver = report("driver", 1.0, [
        ("cluster.train", 998.0, 1004.5, {"partitions": 1})])
    return [driver, boot, f1, f2, node]


def loaded(reports=None):
    return spans_mod.load(RUN, reports if reports is not None
                          else collected())


def test_spans_land_on_one_wall_clock():
    got = loaded()
    assert got["window"] == (1000.0, 1010.0)
    task = [s for s in got["feeder"][1]["spans"] if s["name"] == "feed.task"]
    assert task[0]["t0"] == pytest.approx(1006.0, abs=1e-6)
    get = [s for s in got["node"][0]["spans"] if s["name"] == "feed.queue_get"]
    assert get[0]["t1"] == pytest.approx(1001.5, abs=1e-6)


def test_shares_of_the_window():
    got = loaded()
    # tasks cover [1000, 1004] and [1006, 1010] of the window: 8 of 10 s
    assert span_stat.compute(got, "feeder", ["feed.task"], "absent") == \
        pytest.approx(20.0)
    # pack .5 + .75, put .5 + 1, encode .5, write 2 less 1.5 blocked,
    # put .25, and [1009, 1010] of the last put: 5 s
    busy = span_stat.compute(
        got, "feeder", ["feed.pack", "feed.encode", "feed.ring_write",
                        "feed.queue_put"], "share",
        less={"feed.ring_write": "blocked_ms"})
    assert busy == pytest.approx(50.0)
    # source: [999, 999.5] lies outside; .25 s inside
    assert span_stat.compute(got, "feeder", ["feed.source"], "share") == \
        pytest.approx(2.5)
    # gets: 1.5 + 1.25 + 4.7 + .5 (clipped at 1010)
    assert span_stat.compute(got, "node", ["feed.queue_get"], "share") == \
        pytest.approx(79.5)


def test_medians_and_sums():
    got = loaded()
    assert span_stat.compute(got, "node", ["feed.stack"], "median_ms") == \
        pytest.approx(500.0)
    assert span_stat.compute(got, "node", ["feed.h2d"], "median_ms") == \
        pytest.approx(10.0)
    assert span_stat.compute(got, "bootstrap", ["node.rendezvous"], "sum_s",
                             when="before") == pytest.approx(1.5)
    # nested trace events count once: [900, 910] and [950, 951]
    assert span_stat.compute(got, "node", ["jaxpr_trace_duration"],
                             "union_s", when="before") == pytest.approx(11.0)
    assert span_stat.compute(got, "node", ["jaxpr_trace_duration"], "sum_s",
                             when="before") == pytest.approx(13.0)
    # nothing compiled: 0 where the listener is known to have been there,
    # nothing where it is not
    assert span_stat.compute(got, "node", ["backend_compile_duration"],
                             "union_s", when="before",
                             given=["jaxpr_trace_duration"]) == 0.0
    assert span_stat.compute(got, "node", ["backend_compile_duration"],
                             "union_s", when="before") is None
    assert span_stat.compute(got, "node", ["feed.resolve"],
                             "median_ms") is None


def test_counter_ratio():
    got = loaded()
    assert counter_ratio.compute(got, "feeder", "feed.bytes.ring",
                                 "feed.bytes.") == pytest.approx(50.0)
    assert counter_ratio.compute(got, "node", "feed.bytes.ring",
                                 "feed.bytes.") is None


def test_put_to_get_pairs_by_ordinal():
    got = loaded()
    pairs = spans_mod.pairs(got)
    assert [(p["attrs"]["route"], g["attrs"]["item"]) for p, g in pairs] == [
        ("queue_oversize", 0), ("queue_oversize", 1), ("ring_ref", 2),
        ("queue", 3)]
    # get end less put end: .5, .25, -.05 (a get may end before its put
    # does), and the fourth get ends outside the window
    assert put_get_ms.compute(got) == pytest.approx(250.0)


def test_nothing_where_the_program_has_no_report():
    assert spans_mod.load(RUN, collected())["node"]
    empty = loaded([])
    assert span_stat.compute(empty, "node", ["feed.stack"],
                             "median_ms") is None
    assert counter_ratio.compute(empty, "feeder", "feed.bytes.ring",
                                 "feed.bytes.") is None
    assert put_get_ms.compute(empty) is None
    assert spans_mod.load({"result": {}}, collected()) is None


def test_nothing_on_a_truncated_ring():
    reports = collected()
    node = reports[-1]
    # the ring wrapped and its oldest span is later than the window's start
    node["spans"] = node["spans"][6:]
    node["recorded"] = 12
    got = loaded(reports)
    assert span_stat.compute(got, "node", ["feed.queue_get"],
                             "share") is None
    assert put_get_ms.compute(got) is None
    # a ring that wrapped before the window started still serves the window
    reports = collected()
    reports[-1]["spans"] = reports[-1]["spans"][2:]
    reports[-1]["recorded"] = 12
    got = loaded(reports)
    assert span_stat.compute(got, "node", ["feed.queue_get"], "share") == \
        pytest.approx(79.5)
    # ... but not what came before it
    assert span_stat.compute(got, "node", ["jaxpr_trace_duration"],
                             "union_s", when="before") is None
    # and dropped spans (the export fault site) give nothing at all
    reports = collected()
    reports[-1]["dropped"] = 1
    assert span_stat.compute(loaded(reports), "node", ["feed.stack"],
                             "median_ms") is None


def test_nothing_on_overlapping_feeder_tasks():
    reports = collected()
    for s in reports[3]["spans"]:              # the second feeder, earlier
        s["t0_ms"] -= 3000.0
        s["t1_ms"] -= 3000.0
    got = loaded(reports)
    assert spans_mod.tasks_overlap(got["feeder"])
    assert span_stat.compute(got, "feeder", ["feed.task"], "absent") is None
    assert put_get_ms.compute(got) is None


def test_nothing_on_unequal_counts():
    reports = collected()
    reports[-1]["spans"] = [s for s in reports[-1]["spans"]
                            if s["attrs"].get("item") != 3]
    reports[-1]["recorded"] = len(reports[-1]["spans"])
    assert put_get_ms.compute(loaded(reports)) is None
    # ordinals that do not count up from 0: the node's ring lost its start
    reports = collected()
    reports[-1]["spans"] = [s for s in reports[-1]["spans"]
                            if s["attrs"].get("item") != 0]
    reports[-1]["recorded"] = len(reports[-1]["spans"])
    reports[2]["spans"] = reports[2]["spans"][3:]
    assert put_get_ms.compute(loaded(reports)) is None
    # a get that ends before its put has started
    reports = copy.deepcopy(collected())
    for s in reports[-1]["spans"]:
        if s["attrs"].get("item") == 1:
            s["t0_ms"] -= 2000.0
            s["t1_ms"] -= 2000.0
    assert put_get_ms.compute(loaded(reports)) is None


def test_the_metric_files_name_readers_and_arguments_that_exist():
    import inspect
    import json

    for name in sorted(os.listdir(os.path.join(toy.BENCH, "metrics"))):
        if not name.endswith(".json"):
            continue
        with open(os.path.join(toy.BENCH, "metrics", name)) as f:
            desc = json.load(f)
        reader = harness.load_module("metrics", desc["reader"])
        params = inspect.signature(reader.read).parameters
        assert set(desc.get("args", {})) <= set(params) - {"run"}, name
