"""The feed plane's spans and counters (trace.process/span/report), from
the feeder task to the node, collected at the driver over the
reservation channel (REPORT) — on the CPU, LocalBackend, toy records.

One small cluster runs once for the module (`fed`); the two other
clusters pin what only another set-up shows: a ring smaller than one
chunk (the ResNet cell's case at toy size: `_push_chunks` cuts the chunk
by bytes) or than one record, and the `trace.export` fault site armed in
every process.
"""
import json
import logging
import multiprocessing as mp
import os
import subprocess
import sys
import time

import msgpack
import numpy as np
import pytest

from tensorflowonspark_tpu import (backend, cluster, faults, reservation,
                                   trace)

RECORDS = 1200          # 3 chunks of 512: two full, one short
ROW = 1024              # float32 values a record: 4 KiB, 2 MiB a chunk


def fn_consume(args, ctx):
    """Node function: numpy batches to the end of the feed; the count of
    records consumed is left in the executor's directory.  Record i of a
    partition is a row of i's: one out of its place fails the node."""
    df = ctx.get_data_feed()
    n = 0
    last = -1
    while not df.should_stop():
        batch = df.next_numpy_batch(64, timeout=60)
        if batch is not None:
            n += len(batch)
            ids = np.concatenate([[last], batch[:, 0]])
            if not ((ids[1:] == ids[:-1] + 1) | (ids[1:] == 0)).all():
                raise RuntimeError(f"records out of order: {ids}")
            last = ids[-1]
    with open(os.path.join(ctx.working_dir, "consumed"), "w") as f:
        f.write(str(n))


def _records(n=RECORDS, row=ROW):
    return [np.full((row,), i, np.float32) for i in range(n)]


def _run(tmp_path, partitions):
    """One executor, fed `partitions` one `c.train` call each; returns
    (trace report, records the node consumed)."""
    be = backend.LocalBackend(1, workdir=str(tmp_path))
    c = cluster.run(be, fn_consume, None, num_executors=1,
                    input_mode=cluster.InputMode.SPARK)
    for part in partitions:
        c.train([part])
    c.shutdown()
    with open(os.path.join(be.executor_dirs[0], "consumed")) as f:
        return c.trace_report(), int(f.read())


def _by_kind(report):
    out = {}
    for r in report:
        out.setdefault(r["source"].split(":")[0], []).append(r)
    return out


def _spans(reports, name):
    return [dict(s, wall0=trace.wall_ns(r["anchor"], s["t0_ms"]),
                 wall1=trace.wall_ns(r["anchor"], s["t1_ms"]))
            for r in reports for s in r["spans"] if s["name"] == name]


def _counters(reports):
    out = {}
    for r in reports:
        for k, v in r["counters"].items():
            out[k] = out.get(k, 0) + v
    return out


@pytest.fixture(scope="module")
def fed(tmp_path_factory):
    os.environ.setdefault("TFOS_TPU_SERVER_HOST", "127.0.0.1")
    lines = []
    handler = logging.Handler()
    handler.emit = lambda rec: lines.append(rec.getMessage())
    log = logging.getLogger("tensorflowonspark_tpu.cluster")
    level = log.level
    log.addHandler(handler)
    log.setLevel(logging.INFO)
    try:
        report, consumed = _run(tmp_path_factory.mktemp("fed"),
                                [_records(), _records()])
    finally:
        log.removeHandler(handler)
        log.setLevel(level)
    return {"report": report, "consumed": consumed, "kinds": _by_kind(report),
            "log": [m for m in lines if m.startswith("trace: ")]}


# ------------------------------------------- the process recorder alone ----

def test_importing_trace_does_not_import_jax():
    code = ("import sys; import tensorflowonspark_tpu.trace as t; "
            "t.span('x').__enter__(); print('jax' in sys.modules)")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run([sys.executable, "-c", code], cwd=root, text=True,
                         capture_output=True, timeout=120)
    assert out.stdout.strip() == "False", out.stderr


def test_one_span_costs_under_a_tenth_of_a_millisecond():
    """The bound the instrumentation is budgeted by: some ten spans a
    batch against steps of half a second.  Measured here with jax loaded
    (the node's case: the span also enters a TraceAnnotation): about 5 us;
    the bound leaves room for a loaded CI box."""
    n = 2000
    with trace.span("warm"):
        pass
    t0 = time.perf_counter()
    for _ in range(n):
        with trace.span("cost", bytes=1):
            pass
    assert (time.perf_counter() - t0) / n < 100e-6


def test_span_names_its_cause_and_keeps_attrs():
    with trace.span("outer.t", a=1) as outer:
        with trace.span("inner.t", cause=outer) as inner:
            inner.set(rows=3)
    got = {s["name"]: s for s in trace.report()["spans"]
           if s["name"] in ("outer.t", "inner.t")}
    assert got["inner.t"]["cause"] == got["outer.t"]["id"] == outer.id
    assert got["outer.t"]["cause"] is None
    assert got["inner.t"]["attrs"] == {"rows": 3}
    assert got["outer.t"]["attrs"] == {"a": 1}
    assert got["outer.t"]["t0_ms"] <= got["inner.t"]["t0_ms"]
    assert got["inner.t"]["t1_ms"] <= got["outer.t"]["t1_ms"]


def test_span_that_raises_is_recorded_abandoned():
    with pytest.raises(KeyError):
        with trace.span("broken.t"):
            raise KeyError("x")
    s = [s for s in trace.report()["spans"] if s["name"] == "broken.t"][-1]
    assert s["attrs"] == {"abandoned": True}


def test_span_ended_records_a_duration_told_afterwards():
    trace.span_ended("told.t", 0.25, fun="f")
    s = [s for s in trace.report()["spans"] if s["name"] == "told.t"][-1]
    assert s["dur_ms"] == pytest.approx(250.0, abs=0.01)
    assert s["attrs"] == {"fun": "f"}


def test_anchor_puts_a_span_on_the_wall_clock():
    before = time.time_ns()
    with trace.span("clock.t"):
        pass
    after = time.time_ns()
    rep = trace.report()
    s = [s for s in rep["spans"] if s["name"] == "clock.t"][-1]
    assert set(rep["anchor"]) == {"wall_ns", "mono_ns"}
    # to the clocks' own agreement: the anchor was read long before
    slack = 50_000_000
    assert before - slack <= trace.wall_ns(rep["anchor"], s["t0_ms"])
    assert trace.wall_ns(rep["anchor"], s["t1_ms"]) <= after + slack
    # every Recorder exports one, the per-object ones of serving too
    assert set(trace.Recorder().export()["anchor"]) == {"wall_ns", "mono_ns"}


def test_report_is_json_and_msgpack_ready():
    with trace.span("ship.t", bytes=7, route="ring_ref"):
        pass
    trace.counters().inc("ship.t.count", 2)
    rep = trace.report("feeder:0:1")
    assert set(rep) == {"source", "anchor", "spans", "counters", "recorded",
                        "dropped"}
    assert rep["source"] == "feeder:0:1"
    assert rep["counters"]["ship.t.count"] >= 2
    assert json.loads(json.dumps(rep)) == rep
    assert msgpack.unpackb(msgpack.packb(rep, use_bin_type=True),
                           raw=False) == rep


def _child_reports(conn):
    with trace.span("child.t"):
        pass
    conn.send(trace.report("child"))
    conn.close()


def test_a_forked_child_starts_with_an_empty_recorder():
    """Every LocalBackend task and node is a fork of the driver: its
    report must not repeat the driver's spans, counters or held reports,
    and its clock has an anchor of its own."""
    with trace.span("parent.t"):
        pass
    trace.counters().inc("parent.t.count")
    trace.process().add_report({"source": "node:77", "spans": []})
    parent = trace.report()
    here, there = mp.get_context("fork").Pipe()
    p = mp.get_context("fork").Process(target=_child_reports, args=(there,))
    p.start()
    child = here.recv()
    p.join(30)
    assert [s["name"] for s in child["spans"]] == ["child.t"]
    assert child["counters"] == {} and child["recorded"] == 1
    assert child["anchor"] != parent["anchor"]
    # and the parent's is as it was
    assert "parent.t" in [s["name"] for s in trace.report()["spans"]]
    assert any(r["source"] == "node:77" for r in trace.process().reports())


def _rep(source, spans, recorded=None, anchor=1):
    return {"source": source, "anchor": {"wall_ns": anchor, "mono_ns": 1},
            "spans": list(spans), "counters": {}, "dropped": 0,
            "recorded": len(spans) if recorded is None else recorded}


def test_reports_are_kept_whole_by_source_the_newest_few_hundred():
    rec = trace._Process()
    for i in range(trace.MAX_REPORTS + 10):
        rec.add_report(_rep(f"feeder:0:{i}", [i]))
    # the same source from another recorder (a pid used again): its
    # report takes the earlier one's place
    again = _rep(f"feeder:0:{trace.MAX_REPORTS}", ["again"], anchor=2)
    rec.add_report(again)
    got = rec.reports()
    assert len(got) == trace.MAX_REPORTS
    assert got[0]["source"] == "feeder:0:10"
    assert got[-1] == again
    assert sum(r["source"] == again["source"] for r in got) == 1


@pytest.mark.parametrize("second,spans,recorded", [
    # what came after the first report: it follows
    (_rep("f", ["c", "d"], recorded=4), ["a", "b", "c", "d"], 4),
    # the first report's answer was lost and the sender sent all again
    (_rep("f", ["a", "b", "c"], recorded=3), ["a", "b", "c"], 3),
    # the sender's ring lost "c" between the two: the count shows it
    (_rep("f", ["d"], recorded=4), ["a", "b", "d"], 4),
], ids=["follows", "sent_again", "gap"])
def test_a_later_report_of_one_recorder_continues_the_earlier(
        second, spans, recorded):
    rec = trace._Process()
    rec.add_report(dict(_rep("f", ["a", "b"]), counters={"n": 2}))
    rec.add_report(dict(second, counters={"n": 5}))
    (got,) = rec.reports()
    assert got["spans"] == spans and got["recorded"] == recorded
    assert got["counters"] == {"n": 5}     # they count from the start


def test_collected_spans_are_bounded_over_all_sources(monkeypatch):
    monkeypatch.setattr(trace, "PROCESS_RING", 4)
    monkeypatch.setattr(trace, "MAX_REPORT_SPANS", 8)
    rec = trace._Process()
    rec.add_report(_rep("a", range(6)))            # a source keeps 4
    assert rec.reports()[0]["spans"] == [2, 3, 4, 5]
    rec.add_report(_rep("b", range(3)))
    rec.add_report(_rep("c", range(3)))            # 10 held: "a" goes
    assert [r["source"] for r in rec.reports()] == ["b", "c"]


def test_report_since_holds_only_what_came_after():
    rec = trace.process()
    with trace.span("since.a"):
        pass
    mark = rec.recorded
    with trace.span("since.b"):
        pass
    rep = trace.report("x", since=mark)
    assert [s["name"] for s in rep["spans"]] == ["since.b"]
    assert rep["recorded"] == mark + 1
    assert trace.report("x", since=rep["recorded"])["spans"] == []
    assert len(trace.report("x")["spans"]) == min(rec.recorded,
                                                  trace.PROCESS_RING)


def test_compile_events_become_spans():
    """`util.enable_compile_cache` registers one jax.monitoring listener:
    what JAX says about tracing, lowering and compiling is on the
    process's timeline, under the event's last path component."""
    import jax
    import jax.numpy as jnp

    from tensorflowonspark_tpu import util

    util._trace_compile_events()
    util._trace_compile_events()          # once a process, however often
    x = jnp.arange(7)
    before = trace.by_span([trace.report()])
    jax.jit(lambda x: x * 3 + 1)(x).block_until_ready()
    after = trace.by_span([trace.report()])

    def grew(name):
        return after.get(name, [0])[0] - before.get(name, [0])[0]

    assert grew("jaxpr_trace_duration") >= 1
    assert grew("jaxpr_to_mlir_module_duration") == 1


def test_compile_events_under_a_millisecond_leave_nothing():
    """JAX reports a trace for every inner `jit` it passes through,
    thousands a step, inside the outer one's span: neither a span nor a
    counter, or they push the feed's spans out of the ring."""
    import jax

    from tensorflowonspark_tpu import util

    util._trace_compile_events()
    before = trace.report()
    for event, secs in (("/jax/core/compile/jaxpr_trace_duration", 2e-4),
                        ("/jax/compilation_cache/compile_time_saved_sec", 9.0),
                        ("/jax/other/thing", 9.0)):
        jax.monitoring.record_event_duration_secs(event, secs)
    after = trace.report()
    assert after["recorded"] == before["recorded"]
    assert after["counters"] == before["counters"]
    jax.monitoring.record_event_duration_secs(
        "/jax/compilation_cache/cache_retrieval_time_sec", 0.25)
    last = trace.report()["spans"][-1]
    assert last["name"] == "cache_retrieval_time_sec"
    assert 249.0 < last["dur_ms"] < 251.0


# ------------------------------------------------- the REPORT message ----

def test_report_message_reaches_the_servers_process():
    server = reservation.Server(1)
    addr = server.start(host="127.0.0.1")
    try:
        client = reservation.Client(addr)
        rep = {"source": "feeder:9:4242", "anchor": {"wall_ns": 1,
               "mono_ns": 1}, "spans": [], "counters": {"feed.items.ring": 3},
               "recorded": 0, "dropped": 0}
        assert client.send_report(rep) == {"type": "OK"}
        client.close()
        assert server.reported_sources() == {"feeder:9:4242"}
        assert rep in trace.process().reports()
    finally:
        server.stop()


def test_report_to_a_server_that_is_gone_never_raises():
    from tensorflowonspark_tpu import node

    server = reservation.Server(1)
    addr = server.start(host="127.0.0.1")
    server.stop()
    with trace.span("unsent.t"):
        pass
    sent = trace.process().sent
    t0 = time.time()
    node._send_report(addr, "node:0")
    assert time.time() - t0 < 10
    # nothing arrived, so the next report still carries it
    assert trace.process().sent == sent


def test_a_reused_process_sends_each_span_once():
    """A Spark Python worker runs many feeder tasks: each task's report
    carries what was recorded since the last one that arrived, and the
    driver joins them under the one source."""
    from tensorflowonspark_tpu import node

    server = reservation.Server(1)
    addr = server.start(host="127.0.0.1")
    try:
        for name in ("reuse.a", "reuse.b"):
            with trace.span(name):
                pass
            node._send_report(addr, "feeder:3:99")
            assert trace.process().sent == trace.process().recorded
        (got,) = [r for r in trace.process().reports()
                  if r["source"] == "feeder:3:99"]
        names = [s["name"] for s in got["spans"]]
        assert names[-2:] == ["reuse.a", "reuse.b"]
        assert names.count("reuse.a") == 1
        assert len(names) == min(got["recorded"], trace.PROCESS_RING)
    finally:
        server.stop()


# ------------------------------------------------------ a fed cluster ----

def test_report_holds_the_driver_each_feeder_task_and_the_node(fed):
    kinds = fed["kinds"]
    assert fed["consumed"] == 2 * RECORDS
    assert fed["report"][0]["source"] == "driver"
    assert len(kinds["driver"]) == 1 and len(kinds["node"]) == 1
    assert len(kinds["feeder"]) == 2                  # one a task
    assert kinds["node"][0]["source"] == "node:0"
    assert all(r["source"].startswith("feeder:0:") for r in kinds["feeder"])
    assert len({r["source"] for r in kinds["feeder"]}) == 2
    trains = _spans(kinds["driver"], "cluster.train")[-2:]
    assert [s["attrs"] for s in trains] == [{"partitions": 1}] * 2
    # a feeder's report holds its own spans, not the driver's
    for r in kinds["feeder"]:
        names = {s["name"] for s in r["spans"]}
        assert "cluster.train" not in names
        assert {"feed.task", "feed.connect", "feed.source", "feed.pack",
                "feed.encode", "feed.ring_write", "feed.queue_put",
                "feed.join"} <= names
    assert {"feed.take", "feed.queue_get", "feed.resolve", "feed.stack"} <= {
        s["name"] for s in kinds["node"][0]["spans"]}


def test_feeder_tasks_lie_inside_their_cluster_train_on_one_clock(fed):
    """The anchors line three processes' clocks up: each `feed.task` lies
    within the driver's `cluster.train` that started it."""
    trains = _spans(fed["kinds"]["driver"], "cluster.train")[-2:]
    tasks = sorted(_spans(fed["kinds"]["feeder"], "feed.task"),
                   key=lambda s: s["wall0"])
    slack = 5_000_000
    for train, task in zip(trains, tasks):
        assert train["wall0"] - slack <= task["wall0"]
        assert task["wall1"] <= train["wall1"] + slack
        assert task["attrs"]["records"] == RECORDS
    assert tasks[0]["wall1"] <= tasks[1]["wall0"] + slack


def test_children_name_their_parents(fed):
    for r in fed["kinds"]["feeder"]:
        task = [s for s in r["spans"] if s["name"] == "feed.task"][0]
        for s in r["spans"]:
            if s["name"] != "feed.task":
                assert s["cause"] == task["id"], s
    node = fed["kinds"]["node"][0]
    takes = {s["id"] for s in node["spans"] if s["name"] == "feed.take"}
    inner = [s for s in node["spans"]
             if s["name"] in ("feed.queue_get", "feed.resolve")]
    assert inner and all(s["cause"] in takes for s in inner)


def test_puts_and_gets_count_the_same_and_each_put_precedes_its_get(fed):
    """The k-th data item put is the k-th got.  A put STARTS before its
    get ends; it may end a moment after it (the manager hands the item to
    a waiting `get` before it answers the `put`)."""
    puts = sorted(_spans(fed["kinds"]["feeder"], "feed.queue_put"),
                  key=lambda s: s["wall1"])
    gets = sorted((s for s in _spans(fed["kinds"]["node"], "feed.queue_get")
                   if "item" in s["attrs"]),
                  key=lambda s: s["attrs"]["item"])
    assert len(puts) == len(gets) >= 2
    assert [g["attrs"]["item"] for g in gets] == list(range(len(gets)))
    for put, get in zip(puts, gets):
        assert put["wall0"] <= get["wall1"] + 1_000_000
        assert get["wall1"] - put["wall1"] > -100_000_000
        assert put["attrs"]["route"] == get["attrs"]["got"] == "ring_ref"
    # what was not a data item says so
    other = {s["attrs"]["got"] for s in
             _spans(fed["kinds"]["node"], "feed.queue_get")
             if "item" not in s["attrs"]}
    assert "end" in other and other <= {"end", "none", "marker"}


def test_small_records_count_under_the_ring(fed):
    c = _counters(fed["kinds"]["feeder"])
    assert c["feed.bytes.ring"] >= 2 * RECORDS * ROW * 4
    assert c["feed.items.ring"] == len(
        _spans(fed["kinds"]["feeder"], "feed.queue_put"))
    assert not any(k.startswith(("feed.bytes.queue", "feed.items.queue"))
                   or k == "feed.ring_fallbacks" for k in c)
    writes = _spans(fed["kinds"]["feeder"], "feed.ring_write")
    assert sum(s["attrs"]["bytes"] for s in writes) == c["feed.bytes.ring"]
    assert all(s["attrs"]["blocked_ms"] >= 0 for s in writes)
    resolved = _spans(fed["kinds"]["node"], "feed.resolve")
    assert sum(s["attrs"]["bytes"] for s in resolved) == c["feed.bytes.ring"]
    stacked = _spans(fed["kinds"]["node"], "feed.stack")
    assert sum(s["attrs"]["bytes"] for s in stacked) == 2 * RECORDS * ROW * 4


def test_the_bootstrap_task_reports_its_steps(fed):
    boot = fed["kinds"]["bootstrap"]
    assert [r["source"] for r in boot] == ["bootstrap:0"]
    spans = {s["name"]: s for s in boot[0]["spans"]}
    assert {"node.bootstrap", "node.manager_start", "node.ring_create",
            "node.register", "node.rendezvous"} <= set(spans)
    for name, s in spans.items():
        if name != "node.bootstrap":
            assert s["cause"] == spans["node.bootstrap"]["id"]
    assert spans["node.rendezvous"]["attrs"] == {"nodes": 1}


def test_shutdown_logs_five_lines(fed):
    log = fed["log"]
    assert len(log) == 5
    assert "feeder=2" in log[0] and "node=1" in log[0]
    assert "bytes.ring=" in log[1] and "items.ring=" in log[1]
    assert log[2].startswith("trace: feeder ") and "feed.task=2x" in log[2]
    assert log[3].startswith("trace: node ") and "feed.take=" in log[3]
    assert log[4].startswith("trace: driver ") and "cluster.train=" in log[4]


# ------------------------------------------------- a run that failed ----

def fn_consume_then_fail(args, ctx):
    fn_consume(args, ctx)
    raise RuntimeError("injected failure after the feed")


def test_a_failed_node_still_reports_and_a_bad_summary_masks_nothing(
        tmp_path, monkeypatch):
    """A failed run is where `feed.queue_get` and `feed.resolve` are
    wanted most: the node sends its report on the way out through the
    error path too.  And the five log lines are best effort: a report
    they cannot read must not take the place of the run's own error."""
    monkeypatch.setenv("TFOS_TPU_SERVER_HOST", "127.0.0.1")

    def malformed(reports):
        raise KeyError("dur_ms")

    monkeypatch.setattr(trace, "summary_lines", malformed)
    c = cluster.run(backend.LocalBackend(1, workdir=str(tmp_path)),
                    fn_consume_then_fail, None, num_executors=1,
                    input_mode=cluster.InputMode.SPARK)
    c.train([_records()])
    with pytest.raises(Exception, match="injected failure after the feed"):
        c.shutdown(grace_secs=1)
    kinds = _by_kind(c.trace_report())
    (node_report,) = kinds["node"]
    gets = [s for s in node_report["spans"] if s["name"] == "feed.queue_get"
            and "item" in s["attrs"]]
    assert gets and gets[0]["attrs"]["got"] == "ring_ref"
    assert _spans(kinds["node"], "feed.resolve")


# --------------------------------- a ring smaller than one chunk (S1) ----

def test_a_chunk_larger_than_the_ring_is_cut_and_rides_the_ring(
        tmp_path, monkeypatch):
    """512 records of 16 KiB are an 8 MiB chunk; the least ring there is
    (`TFOS_TPU_RING_MB=1`: 64 slots of 64 KiB, 4 MiB) cannot hold it, so
    `_push_chunks` cuts it into row slices of 31 records that each fit a
    payload (an eighth of the ring) and every byte rides the ring.  This
    is the ResNet cell (77 MB chunks, 64 MiB ring) at toy size."""
    monkeypatch.setenv("TFOS_TPU_SERVER_HOST", "127.0.0.1")
    monkeypatch.setenv("TFOS_TPU_RING_MB", "1")
    wide = 4 * ROW
    report, consumed = _run(tmp_path, [_records(1024, wide)])
    assert consumed == 1024
    kinds = _by_kind(report)
    c = _counters(kinds["feeder"])
    puts = _spans(kinds["feeder"], "feed.queue_put")
    assert set(c) == {"feed.bytes.ring", "feed.items.ring",
                      "feed.chunk_splits"}
    assert c["feed.chunk_splits"] == 2
    assert c["feed.items.ring"] == len(puts) == 2 * 17
    # the records' bytes and the codec's few hundred a payload
    data = 1024 * wide * 4
    assert data < c["feed.bytes.ring"] < data + 512 * len(puts)
    assert max(s["attrs"]["bytes"] for s in puts) <= (4 << 20) // 8
    assert [s["attrs"]["route"] for s in puts] == ["ring_ref"] * len(puts)
    gets = [s for s in _spans(kinds["node"], "feed.queue_get")
            if "item" in s["attrs"]]
    assert [s["attrs"]["got"] for s in gets] == ["ring_ref"] * len(puts)
    resolved = _spans(kinds["node"], "feed.resolve")
    assert sum(s["attrs"]["bytes"] for s in resolved) == c["feed.bytes.ring"]
    # (in order, or `fn_consume` had failed the run)
    assert [s["attrs"]["rows"] for s in _spans(kinds["node"], "feed.take")
            if s["attrs"]["rows"]] == [64] * 16


def test_a_record_larger_than_the_ring_rides_the_queue_oversize(
        tmp_path, monkeypatch):
    """What cannot be cut: one record of 5 MiB against a ring of 4.  It
    crosses the manager's socket whole, the only thing that still may."""
    monkeypatch.setenv("TFOS_TPU_SERVER_HOST", "127.0.0.1")
    monkeypatch.setenv("TFOS_TPU_RING_MB", "1")
    wide = 1280 * ROW
    report, consumed = _run(tmp_path, [_records(3, wide)])
    assert consumed == 3
    kinds = _by_kind(report)
    assert _counters(kinds["feeder"]) == {
        "feed.bytes.queue_oversize": 3 * wide * 4,
        "feed.items.queue_oversize": 3, "feed.chunk_splits": 1}
    puts = _spans(kinds["feeder"], "feed.queue_put")
    assert [(s["attrs"]["route"], s["attrs"]["bytes"]) for s in puts] == \
        [("queue_oversize", wide * 4)] * 3
    gets = [s for s in _spans(kinds["node"], "feed.queue_get")
            if "item" in s["attrs"]]
    assert [s["attrs"]["got"] for s in gets] == ["packed"] * 3
    assert not _spans(kinds["node"], "feed.resolve")


# ------------------------------------------ the exporter failing (chaos) ----

def test_a_denied_trace_export_loses_spans_and_nothing_else(
        tmp_path, monkeypatch):
    """`faults.deny("trace.export")` in every process (the children are
    forks of this one, plan and all): every record is fed and consumed,
    the counters count, the reports arrive; only the spans are gone,
    and counted."""
    monkeypatch.setenv("TFOS_TPU_SERVER_HOST", "127.0.0.1")
    plan = faults.FaultPlan(seed=1).on("trace.export", kind="deny",
                                       nth=1, times=None)
    with faults.active(plan):
        report, consumed = _run(tmp_path, [_records()])
    assert consumed == RECORDS
    kinds = _by_kind(report)
    assert len(kinds["feeder"]) == 1 and len(kinds["node"]) == 1
    for r in kinds["feeder"] + kinds["node"]:
        assert r["spans"] == [] and r["recorded"] == 0 and r["dropped"] > 0
    assert _counters(kinds["feeder"])["feed.bytes.ring"] >= RECORDS * ROW * 4
