"""What the program's own spans and counters say about the measured window.

The program records spans and counters in each of its processes
(`tensorflowonspark_tpu/trace.py`) and brings the feeders' and the nodes'
reports to the driver process over the reservation channel.  The driver
process of a run is `run.py`'s own, so the readers under `metrics/` find
everything in `trace.collected()`: a list of reports
`{"source", "anchor", "spans", "counters", "recorded", "dropped"}`, the
driver's own first.  A span's `t0_ms`/`t1_ms` are on its process's
monotonic clock; the report's `anchor` (both clocks read together) puts
them on the wall clock, where the node took `t_window`.

A program without this (the parent of the PR that brought it) has no
`trace.collected`: `load` then returns None, and so does every reader.
The same where a report is missing or its ring lost spans of the window.
"""
import statistics

TOLERANCE_S = 1e-3      # two processes' anchors, read on one host
KINDS = ("driver", "bootstrap", "feeder", "node")   # a source's first part


def load(run, collected=None):
    """`{"window": (t0, t1), "driver": [...], "feeder": [...], "node":
    [...], "bootstrap": [...]}` (reports by the kind of process that sent
    them) with every span's `t0`/`t1` in wall-clock seconds, or None."""
    if collected is None:
        try:
            from tensorflowonspark_tpu import trace
        except ImportError:
            return None
        if not hasattr(trace, "collected"):
            return None
        collected = trace.collected()
    result = run["result"]
    if "t_window" not in result:
        return None
    t0 = float(result["t_window"])
    out = {kind: [] for kind in KINDS}
    out["window"] = (t0, t0 + float(result["window"]["seconds"]))
    for rep in collected:
        kind = str(rep.get("source", "")).split(":")[0]
        if kind not in KINDS:
            continue
        off = rep["anchor"]["wall_ns"] / 1e9 - rep["anchor"]["mono_ns"] / 1e9
        spans = [dict(s, t0=s["t0_ms"] / 1e3 + off, t1=s["t1_ms"] / 1e3 + off)
                 for s in rep["spans"]]
        out[kind].append(dict(rep, spans=spans))
    return out


def whole(reports, window):
    """True when `reports` (of one kind) exist and none lost a span of the
    window: nothing dropped, and a ring that wrapped (its oldest spans
    fell off) still starts before the window does."""
    if not reports:
        return False
    for rep in reports:
        if rep.get("dropped"):
            return False
        if rep.get("recorded", 0) > len(rep["spans"]):
            if not rep["spans"] or not min(
                    s["t0"] for s in rep["spans"]) <= window[0]:
                return False
    return True


def named(reports, names):
    return [s for rep in reports for s in rep["spans"] if s["name"] in names]


def union_s(spans, window):
    """Seconds of the window covered by the union of `spans`."""
    total, end = 0.0, window[0]
    for s in sorted(spans, key=lambda s: s["t0"]):
        a, b = max(s["t0"], end), min(s["t1"], window[1])
        if b > a:
            total += b - a
            end = b
    return total


def tasks_overlap(feeders):
    """True when two `feed.task` spans overlap: two feeders at a time, and
    the k-th item put is no longer the k-th got."""
    tasks = sorted(named(feeders, ("feed.task",)), key=lambda s: s["t0"])
    return any(b["t0"] < a["t1"] - TOLERANCE_S
               for a, b in zip(tasks, tasks[1:]))


def pairs(loaded):
    """`[(put, get), ...]` of the data items, the k-th put with the k-th
    got (the input queue is FIFO and one feeder runs at a time), or None
    where that cannot be said: overlapping feeder tasks, a node's ordinals
    that do not count up from 0 (a ring that wrapped), unequal counts, or
    a get that ends before its put has started.  (A get may END a little
    before its put does: the manager hands the item to a waiting `get`
    before it answers the `put`.)"""
    feeders, nodes = loaded["feeder"], loaded["node"]
    if not feeders or len(nodes) != 1 or tasks_overlap(feeders):
        return None
    if any(r.get("dropped") or r.get("recorded", 0) > len(r["spans"])
           for r in feeders + nodes):
        return None
    puts = sorted(named(feeders, ("feed.queue_put",)), key=lambda s: s["t1"])
    gets = [s for s in named(nodes, ("feed.queue_get",))
            if "item" in s["attrs"]]
    gets.sort(key=lambda s: s["attrs"]["item"])
    if [s["attrs"]["item"] for s in gets] != list(range(len(gets))):
        return None
    if len(puts) != len(gets) or not puts:
        return None
    if any(g["t1"] < p["t0"] - TOLERANCE_S for p, g in zip(puts, gets)):
        return None
    return list(zip(puts, gets))


def median(values):
    values = list(values)
    return statistics.median(values) if values else None
