"""Milliseconds from a data item's `feed.queue_put` ending in the feeder
to its `feed.queue_get` ending in the node (`benchmark/spans.py` pairs the
k-th put with the k-th got): the median over the items got inside the
measured window.  Where the node waits for the feeder this is what the
item's second crossing of the manager's socket takes; where the feeder is
ahead, how long the item lay in the queue.  Nothing where the pairing
cannot be made."""
import spans as spans_mod


def read(run):
    loaded = spans_mod.load(run)
    if loaded is None:
        return None
    return compute(loaded)


def compute(loaded):
    pairs = spans_mod.pairs(loaded)
    if pairs is None:
        return None
    t0, t1 = loaded["window"]
    return spans_mod.median((g["t1"] - p["t1"]) * 1e3 for p, g in pairs
                            if t0 <= g["t1"] <= t1)
