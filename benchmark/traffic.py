"""The one traffic generator: seeded records for a fed training cell.

A traffic mix is a data file (`benchmark/traffic/<name>.json`): the record's
fields (dtype, shape, value range), the batch the step takes, the size of the
pool of distinct records, how many records a partition (one feeder task)
holds, and the most records a second the feed is offered.  Everything here
is numpy only: the driver process that calls it never imports JAX.

Record `i` of seed `s` is a pure function of `(s, i)`, so the node can make
the first few batches again for the comparison without the pool.  Every seed
gives records of the same sizes, in the same number, in another order.
"""
import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load(kind, name):
    """`benchmark/<kind>/<name>.json` as a dict."""
    with open(os.path.join(HERE, kind, name + ".json")) as f:
        return json.load(f)


def fields(traffic, config):
    """The record's fields with `{"config": key}` bounds resolved."""
    out = []
    for f in traffic["record"]:
        high = f["high"]
        if isinstance(high, dict):
            high = config[high["config"]]
        out.append((np.dtype(f["dtype"]), tuple(f["shape"]), int(f["low"]),
                    int(high)))
    return out


def make_record(spec, seed, index):
    """Record `index` of `seed`: one array, or a tuple of one per field."""
    rng = np.random.default_rng([int(seed), int(index)])
    vals = tuple(
        rng.integers(low, high, size=shape, dtype=dtype) if shape
        else dtype.type(rng.integers(low, high))
        for dtype, shape, low, high in spec)
    return vals[0] if len(vals) == 1 else vals


def order(seed, pool, total):
    """Pool indices of the first `total` records fed: whole seeded
    permutations of the pool, one after another."""
    out = []
    epoch = 0
    while sum(len(o) for o in out) < total:
        out.append(np.random.default_rng(
            [int(seed), 1 << 40, epoch]).permutation(pool))
        epoch += 1
    return np.concatenate(out)[:total]


def total_records(traffic, seconds):
    """More than the window can consume: the offered rate over the window
    and a margin, plus every step outside it."""
    b = traffic["batch"]
    outside = (traffic["check_steps"] + traffic["warm_steps"]
               + traffic["trace_steps"] + 6) * b
    n = outside + int(traffic["feed_records_per_s"] * (seconds + 5))
    per = traffic["records_per_partition"]
    return -(-n // per) * per


def partitions(traffic, config, seed, seconds):
    """The partitions `c.train` feeds: lists that hold the pool's records by
    reference, so host memory is the pool's."""
    spec = fields(traffic, config)
    pool = [make_record(spec, seed, i) for i in range(traffic["pool"])]
    idx = order(seed, traffic["pool"], total_records(traffic, seconds))
    per = traffic["records_per_partition"]
    return [[pool[j] for j in idx[k:k + per]]
            for k in range(0, len(idx), per)]


def first_batches(traffic, config, seed, steps):
    """The first `steps` batches as the seed makes them, stacked as
    `DataFeed.next_numpy_batch` stacks them (one array, or one per field)."""
    spec = fields(traffic, config)
    b = traffic["batch"]
    idx = order(seed, traffic["pool"], steps * b)
    out = []
    for s in range(steps):
        recs = [make_record(spec, seed, j) for j in idx[s * b:(s + 1) * b]]
        if len(spec) == 1:
            out.append(np.stack(recs))
        else:
            out.append(tuple(np.stack([r[i] for r in recs])
                             for i in range(len(spec))))
    return out


def record_bytes(traffic, config):
    return sum(int(np.prod(shape, dtype=np.int64)) * dtype.itemsize
               for dtype, shape, _, _ in fields(traffic, config))
