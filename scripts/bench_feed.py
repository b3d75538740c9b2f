"""Feed data-plane microbenchmark: manager-queue vs shared-memory ring.

Measures the InputMode.SPARK feed path end-to-end across a real process
boundary — producer process runs `node._push_chunks` (exactly what the
feeder task runs), consumer runs `feed.DataFeed.next_numpy_batch` — for
both transports, plus the raw ring bandwidth ceiling. The workload is
the round-1 baseline shape (MNIST-like rows: 784 f32 + 1 int64 label).

    python scripts/bench_feed.py [--rows-mb 256] [--raw-mb 2048] [--skip-queue]
"""
import argparse
import multiprocessing as mp
import os
import sys
import time
import uuid

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from tensorflowonspark_tpu import feed as feed_mod  # noqa: E402
from tensorflowonspark_tpu import manager as manager_mod  # noqa: E402
from tensorflowonspark_tpu import marker, shm  # noqa: E402
from tensorflowonspark_tpu import node as node_mod  # noqa: E402

ROW_BYTES = 784 * 4 + 8


def _make_rows(total_mb):
    n = (total_mb << 20) // ROW_BYTES
    img = np.random.default_rng(0).normal(size=(784,)).astype(np.float32)
    return [(img, i) for i in range(n)]


def _producer(rows, mgr_addr, authkey, use_ring):
    mgr = manager_mod.connect(mgr_addr, authkey)
    q = mgr.get_queue("input")
    node_mod._push_chunks(q, iter(rows), mgr=mgr if use_ring else None)
    q.put(None)


def bench_path(rows, use_ring):
    """Full path: producer process -> transport -> DataFeed batches."""
    authkey = uuid.uuid4().bytes
    mgr = manager_mod.start(authkey, ["input", "output", "error"])
    ring = None
    if use_ring:
        ring = shm.ShmChunkRing.create()
        mgr.set("shm_ring", ring.info())
    try:
        ctx = mp.get_context("fork")
        p = ctx.Process(target=_producer,
                        args=(rows, mgr._tfos_addr, authkey, use_ring))
        nbytes = len(rows) * ROW_BYTES
        t0 = time.perf_counter()
        p.start()
        df = feed_mod.DataFeed(mgr)
        seen = 0
        while not df.should_stop():
            batch = df.next_numpy_batch(4096, timeout=60)
            if batch is None:
                break
            seen += len(batch[1])
        dt = time.perf_counter() - t0
        p.join(30)
        assert seen == len(rows), (seen, len(rows))
        return nbytes / dt / (1 << 20)
    finally:
        if ring is not None:
            ring.close()
            ring.unlink()
        mgr.shutdown()


def _raw_producer(info, parts_spec, reps, done):
    ring = shm.ShmChunkRing.attach(info)
    payload = [np.zeros(parts_spec, dtype=np.uint8)]
    parts, n = shm.encode_chunk(marker.PackedChunk((payload[0],), None))
    q = done  # queue carries refs
    for _ in range(reps):
        q.put(ring.write(parts, n, timeout=60))
    q.put(None)


def bench_raw_ring(chunk_mb=4, total_mb=2048):
    """Transport ceiling: pre-encoded payloads, no packing/stacking."""
    ring = shm.ShmChunkRing.create()
    try:
        ctx = mp.get_context("fork")
        q = ctx.Queue()
        reps = max(1, total_mb // chunk_mb)
        p = ctx.Process(target=_raw_producer,
                        args=(ring.info(), chunk_mb << 20, reps, q))
        t0 = time.perf_counter()
        p.start()
        while True:
            ref = q.get(timeout=60)
            if ref is None:
                break
            ring.read(ref)
        dt = time.perf_counter() - t0
        p.join(30)
        return reps * chunk_mb / dt
    finally:
        ring.close()
        ring.unlink()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows-mb", type=int, default=256,
                    help="MB of row-shaped data for the full-path benches")
    ap.add_argument("--raw-mb", type=int, default=2048,
                    help="MB pushed through the raw-ring ceiling bench")
    ap.add_argument("--skip-queue", action="store_true",
                    help="skip the slow legacy-queue run")
    args = ap.parse_args()

    raw = bench_raw_ring(total_mb=args.raw_mb)
    print(f"raw ring transport:        {raw:9.1f} MB/s "
          f"(pre-encoded {4} MB payloads)")

    rows = _make_rows(args.rows_mb)
    ring_mbps = bench_path(rows, use_ring=True)
    print(f"feed path (shm ring):      {ring_mbps:9.1f} MB/s "
          f"({args.rows_mb} MB of 784-f32 rows, cross-process)")

    if not args.skip_queue:
        rows_q = _make_rows(min(args.rows_mb, 64))
        q_mbps = bench_path(rows_q, use_ring=False)
        print(f"feed path (manager queue): {q_mbps:9.1f} MB/s "
              f"(round-1 transport)")
        print(f"speedup ring vs queue:     {ring_mbps / q_mbps:9.1f}x")


if __name__ == "__main__":
    main()
