"""Shared-memory data plane: ring protocol, codec, and cross-process use.

Mirrors the reference's queue-feed tests (reference: tests/test_TFNode.py
DataFeed semantics) at the transport layer below them: payload bytes ride
/dev/shm, refs ride the queue.
"""
import multiprocessing as mp
import queue
import time
import types

import numpy as np
import pytest

from tensorflowonspark_tpu import marker, shm


@pytest.fixture
def ring():
    r = shm.ShmChunkRing.create(slot_bytes=1 << 16, nslots=4)
    yield r
    r.close()
    r.unlink()


def _roundtrip(ring, chunk):
    parts, n = shm.encode_chunk(chunk)
    ref = ring.write(parts, n, timeout=5)
    return ring.read(ref)


class TestCodec:
    def test_packed_field_records(self, ring):
        rows = [(np.arange(6, dtype=np.float32) + i, i) for i in range(10)]
        packed = marker.pack_records(rows)
        assert isinstance(packed, marker.PackedChunk)
        out = _roundtrip(ring, packed)
        assert isinstance(out, marker.PackedChunk)
        np.testing.assert_array_equal(out.columns[0], packed.columns[0])
        np.testing.assert_array_equal(out.columns[1], packed.columns[1])
        assert out.row_type is tuple and not out.matrix

    def test_packed_matrix_records(self, ring):
        rows = [tuple(float(i + j) for j in range(24)) for i in range(8)]
        packed = marker.pack_records(rows)
        assert packed.matrix
        out = _roundtrip(ring, packed)
        assert out.matrix and out.row_type is tuple
        np.testing.assert_array_equal(out.columns[0], packed.columns[0])

    def test_scalar_records_keep_python_types(self, ring):
        packed = marker.pack_records([1, 2, 3])
        out = _roundtrip(ring, packed)
        assert out.row_type is int

    def test_object_chunk_rides_pickle_blob(self, ring):
        items = [{"a": i, "b": "x" * i} for i in range(5)]
        out = _roundtrip(ring, marker.Chunk(items))
        assert out == items

    def test_non_contiguous_columns(self, ring):
        big = np.arange(64, dtype=np.float32).reshape(8, 8)
        packed = marker.PackedChunk((big[:, ::2],), None)  # strided view
        out = _roundtrip(ring, packed)
        np.testing.assert_array_equal(out.columns[0], big[:, ::2])


class TestRingProtocol:
    def test_multi_frame_payload(self, ring):
        # 3 * slot_bytes payload spans multiple frames and reassembles
        arr = np.random.default_rng(0).integers(
            0, 255, size=3 * (1 << 16), dtype=np.uint8)
        out = _roundtrip(ring, marker.PackedChunk((arr,), None))
        np.testing.assert_array_equal(out.columns[0], arr)

    def test_wraparound_many_writes(self, ring):
        rng = np.random.default_rng(1)
        for i in range(50):  # >> nslots: exercises wrap + free accounting
            arr = rng.normal(size=rng.integers(1, 4000)).astype(np.float32)
            out = _roundtrip(ring, marker.PackedChunk((arr,), None))
            np.testing.assert_array_equal(out.columns[0], arr)

    def test_oversized_payload_rejected(self, ring):
        arr = np.zeros(5 * (1 << 16), dtype=np.uint8)  # > nslots * slot
        parts, n = shm.encode_chunk(marker.PackedChunk((arr,), None))
        with pytest.raises(ValueError, match="frames"):
            ring.write(parts, n, timeout=1)

    def test_full_ring_times_out_without_consumer(self, ring):
        arr = np.zeros(1 << 15, dtype=np.uint8)
        parts, n = shm.encode_chunk(marker.PackedChunk((arr,), None))
        for _ in range(4):
            ring.write(parts, n, timeout=1)
        with pytest.raises(shm.RingTimeout):
            ring.write(parts, n, timeout=0.3)

    def test_timed_out_write_preserves_unread_payloads(self, ring):
        # A write that times out waiting for a FULL slot (ring wrapped,
        # consumer slow) must repair ONLY its own frames: the occupied
        # slots still hold unread payloads a retrying feeder must not
        # overwrite (round-3 partial-write repair).
        rng = np.random.RandomState(0)
        arrs = [rng.randint(0, 255, 1 << 15).astype(np.uint8)
                for _ in range(4)]
        refs = []
        for a in arrs:
            parts, n = shm.encode_chunk(marker.PackedChunk((a,), None))
            refs.append(ring.write(parts, n, timeout=1))
        big = rng.randint(0, 255, 2 * (1 << 15)).astype(np.uint8)
        parts, n = shm.encode_chunk(marker.PackedChunk((big,), None))
        with pytest.raises(shm.RingTimeout):
            ring.write(parts, n, timeout=0.3)   # acquires nothing
        # every earlier payload survives intact
        for a, ref in zip(arrs, refs):
            out = ring.read(ref)
            np.testing.assert_array_equal(out.columns[0], a)
        # and the ring is not wedged: the failed write now fits
        ref = ring.write(parts, n, timeout=1)
        np.testing.assert_array_equal(ring.read(ref).columns[0], big)

    def test_skip_frees_frames(self, ring):
        arr = np.zeros(1 << 15, dtype=np.uint8)
        parts, n = shm.encode_chunk(marker.PackedChunk((arr,), None))
        refs = [ring.write(parts, n, timeout=1) for _ in range(4)]
        for ref in refs:
            ring.skip(ref)
        ring.write(parts, n, timeout=1)  # space is back

    def test_sequence_survives_reattach(self, ring):
        # successive feeder tasks attach fresh; seq continues, not resets
        parts, n = shm.encode_chunk(marker.pack_records([1, 2, 3]))
        ref1 = ring.write(parts, n, timeout=1)
        other = shm.ShmChunkRing.attach(ring.info())
        ref2 = other.write(parts, n, timeout=1)
        assert ref2.seq == ref1.seq + ref1.nframes
        assert len(ring.read(ref1)) == 3 and len(ring.read(ref2)) == 3
        other.close()


def _producer_proc(info, count, q):
    ring = shm.ShmChunkRing.attach(info)
    for i in range(count):
        rows = [(np.full(256, i, dtype=np.float32), i * 10 + j)
                for j in range(64)]
        parts, n = shm.encode_chunk(marker.pack_records(rows))
        q.put(ring.write(parts, n, timeout=30))
    q.put(None)
    ring.close()


class TestCrossProcess:
    def test_producer_process_feeds_consumer(self):
        ring = shm.ShmChunkRing.create(slot_bytes=1 << 15, nslots=4)
        try:
            ctx = mp.get_context("fork")
            q = ctx.Queue()
            p = ctx.Process(target=_producer_proc, args=(ring.info(), 12, q))
            p.start()
            got = 0
            while True:
                ref = q.get(timeout=30)
                if ref is None:
                    break
                chunk = ring.read(ref)
                assert isinstance(chunk, marker.PackedChunk)
                np.testing.assert_array_equal(
                    chunk.columns[0][0], np.full(256, got, dtype=np.float32))
                assert list(chunk.columns[1][:3]) == \
                    [got * 10, got * 10 + 1, got * 10 + 2]
                got += 1
            p.join(30)
            assert p.exitcode == 0 and got == 12
        finally:
            ring.close()
            ring.unlink()

    def test_attacher_exit_does_not_unlink(self):
        # a feeder task exiting must not let its resource tracker destroy
        # the segment (the 3.12 attach-registration hazard)
        ring = shm.ShmChunkRing.create(slot_bytes=1 << 14, nslots=2)
        try:
            ctx = mp.get_context("spawn")  # spawn: own resource tracker
            p = ctx.Process(target=_attach_and_exit, args=(ring.info(),))
            p.start()
            p.join(60)
            assert p.exitcode == 0
            time.sleep(0.5)  # give the child's tracker time to misbehave
            again = shm.ShmChunkRing.attach(ring.info())  # must still exist
            again.close()
        finally:
            ring.close()
            ring.unlink()


def _attach_and_exit(info):
    r = shm.ShmChunkRing.attach(info)
    r.close()


class TestFeedIntegration:
    def test_push_chunks_through_ring_to_datafeed(self, tmp_path):
        """The full producer->consumer path: node._push_chunks with a ring
        advertised in the manager kv, consumed by DataFeed."""
        import uuid as uuid_mod

        from tensorflowonspark_tpu import feed as feed_mod
        from tensorflowonspark_tpu import manager as manager_mod
        from tensorflowonspark_tpu import node as node_mod

        authkey = uuid_mod.uuid4().bytes
        mgr = manager_mod.start(authkey, ["input", "output", "error"])
        ring = shm.ShmChunkRing.create(slot_bytes=1 << 16, nslots=4)
        try:
            mgr.set("shm_ring", ring.info())
            q = mgr.get_queue("input")
            rows = [(np.arange(8, dtype=np.float32) * i, i)
                    for i in range(1000)]
            count = node_mod._push_chunks(q, iter(rows), mgr=mgr)
            assert count == 1000
            q.put(None)

            df = feed_mod.DataFeed(mgr)
            seen = 0
            while not df.should_stop():
                batch = df.next_numpy_batch(256, timeout=5)
                if batch is None:
                    break
                xs, ys = batch
                for k in range(len(ys)):
                    i = int(ys[k])
                    np.testing.assert_array_equal(
                        xs[k], np.arange(8, dtype=np.float32) * i)
                seen += len(ys)
            assert seen == 1000
            q.join()  # all refs task_done'd: feeder join() would return
        finally:
            ring.close()
            ring.unlink()
            mgr.shutdown()

    def test_terminate_drains_ring_refs(self):
        import uuid as uuid_mod

        from tensorflowonspark_tpu import feed as feed_mod
        from tensorflowonspark_tpu import manager as manager_mod
        from tensorflowonspark_tpu import node as node_mod

        authkey = uuid_mod.uuid4().bytes
        mgr = manager_mod.start(authkey, ["input", "output", "error"])
        ring = shm.ShmChunkRing.create(slot_bytes=1 << 18, nslots=8)
        try:
            mgr.set("shm_ring", ring.info())
            q = mgr.get_queue("input")
            rows = [(np.zeros(512, dtype=np.float32), i) for i in range(600)]
            node_mod._push_chunks(q, iter(rows), mgr=mgr)
            first = q.get()
            assert isinstance(first, shm.ShmRef)    # rode the ring...
            ring.skip(first)                        # (consume one by hand)
            q.task_done()
            df = feed_mod.DataFeed(mgr)
            df.terminate()                          # ...the rest drain here
            assert manager_mod.get_value(mgr, "state") == "terminating"
            # ring fully freed afterwards: a near-capacity write succeeds
            parts, n = shm.encode_chunk(marker.pack_records(
                [np.zeros((7 << 18) // 4, dtype=np.float32)]))
            ring.write(parts, n, timeout=1)
        finally:
            ring.close()
            ring.unlink()
            mgr.shutdown()


# ---- ring payloads are cut by bytes (node._push_chunks, no manager process)

class _FakeQueue(queue.Queue):
    """The input queue without a manager process; keeps what was put."""

    def __init__(self):
        super().__init__()
        self.puts = []

    def put(self, item, *args, **kwargs):
        self.puts.append(item)
        super().put(item, *args, **kwargs)


class _FakeManager:
    """What `_push_chunks` and `DataFeed` ask of the queue manager."""

    def __init__(self, ring):
        self.kv = {"shm_ring": ring.info()}
        self.q = _FakeQueue()

    def get_queue(self, name):
        assert name == "input"
        return self.q

    def get(self, key):
        value = self.kv.get(key)
        return None if value is None else types.SimpleNamespace(
            _getvalue=lambda: value)

    def set(self, key, value):
        self.kv[key] = value


def _push(ring, records, batch=None, **kwargs):
    """`node._push_chunks(records)` in a thread (a ring smaller than the
    records blocks it until the consumer frees frames), then the end of
    the feed.  Returns what it returned, the items it put, its
    `feed.queue_put` spans' (route, bytes), what its counters counted
    and, with ``batch``, the numpy batches a `DataFeed` took meanwhile."""
    import threading

    from tensorflowonspark_tpu import feed as feed_mod
    from tensorflowonspark_tpu import node as node_mod
    from tensorflowonspark_tpu import trace

    mgr = _FakeManager(ring)
    before = dict(trace.counters().snapshot())
    out = {}

    def feeder():
        with trace.span("test.push") as task:
            out["task"] = task.id
            try:
                out["count"] = node_mod._push_chunks(
                    mgr.q, iter(records), mgr=mgr, timeout=60, cause=task,
                    **kwargs)
            finally:
                mgr.q.put(None)

    t = threading.Thread(target=feeder, daemon=True)
    t.start()
    batches = []
    if batch:
        df = feed_mod.DataFeed(mgr)
        while not df.should_stop():
            got = df.next_numpy_batch(batch, timeout=30)
            if got is None:
                break
            batches.append(got)
    t.join(60)
    assert not t.is_alive() and "count" in out
    after = trace.counters().snapshot()
    counted = {k: v - before.get(k, 0) for k, v in after.items()
               if k.startswith("feed.") and v != before.get(k, 0)}
    puts = [(s["attrs"]["route"], s["attrs"]["bytes"])
            for s in trace.report()["spans"]
            if s["name"] == "feed.queue_put" and s["cause"] == out["task"]]
    return out["count"], mgr.q.puts[:-1], puts, counted, batches


@pytest.fixture
def small_ring():
    """Eight slots of 64 KiB: a payload may hold 65024 bytes, a record
    that rides the ring 520192."""
    r = shm.ShmChunkRing.create(slot_bytes=1 << 16, nslots=8)
    yield r
    r.close()
    r.unlink()


class TestPayloadsCutByBytes:
    def test_image_records_cross_slice_and_batch_boundaries(self, small_ring):
        """(wide uint8 array, int32 label) records, as the ResNet cell's:
        512 of them are 1.5 MB against a payload of 65024 bytes, so each
        packed chunk goes as slices of 21 records; batches of 100 line up
        with neither.  Field by field what was put in, in order."""
        rng = np.random.default_rng(7)
        records = [(rng.integers(0, 256, 3000, dtype=np.uint8), np.int32(i))
                   for i in range(700)]
        count, items, puts, counted, batches = _push(
            small_ring, records, batch=100)
        assert count == 700 == sum(len(ref) for ref in items)
        assert [len(b[1]) for b in batches] == [100] * 7
        xs = np.concatenate([b[0] for b in batches])
        ys = np.concatenate([b[1] for b in batches])
        assert xs.dtype == np.uint8 and ys.dtype == np.int32
        np.testing.assert_array_equal(xs, np.stack([r[0] for r in records]))
        np.testing.assert_array_equal(ys, np.arange(700, dtype=np.int32))
        # every item a ref, no payload over an eighth of the ring
        assert all(isinstance(ref, shm.ShmRef) for ref in items)
        assert max(ref.nbytes for ref in items) <= \
            small_ring.capacity_bytes // 8
        assert {route for route, _ in puts} == {"ring_ref"}
        assert [len(ref) for ref in items[:25]] == [21] * 24 + [8]
        assert counted["feed.chunk_splits"] == 2
        assert counted["feed.items.ring"] == len(items) == len(puts)
        assert set(counted) == {"feed.chunk_splits", "feed.items.ring",
                                "feed.bytes.ring"}

    @pytest.mark.parametrize("values,route", [
        (160 * 1024, "queue_oversize"),     # 640 KiB: the ring holds 512
        (25 * 1024, "ring_ref"),            # 100 KiB: over a payload's room
    ], ids=["larger_than_the_ring", "larger_than_a_payload"])
    def test_a_record_that_cannot_be_cut_goes_alone(self, small_ring,
                                                    values, route):
        """A slice is never under one record.  One larger than a payload's
        room still rides the ring, a payload of its own; only one larger
        than the ring itself rides the queue, whole."""
        records = [np.full(values, i, np.float32) for i in range(3)]
        count, items, puts, counted, batches = _push(
            small_ring, records, batch=2)
        assert count == 3
        nbytes = values * 4 if route == "queue_oversize" else items[0].nbytes
        assert puts == [(route, nbytes)] * 3
        assert values * 4 <= nbytes < values * 4 + 512
        kind = marker.PackedChunk if route == "queue_oversize" else shm.ShmRef
        assert [type(i) for i in items] == [kind] * 3
        assert all(len(i) == 1 for i in items)
        assert counted["feed.chunk_splits"] == 1
        assert [len(b) for b in batches] == [2, 1]
        np.testing.assert_array_equal(np.concatenate(batches),
                                      np.stack(records))

    def test_a_progress_marker_follows_every_slice_it_claims(self):
        """With `progress_fn` a chunk is 100 records here, 1.6 MB, cut in
        slices of 31: a marker is put only when the records before it,
        down to the last slice, are in the queue."""
        ring = shm.ShmChunkRing.create(slot_bytes=1 << 16, nslots=64)
        try:
            records = [np.full(4096, i, np.float32) for i in range(230)]
            count, items, puts, counted, _ = _push(
                ring, records, progress_every=100,
                progress_fn=lambda n: marker.Progress(7, n))
        finally:
            ring.close()
            ring.unlink()
        assert count == 230
        marks, seen = [], 0
        for item in items:
            if isinstance(item, marker.Progress):
                assert item.offset == seen
                marks.append(item.offset)
            else:
                seen += len(item)
        assert marks == [100, 200, 230] and seen == 230
        assert [len(i) for i in items if isinstance(i, shm.ShmRef)] == \
            [31, 31, 31, 7] * 2 + [30]
        assert counted["feed.chunk_splits"] == 2    # the 30 fit one payload

    @pytest.mark.parametrize("records,expected", [
        # the GPT-2 cell's rows: three 2.1 MB chunks a payload
        ([np.full(1025, i, np.int32) for i in range(2200)],
         [("ring_ref", 6297861, 1536), ("ring_ref", 2722586, 664)]),
        # 5.1 MB chunks: two of them would cross 8 MiB less 64 KiB, the
        # second and the short third do not
        ([np.full(2500, i, np.float32) for i in range(1100)],
         [("ring_ref", 5120069, 512), ("ring_ref", 5880186, 588)]),
        # python scalars: 64 sub-chunks a payload, however small
        (list(range(40000)),
         [("ring_ref", 266599, 32768), ("ring_ref", 58929, 7232)]),
    ], ids=["lm_rows", "wide_rows", "scalars"])
    def test_chunks_under_the_budget_are_put_as_before(self, records,
                                                       expected):
        """At the default ring a chunk under 8 MiB is never cut: the same
        puts, route, bytes and records each, as before payloads were
        bounded by the ring (the numbers are the parent commit's)."""
        ring = shm.ShmChunkRing.create()
        try:
            assert ring.capacity_bytes == 64 << 20
            count, items, puts, counted, _ = _push(ring, records)
        finally:
            ring.close()
            ring.unlink()
        assert count == len(records)
        assert [(route, nbytes, len(item)) for (route, nbytes), item
                in zip(puts, items)] == expected
        assert "feed.chunk_splits" not in counted
