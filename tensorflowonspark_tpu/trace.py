"""Tracing: request-scoped spans for the serving stack, process-scoped
spans and counters for the feed plane.

**Serving.**  A trace ID is minted (or accepted via ``X-Trace-Id``) at the fleet
gateway, forwarded in the replica-bound body exactly like priority
classes (``fleet.py``), carried inside the batcher's pending item, and
— for the exotic hops — inside the wire-snapshot meta (migration,
park/unpark) and the journal replay meta, so one request keeps one ID
across every process that ever touches it.

Each process holds a :class:`Recorder`: a bounded ring of completed
spans stamped with the host monotonic clock.  Nothing here ever reads
a device value — decode-tick spans are recorded from the host drain
thread (``_host_loop``) at token-commit time, so the async engine
stays hostsync-clean.  The ring is a ``collections.deque(maxlen=...)``:
recording is O(1), old spans fall off the back, and a wedged or
fault-injected exporter can never apply backpressure to serving
(``faults.deny("trace.export")`` makes the recorder drop spans
silently — streams must stay byte-identical).

Span shape (JSON-ready)::

    {"trace": "4f2a…", "name": "prefill", "t0_ms": 12.3,
     "t1_ms": 14.9, "dur_ms": 2.6, "attrs": {"row": 3, "chunk": 256}}

``t0_ms``/``t1_ms`` are ``time.monotonic()`` milliseconds of the
recording process.  Every recorder also keeps one ANCHOR, the wall
clock and the monotonic clock read together at its creation
(``{"wall_ns": time.time_ns(), "mono_ns": time.monotonic_ns()}``), and
exports it with its spans: :func:`wall_ns` puts a span of any process
on the wall clock, so spans of different processes (and hosts, to
NTP's accuracy) line up on one axis.  The gateway's
``GET /v1/trace/<id>`` still stitches per-process timelines side by
side, tagged with their source.

**The feed plane** (feeder task, node, driver) has no request to hang a
span on, so it records on ONE recorder per process:
:func:`process` (made on first use; a forked child starts its own,
empty, with its own anchor), :func:`span` (a context manager: balanced
by construction), :func:`counters` (a ``metrics.Counters`` beside it)
and :func:`report` (all of it, JSON-ready).  It always records, bounded
by the ring: there is no switch.  When jax is loaded in the process a
span also enters ``jax.profiler.TraceAnnotation``, so under a profiler
session the node's spans lie on the device trace's clock (plane
``/host:CPU``, line ``python3``); this module itself never imports jax.
Reports of other processes that reach this one (``reservation``'s
``REPORT`` message brings the feeders' and the nodes' to the driver)
are kept whole beside its own spans, by source: :func:`collected`.

Span and counter names of the feed plane (``node.py``, ``feed.py``,
``cluster.py``; PERF.md names the metric that reads each)::

    cluster.train                                           (driver)
    feed.task  feed.connect  feed.source  feed.pack  feed.encode
    feed.ring_write  feed.queue_put  feed.join              (feeder)
    feed.take  feed.queue_get  feed.resolve  feed.stack  feed.h2d
    node.bootstrap  node.manager_start  node.ring_create
    node.register  node.rendezvous  node.init_distributed   (node)
    jaxpr_trace_duration  jaxpr_to_mlir_module_duration
    backend_compile_duration  ... (util.enable_compile_cache: what
    jax.monitoring reports under /jax/core/compile/ and
    /jax/compilation_cache/)
    counters: feed.bytes.<route>  feed.items.<route>  with <route> one
    of ring, queue, queue_oversize (a record larger than the ring);
    feed.ring_fallbacks  feed.chunk_splits (packed chunks cut by bytes
    to fit ring payloads)
    counters of a node that trains dropless expert layers
    (``models.transformer.moe_stats``, scalars in the step's metrics that
    ``parallel.train`` hands to :func:`count_when_ready`: no host callback
    in the step, no sync in the loop): moe.pairs.local  moe.pairs.absent
    (a token's picks that fell on experts held here, and on absent ones)
    moe.load.max  moe.load.mean (tokens of the fullest held expert and of
    the mean one)  moe.picks.moved  moe.picks.kept (picks that a
    selection bias took from, and left among, the k largest scores; all
    six summed over layers and steps); of one whose loss has a
    multi-token-prediction term (``models.transformer.next_token_losses``,
    carried the same way): loss.terms.next1  loss.terms.next2 (the two
    summands of the loss as they are added, the second after its weight,
    summed over steps)
    counters of a process that traces flash attention
    (``ops.flash_attention``, added on the host each time a kernel call is
    traced, so per compiled program and not per step):
    flash.subtiles.computed  flash.subtiles.masked  flash.subtiles.square
    (sub-tiles a head computes, those of them that carry the mask
    arithmetic, and those the padded square holds)
    flash.calls.packed  flash.calls.transposed  flash.calls.latent
    (kernel calls by layout; latent: the kernels with a query/key width
    unlike the value's and one rotary key a token);
    of one that traces a fused optimizer (``ops.fused_optim``, the same
    way, each time a leaf's kernel call is traced): adamw.elems.direct
    adamw.elems.packed (a leaf's elements, local to the shard under a
    mesh, by whether the kernel blocks the leaf's own layout or a packed
    ``[n, 128]`` copy of it; Lion's leaves count under the same names);
    and of one that traces a transformer block
    (``models.transformer``, the same way): mixer.calls.attention
    mixer.calls.conv  mixer.calls.latent (what a step program's sequence
    mixers are)  moe.shared.calls (its shared experts)

Scopes of the train step in the DEVICE trace (``jax.named_scope``: metadata
on the operations they cover, a component of each one's ``op_name`` beside
the flax modules' names; no operation, no switch; the persistent compile
cache keys on them, ``util.enable_compile_cache``).  The benchmark splits
the step's device time by these paths; after each scope the metric of
``BENCHMARK.json`` that reads it::

    route      moe_route_ms.*: top-k, weights, sort key, row positions,
               group sizes
    dispatch   moe_dispatch_ms.*: the row gather and its backward
    combine    moe_dispatch_ms.*: the weighted sum back, and its backward
    experts    moe_experts_outside_gmm_ms.*: the grouped matmuls with the
               activation between them (the kernels themselves taken off)
    cast       moe_experts_outside_gmm_ms.*: the held experts' weights to
               the compute type
        (these five in ``models.transformer.MoEMLP``, dropless, under
        ``layer_<n>/moe/``; with what stays filed at the layer they sum
        to moe_outside_gmm_ms.*)
    unembed_xent   head_ms.moe/.lfm/.joy: ``ops.xent.fused_unembed_xent``,
               forward and backward rule, the head's product fused into
               the loss
    lm_loss    no metric of its own, lowers device_unnamed_pct:
               ``models.transformer.lm_loss`` (both losses through
               :func:`loss_scope`)
    optimizer  optimizer_outside_kernel_ms: ``parallel.train``'s step, the
               optimizer's update and the gradients' global norm (the fused
               kernels, ``optimizer/adamw_fused``, are adamw_kernel_ms.*'s)
    stem       no metric of its own, lowers device_unnamed_pct.img:
               ``models.resnet.ResNet``, the stem's activation and max-pool

Lifecycle discipline: a span handed out by :meth:`Recorder.begin` must
reach exactly one of :meth:`Recorder.end` / :meth:`Recorder.abandon`
(the ``trace-span`` graftcheck ResourceSpec enforces this statically).
Sites that cannot scope a span inside one function use
:meth:`Recorder.span_at` with explicit endpoints instead — nothing
open ever escapes.
"""
import collections
import contextlib
import itertools
import os
import sys
import threading
import time
import uuid

from . import faults, metrics

# Hex digits plus dashes: accepts both uuid4().hex and W3C-style
# dashed trace ids from external callers.  Anything else is rejected
# at the door (gateway mints a fresh id; replica _validate 400s).
_ID_CHARS = frozenset("0123456789abcdefABCDEF-")
MAX_ID_LEN = 64

# Stage names recorded by the stack, for reference and docs:
#   gateway.route  gateway.relay  gateway.replay
#   queue  admit  prefill  decode  retire
#   freeze  wire  resume  replay  park  unpark
#   promote  prefix_pull
#   job.submit  job.partition  job.record  job.cancel  job.done
DEFAULT_RING = 4096
DEFAULT_DECODE_SAMPLE = 16
# the process recorder's ring: a node of a long job keeps its newest
# spans, some ten a batch; reports of other processes, the newest few
# hundred sources (a feeder sends one a task) and, over all of them,
# the newest MAX_REPORT_SPANS spans
PROCESS_RING = 16384
MAX_REPORTS = 256
MAX_REPORT_SPANS = 8 * PROCESS_RING


def new_id():
    """A fresh 32-hex-char trace id."""
    return uuid.uuid4().hex


def valid_id(tid):
    """True for a plausible externally-supplied trace id."""
    return (isinstance(tid, str) and 0 < len(tid) <= MAX_ID_LEN
            and not set(tid) - _ID_CHARS)


def _now_ms():
    return time.monotonic() * 1000.0


class Recorder:
    """Bounded per-process span ring.

    Every method tolerates ``trace_id=None`` (untraced request) by
    doing nothing and returning ``None`` — call sites never branch on
    whether tracing is on, which keeps the traced and untraced code
    paths literally the same instructions apart from dict stores.
    """

    def __init__(self, capacity=DEFAULT_RING,
                 decode_sample=DEFAULT_DECODE_SAMPLE):
        self.capacity = int(capacity) if capacity else DEFAULT_RING
        # every Nth committed host tick per traced row gets a decode
        # span; 0/None disables decode sampling entirely
        self.decode_sample = int(decode_sample or 0)
        self._lock = threading.Lock()
        self._ring = collections.deque(maxlen=self.capacity)
        self.recorded = 0       # spans accepted into the ring
        self.dropped = 0        # spans dropped by the export fault site
        # both clocks read together: what puts this recorder's spans on
        # the wall clock, beside those of other processes
        self.anchor = {"wall_ns": time.time_ns(),
                       "mono_ns": time.monotonic_ns()}

    # -- recording ----------------------------------------------------

    def begin(self, trace_id, name, **attrs):
        """Open a span; returns the span token (or None when
        untraced).  Must be balanced by end()/abandon()."""
        if not trace_id:
            return None
        return {"trace": trace_id, "name": name, "t0_ms": _now_ms(),
                "attrs": attrs}

    def end(self, span, **attrs):
        """Close and record a span from begin()."""
        if span is None:
            return
        span["t1_ms"] = _now_ms()
        if attrs:
            span["attrs"].update(attrs)
        self._push(span)

    def abandon(self, span):
        """Close a span whose operation failed; recorded with an
        ``abandoned`` marker so the timeline shows the cut."""
        if span is None:
            return
        span["attrs"]["abandoned"] = True
        span["t1_ms"] = _now_ms()
        self._push(span)

    def event(self, trace_id, name, **attrs):
        """A zero-duration span (point event)."""
        if not trace_id:
            return
        t = _now_ms()
        self._push({"trace": trace_id, "name": name, "t0_ms": t,
                    "t1_ms": t, "attrs": attrs})

    def span_at(self, trace_id, name, t0, t1, **attrs):
        """Record a completed span with explicit monotonic endpoints
        (seconds, as from ``time.monotonic()``) — for stages whose
        start was stamped in another function/thread."""
        if not trace_id:
            return
        self._push({"trace": trace_id, "name": name,
                    "t0_ms": t0 * 1000.0, "t1_ms": t1 * 1000.0,
                    "attrs": attrs})

    @contextlib.contextmanager
    def span(self, trace_id, name, **attrs):
        """Context manager for spans scoped to one block; failures
        inside the block record the span with ``abandoned`` set."""
        s = self.begin(trace_id, name, **attrs)
        try:
            yield s
        except BaseException:
            self.abandon(s)
            raise
        self.end(s)

    def _push(self, span):
        span["dur_ms"] = round(span["t1_ms"] - span["t0_ms"], 3)
        span["t0_ms"] = round(span["t0_ms"], 3)
        span["t1_ms"] = round(span["t1_ms"], 3)
        if faults.deny("trace.export"):
            # chaos site: the observability plane "failing" must cost
            # spans, never tokens — drop silently and count it
            with self._lock:
                self.dropped += 1
            return
        with self._lock:
            self._ring.append(span)
            self.recorded += 1

    # -- querying -----------------------------------------------------

    def spans(self, trace_id):
        """All retained spans for a trace id, oldest first."""
        with self._lock:
            return [dict(s) for s in self._ring
                    if s.get("trace") == trace_id]

    def summary(self, trace_id):
        """Compact per-request digest for the final stream event:
        span count and per-stage {count, total ms}."""
        found = self.spans(trace_id)
        if not found:
            return None
        stages = {}
        for s in found:
            st = stages.setdefault(s["name"], {"count": 0, "ms": 0.0})
            st["count"] += 1
            st["ms"] = round(st["ms"] + s["dur_ms"], 3)
        return {"id": trace_id, "spans": len(found), "stages": stages}

    def export(self, since=0):
        """What is retained of the spans recorded after the first
        `since`, oldest first, with the anchor that puts it on the wall
        clock.  ``recorded`` counts from the recorder's creation: larger
        than ``since + len(spans)`` means the ring's oldest fell off."""
        with self._lock:
            skip = max(0, len(self._ring) - max(0, self.recorded - since))
            return {"anchor": dict(self.anchor),
                    "spans": [dict(s) for s in
                              itertools.islice(self._ring, skip, None)],
                    "recorded": self.recorded, "dropped": self.dropped}

    def stats(self):
        with self._lock:
            return {"trace_spans_recorded": self.recorded,
                    "trace_spans_dropped": self.dropped,
                    "trace_ring_len": len(self._ring),
                    "trace_ring_capacity": self.capacity}


def wall_ns(anchor, t_ms):
    """A span's ``t0_ms``/``t1_ms`` on the wall clock (nanoseconds since
    the epoch), by the anchor its recorder exported with it."""
    return anchor["wall_ns"] + int(t_ms * 1e6) - anchor["mono_ns"]


# ------------------------------------------------ the process recorder ----

class _Process(Recorder):
    """This process's recorder: its own spans and counters, and the
    reports other processes sent here, whole and by source."""

    def __init__(self):
        super().__init__(capacity=PROCESS_RING)
        self.counters = metrics.Counters()
        # `count_when_ready`: values the device has not finished yet
        self._pending = collections.deque()
        self._ids = itertools.count(1)
        self._reports = collections.OrderedDict()
        # `recorded` at the last report that reached the driver: the next
        # one carries what came after (`report(since=...)`)
        self.sent = 0

    def record(self, name, t0_ms, t1_ms, cause, attrs, span_id=None):
        """Push one process-scoped span (`span`, `span_ended`)."""
        self._push({"id": next(self._ids) if span_id is None else span_id,
                    "cause": getattr(cause, "id", cause), "name": name,
                    "t0_ms": t0_ms, "t1_ms": t1_ms, "attrs": attrs})

    def drain(self, block):
        """Count what `count_when_ready` holds, oldest first: everything
        (`block`, waiting for the device) or as far as it is ready."""
        with self._lock:
            while self._pending and (block or all(
                    v.is_ready() for v in self._pending[0].values())):
                for name, v in self._pending.popleft().items():
                    n = float(v)
                    self.counters.inc(name, int(n) if n == int(n) else n)

    def add_report(self, report):
        """Keep a report of another process.  A later report of the same
        source and recorder (a reused feeder process reports after every
        task, each time what it recorded since the last) continues the
        earlier one: its spans follow, less any the earlier already
        holds, and its counters, which count from the process's start,
        take the earlier one's place.  Bounded: the newest
        `PROCESS_RING` spans a source, `MAX_REPORTS` sources and
        `MAX_REPORT_SPANS` spans over all (the oldest source goes
        first)."""
        source = str(report.get("source"))
        spans = list(report.get("spans") or ())
        with self._lock:
            old = self._reports.pop(source, None)
            if old is not None and old.get("anchor") == report.get("anchor") \
                    and report.get("recorded", 0) >= old.get("recorded", 0):
                first = report.get("recorded", 0) - len(spans)
                spans = old["spans"] + \
                    spans[max(0, old.get("recorded", 0) - first):]
            self._reports[source] = dict(report,
                                         spans=spans[-PROCESS_RING:])
            held = sum(len(r["spans"]) for r in self._reports.values())
            while len(self._reports) > 1 and (
                    len(self._reports) > MAX_REPORTS
                    or held > MAX_REPORT_SPANS):
                held -= len(self._reports.popitem(last=False)[1]["spans"])

    def reports(self):
        with self._lock:
            return list(self._reports.values())


_process = None
_process_lock = threading.Lock()


def _forget_process():
    # after fork, in the child: the parent's spans, counters and held
    # reports are not the child's to report, and its anchor is its own
    global _process, _process_lock
    _process = None
    _process_lock = threading.Lock()


os.register_at_fork(after_in_child=_forget_process)


def process():
    """This process's recorder, made on first use."""
    global _process
    rec = _process
    if rec is None:
        with _process_lock:
            if _process is None:
                _process = _Process()
            rec = _process
    return rec


def counters():
    """The ``metrics.Counters`` beside the process recorder."""
    return process().counters


class span:
    """``with trace.span(name, cause=parent, **attrs) as s:`` records one
    span on the process recorder: name, start, end, ``id``, the id of
    the span that caused it, ``attrs``.  ``s.id`` (or ``s`` itself) is
    what a child passes as ``cause``; ``s.set(**attrs)`` adds what is
    only known inside.  A block that raises is recorded with
    ``abandoned`` set."""

    __slots__ = ("name", "cause", "attrs", "id", "_t0", "_annotation")

    def __init__(self, name, cause=None, **attrs):
        self.name = name
        self.cause = getattr(cause, "id", cause)
        self.attrs = attrs
        self.id = None

    def set(self, **attrs):
        self.attrs.update(attrs)

    def __enter__(self):
        self.id = next(process()._ids)
        self._annotation = None
        jax = sys.modules.get("jax")
        if jax is not None:
            try:
                self._annotation = jax.profiler.TraceAnnotation(self.name)
                self._annotation.__enter__()
            except Exception:      # jax half imported, or no profiler
                self._annotation = None
        self._t0 = _now_ms()
        return self

    def __exit__(self, exc_type, exc, tb):
        t1 = _now_ms()
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
        if exc_type is not None:
            self.attrs["abandoned"] = True
        process().record(self.name, self._t0, t1, self.cause, self.attrs,
                         span_id=self.id)
        return False


def count_when_ready(values):
    """Add device scalars (``{counter: a jax.Array of one element}``, the
    outputs of a step that was just dispatched) to :func:`counters` once
    the device has them, without waiting: they are kept, and counted in
    order by the next call that finds them ready, or by :func:`report`
    (which waits: a report holds every step dispatched before it)."""
    rec = process()
    with rec._lock:
        rec._pending.append(values)
    rec.drain(block=False)


def span_ended(name, seconds, cause=None, **attrs):
    """Record, on the process recorder, a span that ends now and took
    `seconds`: for a duration something else measured and only tells
    afterwards (`jax.monitoring`'s compile events)."""
    t1 = _now_ms()
    process().record(name, t1 - seconds * 1e3, t1, cause, attrs)


@contextlib.contextmanager
def loss_scope(name):
    """`jax.named_scope(name)` for code that a differentiated function
    calls at its top, outside every module (a loss), as a context manager
    or a decorator.  JAX writes a transformation around the OUTERMOST
    scope of the path (`jvp(Transformer)/layer_0/...`), and there `name`
    would read `jvp(name)`, `transpose(jvp(name))`: one of JAX's own markers
    to whoever splits a path by its components.  So the scope is entered
    twice: the outer one takes the marker, the inner one stays the plain
    component `name` (`jvp(name)/name/...`)."""
    import jax

    with jax.named_scope(name), jax.named_scope(name):
        yield


def report(source=None, since=0):
    """This process's spans and counters, JSON-ready:
    ``{"source", "anchor", "spans", "counters", "recorded", "dropped"}``;
    with `since`, only the spans recorded after the first `since` (the
    counters always count from the process's start)."""
    rec = process()
    rec.drain(block=True)
    out = rec.export(since)
    out["source"] = source or f"pid:{os.getpid()}"
    out["counters"] = rec.counters.snapshot()
    return out


def collected(source="driver"):
    """This process's own report (under ``source``) and, after it, every
    report that other processes sent here."""
    return [report(source)] + process().reports()


def by_span(reports):
    """``{name: [count, seconds]}`` over the spans of some reports."""
    out = {}
    for r in reports:
        for s in r.get("spans", ()):
            row = out.setdefault(s["name"], [0, 0.0])
            row[0] += 1
            row[1] += s["dur_ms"] / 1e3
    return out


def summary_lines(reports):
    """Five lines for an operator's log: what was collected, the feed's
    bytes by route, and the feeders', the nodes' and the driver's time by
    span (count and seconds, longest first)."""
    groups = {"feeder": [], "node": [], "driver": []}
    for r in reports:
        kind = str(r.get("source", "")).split(":")[0]
        groups.setdefault(kind, []).append(r)
    counts = {}
    for r in reports:
        for k, v in (r.get("counters") or {}).items():
            counts[k] = counts.get(k, 0) + v

    def spans_of(kind):
        rows = sorted(by_span(groups[kind]).items(), key=lambda kv: -kv[1][1])
        return " ".join(f"{n}={c}x{t:.3f}s" for n, (c, t) in rows) or "-"

    lines = ["trace: " + " ".join(
        f"{k}={len(v)}" for k, v in groups.items()) + " reports, "
        f"{sum(r.get('recorded', 0) for r in reports)} spans recorded, "
        f"{sum(r.get('dropped', 0) for r in reports)} dropped",
        "trace: feed " + (" ".join(
            f"{k[5:]}={v}" for k, v in sorted(counts.items())
            if k.startswith("feed.")) or "-")]
    lines += [f"trace: {kind} {spans_of(kind)}"
              for kind in ("feeder", "node", "driver")]
    return lines
