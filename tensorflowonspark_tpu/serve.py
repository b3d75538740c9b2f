"""Online inference server over an exported model.

Complements the batch CLI (`tensorflowonspark_tpu.inference`, the
Inference.scala analog) with a long-lived HTTP endpoint — the online half
of the serving story the reference delegated to external TF Serving.
Stdlib-only (http.server), TF-Serving-compatible request shape:

    python -m tensorflowonspark_tpu.serve --export_dir /models/m --port 8501

    POST /v1/models/default:predict   {"instances": [{"x": [...]}, ...]}
        -> {"predictions": [{"y": [...]}, ...]}
    GET  /v1/models/default           -> model/engine metadata + health

Engine selection mirrors the batch CLI: the AOT artifact (native PJRT
runner where available) when the export carries one, else the rebuilt
jitted model.  :predict requests batch within themselves (a lock
serializes device executions; ``--batch_wait_ms`` coalesces concurrent
requests instead).  :generate requests all run through the
continuous-batching slot engine (GenerateService/ContinuousBatcher):
concurrent generations share the in-flight batch at token boundaries —
no request-level serialization.

The engine composes (docs/source/serving.rst for each): paged kv with
prefix caching (``--generate_kv_page_size``/``--generate_kv_pages``),
lossless speculative decoding (``--spec_draft``/``--draft_export_dir``:
model-based or n-gram drafting, rejection-sampled verification for
sampled rows, adaptive draft length), weight-only int8
(``--generate_quantize``), an int8 kv cache (``--generate_kv_dtype``),
multi-adapter LoRA (``--generate_lora_rank``/``--generate_lora``), and
per-request sampling controls (``top_k``/``top_p``/``min_p``/
``repetition_penalty``/``stop``) that reproduce solo library calls
token-for-token via one shared implementation.
"""
import argparse
import collections
from typing import Any
import json
import logging
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

from . import faults, trace

logger = logging.getLogger(__name__)

# Scheduling priority classes, lowest index = highest priority.  The
# gateway resolves a request's class (X-Priority header or its
# tenant->class map) and forwards it in the body; direct clients may
# set either.  Everything else in the scheduler keys off these names.
PRIORITY_CLASSES = ("interactive", "batch")

# Weight-only quantization modes for the :generate LM — the ONE source
# of truth shared by the --generate_quantize argparse choices and
# GenerateService._load_lm's validation (they drifted once; int4 landed
# in both through this constant).
QUANTIZE_MODES = ("none", "int8", "int4")


def build_argparser():
    p = argparse.ArgumentParser(
        prog="tensorflowonspark_tpu.serve",
        description="online inference HTTP server over an exported model")
    p.add_argument("--export_dir", required=True)
    p.add_argument("--model_name", default="default",
                   help="name served under /v1/models/<name>")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=8501)
    p.add_argument("--batch_size", type=int, default=64)
    p.add_argument("--batch_wait_ms", type=float, default=0.0,
                   help=">0 enables dynamic micro-batching: concurrent "
                        "requests within this window coalesce into one "
                        "device execution (up to --batch_size rows)")
    p.add_argument("--signature_def_key", default=None)
    p.add_argument("--max_new_tokens_limit", type=int, default=512,
                   help="upper bound a :generate request may ask for")
    p.add_argument("--draft_export_dir", default=None,
                   help="a smaller decoder-LM export used as the "
                        "speculative draft for :generate requests "
                        "(greedy outputs identical, sampled outputs "
                        "distribution-preserving; faster when the draft "
                        "agrees); speculation runs inside the decode slots")
    p.add_argument("--draft_k", type=int, default=4,
                   help="max draft tokens proposed per verification pass "
                        "(per-row acceptance EWMA adapts the actual k "
                        "between 1 and this)")
    p.add_argument("--spec_draft", default=None,
                   choices=("model", "ngram", "off"),
                   help="speculative draft source: 'model' runs the "
                        "--draft_export_dir LM, 'ngram' proposes by "
                        "suffix-matching the row's own context (no draft "
                        "model needed), 'off' disables speculation; "
                        "default: 'model' when --draft_export_dir is set, "
                        "else 'off'")
    p.add_argument("--generate_slots", type=int, default=8,
                   help="decode slots of the :generate engine (continuous "
                        "batching: concurrent requests join the in-flight "
                        "batch at token boundaries); every request decodes "
                        "through slots")
    p.add_argument("--generate_read_chunk", type=int, default=8,
                   help="slot batcher readback granularity: tokens reach "
                        "clients in bursts of this size (larger = higher "
                        "throughput on high-latency runtimes, burstier "
                        "streams; 1 = per-token)")
    p.add_argument("--generate_prefill_chunk", type=int, default=512,
                   help="admission prefill chunk (tokens): long prompts "
                        "prefill in chunks interleaved with decode steps "
                        "so in-flight streams stall at most one chunk "
                        "(paged mode rounds this UP to a kv page "
                        "multiple so chunks never straddle a page)")
    p.add_argument("--generate_prefill_rows", type=int, default=4,
                   help="admission pipeline width: up to this many "
                        "waiting requests prefill one chunk each PER "
                        "BATCHED DISPATCH (the prefill engine; 1 = the "
                        "sequential one-request-at-a-time admission)")
    p.add_argument("--generate_prefill_budget", type=int, default=0,
                   help="prefill token budget per scheduler round "
                        "(Sarathi-style stall-free scheduling): the "
                        "chunks dispatched between two decode steps "
                        "never exceed this many tokens (0 = "
                        "prefill_rows * prefill_chunk)")
    p.add_argument("--generate_engine", choices=["async", "serial"],
                   default="async",
                   help="decode engine structure: \"async\" (default) = "
                        "double-buffered pipeline — a device thread keeps "
                        "up to --generate_pipeline_depth flushed chunks "
                        "in flight while a host thread drains readbacks, "
                        "commits tokens, and delivers stream batches; "
                        "\"serial\" = the single-thread reference loop "
                        "(byte-identical tokens; parity/debugging)")
    p.add_argument("--generate_pipeline_depth", type=int, default=2,
                   help="async engine: flushed readback chunks allowed "
                        "in flight between device and host threads "
                        "(the double buffer; >= 1)")
    p.add_argument("--generate_timeout_s", type=float, default=None,
                   help="wall-time bound on one :generate request "
                        "(default: max(600, 2*max_new_tokens_limit))")
    p.add_argument("--generate_kv_page_size", type=int, default=0,
                   help=">0 enables a PAGED kv cache for the :generate "
                        "slots: rows draw pages of this many tokens from "
                        "a shared pool instead of reserving max_seq_len "
                        "each (requires --generate_kv_pages)")
    p.add_argument("--generate_kv_pages", type=int, default=0,
                   help="pool size (pages) for --generate_kv_page_size")
    p.add_argument("--generate_long_prompt_threshold", type=int, default=0,
                   help=">0 routes prompts longer than this many tokens "
                        "through the mega-prompt lane: they admit "
                        "immediately but stream prefill chunk-by-chunk "
                        "in their own WFQ-scheduled lane (bounded chunk "
                        "quota per round) instead of monopolizing the "
                        "prefill budget, allocating kv pages lazily as "
                        "chunks land and demoting cold prefix-cache "
                        "pages to the host tier when the device pool "
                        "runs dry.  Requires --generate_kv_page_size; "
                        "0 = every prompt uses the normal admission "
                        "path")
    p.add_argument("--generate_host_cache_mb", type=int, default=0,
                   help=">0 enables the host-DRAM KV page tier behind "
                        "the paged pool: evicted and retired full-prefix "
                        "pages are demoted into a bounded host-side LRU "
                        "cache of this many MiB and promoted back into "
                        "the prefix cache (skipping their prefill "
                        "entirely) when a later prompt shares the "
                        "prefix — warm multi-turn TTFT becomes a "
                        "page-in instead of an O(history) re-prefill.  "
                        "Requires --generate_kv_page_size; also serves "
                        "peers' kv:prefix pulls when fleet-registered")
    p.add_argument("--generate_paged_attn", choices=["kernel", "einsum"],
                   default=None,
                   help="paged kv READ path: \"kernel\" (default) = the "
                        "Pallas flash-decode kernel (page table walked in "
                        "place, only occupied pages read); \"einsum\" = "
                        "the full-gather reference body (parity / "
                        "debugging).  Only meaningful with "
                        "--generate_kv_page_size")
    p.add_argument("--generate_paged_prefill", choices=["kernel", "blend"],
                   default=None,
                   help="paged prefill (S>1 chunk) path: \"kernel\" "
                        "(default) = the Pallas paged-prefill kernels "
                        "(page-granular in-place pool writes + chunked "
                        "flash read, O(chunk) traffic); \"blend\" = the "
                        "one-hot einsum blend + full-gather reference "
                        "(parity / debugging).  Only meaningful with "
                        "--generate_kv_page_size")
    p.add_argument("--generate_kv_dtype", choices=["auto", "int8"],
                   default="auto",
                   help="int8 = quantized slot kv cache (int8 payload + "
                        "per-token-head scales): ~2x less resident kv vs "
                        "bf16, composing with --generate_kv_page_size "
                        "paging and every sampling control")
    p.add_argument("--generate_lora_rank", type=int, default=0,
                   help=">0 enables a multi-adapter LoRA bank on the "
                        ":generate slots: requests select a registered "
                        "adapter by name ({\"adapter\": \"x\"}) and N "
                        "tenants share the one batched decode step "
                        "(rows without an adapter run the base model "
                        "exactly)")
    p.add_argument("--generate_lora_capacity", type=int, default=8,
                   help="max adapters resident in the bank")
    p.add_argument("--generate_lora", action="append", default=None,
                   metavar="NAME=PATH",
                   help="register adapter NAME from a lora.save_adapters "
                        "file at startup (repeatable)")
    p.add_argument("--generate_quantize", choices=list(QUANTIZE_MODES),
                   default="none",
                   help="weight-only post-training quantization of the "
                        ":generate LM (and draft): int8 = kernels stored "
                        "int8 + per-channel scale (~4x less weight HBM, "
                        "~half the per-token weight read vs bf16); int4 = "
                        "nibble-packed with per-group scales (~8x / ~4x). "
                        "Decode steps consume the quantized leaves through "
                        "the Pallas fused-dequant matmul "
                        "(ops/quant_matmul.py; inline-dequant fallback "
                        "under a mesh) — int8 outputs match the "
                        "materialized-dequant path token-for-token, int4 "
                        "shifts outputs by the (bounded, grouped) "
                        "quantization noise")
    p.add_argument("--input_mapping", default=None)
    p.add_argument("--output_mapping", default=None)
    p.add_argument("--engine", choices=["auto", "native", "jax", "builder"],
                   default="auto")
    p.add_argument("--role", choices=["mixed", "prefill", "decode"],
                   default="mixed",
                   help="disaggregated-serving role advertised to the "
                        "fleet gateway: \"prefill\" replicas take "
                        ":generate admissions and hand each session to a "
                        "decode replica (page-granular kv migration) once "
                        "its first tokens flush; \"decode\" replicas "
                        "receive migrated sessions; \"mixed\" (default) "
                        "does both.  Advisory — every replica still "
                        "serves every endpoint")
    p.add_argument("--fleet", default=None, metavar="HOST:PORT",
                   help="register this replica with a fleet gateway's "
                        "registry (python -m tensorflowonspark_tpu.fleet) "
                        "over the reservation protocol, and heartbeat "
                        "until shutdown")
    p.add_argument("--fleet_heartbeat_s", type=float, default=2.0,
                   help="replica->gateway heartbeat interval (keep well "
                        "under the gateway's --heartbeat_timeout_s)")
    p.add_argument("--advertise_host", default=None,
                   help="host the GATEWAY should dial this replica on "
                        "(default: --host; set when binding 0.0.0.0)")
    p.add_argument("--generate_priority_weight", type=int, default=4,
                   help="weighted-fair admission ratio for :generate "
                        "priority classes: admit up to N interactive "
                        "sessions per batch-class session while both "
                        "queues are non-empty (requests carry a class "
                        "via X-Priority or {\"priority\": ...}; default "
                        "class is \"interactive\")")
    p.add_argument("--generate_preempt_ms", type=float, default=0.0,
                   help=">0 enables the preemption controller: when the "
                        "oldest waiting interactive admission has queued "
                        "longer than this many ms, the lowest-priority "
                        "running session is PARKED (freeze_session "
                        "snapshot held host-side, its kv pages freed) "
                        "and resumed byte-identically via the :resume "
                        "path when interactive pressure drops")
    p.add_argument("--generate_park_capacity", type=int, default=8,
                   help="bounded park pool: max frozen sessions held "
                        "host-side by the preemption controller; at "
                        "capacity further preemptions are skipped and "
                        "counted as park_spills")
    p.add_argument("--generate_trace_ring", type=int, default=4096,
                   help="per-process span ring capacity for request "
                        "tracing (trace.Recorder); old spans fall off "
                        "the back, recording never blocks serving")
    p.add_argument("--generate_trace_decode_sample", type=int, default=16,
                   help="record a decode span every Nth committed host "
                        "tick per traced row (0 disables decode "
                        "sampling; admission/prefill/retire and the "
                        "migration/park hops are always recorded)")
    p.add_argument("--verbose", action="store_true")
    return p


def _is_int(x):
    """A REAL int: JSON `true`/`false` arrive as Python bools, which are
    ints by inheritance — `{"top_k": true}` would otherwise sail through
    int validation as top_k=1 instead of 400ing."""
    return isinstance(x, int) and not isinstance(x, bool)


def _bucket_len(n, cap):
    """Padded length for a prefill chunk of `n` tokens: the next power
    of two (floor 8), capped at the configured chunk size — the jit
    compiles per BUCKET, not per prompt length, so compile variants
    stay O(log(cap)) while pad waste stays under 2x."""
    return min(max(8, 1 << (n - 1).bit_length()), cap)


def _pow2_width(n):
    """Padded row count for a batched prefill dispatch: next power of
    two — same bounded-compile-variants reasoning as `_bucket_len`."""
    return 1 << (n - 1).bit_length()


def max_table_pages(max_seq_len, kv_page_size):
    """The page-table width CAP for one row: enough entries to map a
    full max_seq_len sequence.  The single sizing authority — every
    width computation (initial allocation, growth clamp, resume
    validation) goes through here so the growable-table layout has
    exactly one notion of \"full width\"."""
    return max_seq_len // kv_page_size


# Initial per-row page-table width (entries).  Rows start this small and
# grow geometrically (pow2 steps, decode._jitted_grow_page_table) only
# when an admission actually needs more — a short-prompt workload never
# pays page-table bytes for a max_seq_len-capable table.
_INIT_TABLE_PAGES = 8


class KVOverflowError(RuntimeError):
    """Device kv pool + host tier could not yield the pages a request
    needs even with nothing else running — the request cannot fit on
    this replica.  Maps to a typed 503 (retryable on a peer with more
    headroom), NOT a 400: the request is well-formed."""


def _aligned_prefill_chunk(prefill_chunk, kv_page_size):
    """Effective prefill chunk size: floor 8, and in paged mode rounded
    UP to a kv_page_size multiple.  A chunk straddling a page boundary
    still writes correctly (positions map through the table), but it
    breaks the prefix cache's page-granular accounting and wastes a
    partial page of every bucket — so misalignment is corrected loudly
    at startup, not silently clamped."""
    chunk = max(8, prefill_chunk)
    if kv_page_size and chunk % kv_page_size:
        aligned = -(-chunk // kv_page_size) * kv_page_size
        logger.warning(
            "prefill_chunk %d is not a multiple of kv_page_size %d; "
            "rounding up to %d", chunk, kv_page_size, aligned)
        return aligned
    return chunk


def _instances_to_columns(instances, input_names=None):
    """[{feature: value}, ...] -> ({feature: [values]}, n).

    Also accepts TF Serving's bare row format ([[...], [...]] or scalars)
    when the model has exactly one input: the values map onto that input.
    """
    if not isinstance(instances, list) or not instances:
        raise ValueError('"instances" must be a non-empty list')
    first = instances[0]
    if not isinstance(first, dict):
        if input_names is not None and len(input_names) == 1:
            return {input_names[0]: list(instances)}, len(instances)
        raise ValueError(
            "each instance must be a {feature: value} object (bare rows are "
            "only accepted for single-input models)")
    cols = {k: [] for k in first}
    for i, inst in enumerate(instances):
        if set(inst) != set(cols):
            raise ValueError(f"instance {i} features {sorted(inst)} differ "
                             f"from instance 0 {sorted(cols)}")
        for k, v in inst.items():
            cols[k].append(v)
    return cols, len(instances)


def _rows_from_outputs(outputs, n):
    """{out_col: array-like [n, ...]} -> [{out_col: value}, ...]."""
    import numpy as np

    listed = {name: np.asarray(col).tolist() for name, col in outputs.items()}
    return [{name: listed[name][i] for name in listed} for i in range(n)]


class _MicroBatcher:
    """Coalesce concurrent predict calls into one device execution — the
    TF-Serving request-batching analog (the reference's JVM TFModel got
    the same effect from partition-granular batching,
    TFModel.scala:121-239).  The first request opens a window of
    ``wait_ms``; requests arriving within it are merged (up to
    ``max_batch`` rows) into one columnar execution, and each caller's
    future receives exactly its row slice.  A lone request pays at most
    ``wait_ms`` extra latency; concurrent bursts pay ONE device dispatch
    instead of N serialized ones."""

    def __init__(self, predict_cols, wait_ms=5.0, max_batch=256):
        import queue as queue_mod

        self._predict = predict_cols
        self._wait_s = wait_ms / 1e3
        self._max = max_batch
        self._q = queue_mod.Queue()
        self.executions = 0
        t = threading.Thread(target=self._loop, name="serve-batcher",
                             daemon=True)
        t.start()

    def submit(self, cols, n):
        import concurrent.futures as cf

        fut = cf.Future()
        self._q.put((cols, n, fut))
        return fut.result()

    def _loop(self):
        import queue as queue_mod
        import time as time_mod

        while True:
            batch = [self._q.get()]
            total = batch[0][1]
            deadline = time_mod.monotonic() + self._wait_s
            while total < self._max:
                remaining = deadline - time_mod.monotonic()
                if remaining <= 0:
                    break
                try:
                    item = self._q.get(timeout=remaining)
                except queue_mod.Empty:
                    break
                batch.append(item)
                total += item[1]
            # per-request validation BEFORE merging: a malformed request
            # fails alone instead of poisoning every future coalesced
            # into its window
            head_keys = set(batch[0][0])
            good = []
            for item in batch:
                cols, _, fut = item
                if set(cols) != head_keys:
                    fut.set_exception(ValueError(
                        f"request features {sorted(cols)} differ from "
                        f"batch head {sorted(head_keys)}"))
                else:
                    good.append(item)
            if not good:
                continue
            try:
                merged = {k: [] for k in head_keys}
                for cols, _, _ in good:
                    for k, v in cols.items():
                        merged[k].extend(v)
                total = sum(n for _, n, _ in good)
                outputs = self._predict(merged, total)
                self.executions += 1
                import numpy as np
                arrays = {k: np.asarray(v) for k, v in outputs.items()}
                off = 0
                for _, n, fut in good:
                    fut.set_result(
                        {k: a[off:off + n] for k, a in arrays.items()})
                    off += n
            except Exception as e:
                # result distribution included: ANY escape here would kill
                # the batcher thread and wedge every future submit forever
                for _, _, fut in good:
                    if not fut.done():
                        fut.set_exception(e)


class ModelService:
    """Loads the predictor once; thread-safe predict over JSON instances.

    ``batch_wait_ms > 0`` enables dynamic micro-batching: concurrent
    requests coalesce into one device execution (see _MicroBatcher).
    """

    def __init__(self, args):
        from . import inference

        self._predict_rows, self.desc = inference._load_predictor(args)
        self._lock = threading.Lock()
        self.export_dir = args.export_dir
        self.model_name = getattr(args, "model_name", "default")
        self.requests = 0
        self._gen_error = None          # why :generate is unavailable
        self._gen = None                # lazy GenerateService (or False =
        self._gen_lock = threading.Lock()   # probed and not a decoder LM)
        self._max_new_limit = getattr(args, "max_new_tokens_limit", 512)
        self._draft_dir = getattr(args, "draft_export_dir", None)
        self._draft_k = getattr(args, "draft_k", 4)
        self._spec_draft = getattr(args, "spec_draft", None)
        self._gen_slots = getattr(args, "generate_slots", 8) or 8
        self._gen_read_chunk = getattr(args, "generate_read_chunk", 8) or 8
        self._gen_prefill_chunk = getattr(args, "generate_prefill_chunk",
                                          512) or 512
        self._gen_prefill_rows = getattr(args, "generate_prefill_rows",
                                         4) or 4
        self._gen_prefill_budget = getattr(args, "generate_prefill_budget",
                                           0) or 0
        self._gen_engine = getattr(args, "generate_engine",
                                   "async") or "async"
        self._gen_pipeline_depth = getattr(args, "generate_pipeline_depth",
                                           2) or 2
        self._gen_timeout_s = getattr(args, "generate_timeout_s", None)
        self._gen_kv_page_size = getattr(args, "generate_kv_page_size", 0)
        self._gen_kv_pages = getattr(args, "generate_kv_pages", 0)
        self._gen_host_cache_mb = getattr(args, "generate_host_cache_mb",
                                          0) or 0
        self._gen_kv_dtype = getattr(args, "generate_kv_dtype",
                                     "auto") or "auto"
        self._gen_paged_attn = getattr(args, "generate_paged_attn", None)
        self._gen_paged_prefill = getattr(args, "generate_paged_prefill",
                                          None)
        self._gen_quantize = getattr(args, "generate_quantize",
                                     "none") or "none"
        self._gen_lora_rank = getattr(args, "generate_lora_rank", 0) or 0
        self._gen_lora_capacity = getattr(args, "generate_lora_capacity",
                                          8) or 8
        self._gen_prio_weight = getattr(args, "generate_priority_weight",
                                        4) or 4
        self._gen_preempt_ms = getattr(args, "generate_preempt_ms",
                                       0.0) or 0.0
        self._gen_park_capacity = getattr(args, "generate_park_capacity",
                                          8) or 8
        self._gen_long_threshold = getattr(
            args, "generate_long_prompt_threshold", 0) or 0
        self._gen_trace_ring = getattr(args, "generate_trace_ring",
                                       4096) or 4096
        sample = getattr(args, "generate_trace_decode_sample", 16)
        self._gen_trace_sample = 16 if sample is None else int(sample)
        self._profile_lock = threading.Lock()   # one capture at a time
        self._gen_lora = {}
        for spec in (getattr(args, "generate_lora", None) or []):
            name, sep, path = spec.partition("=")
            if not sep or not name or not path:
                raise ValueError(
                    f"--generate_lora {spec!r} must be NAME=PATH")
            self._gen_lora[name] = path
        # disaggregated serving: the replica's role is advisory routing
        # metadata (the gateway prefers prefill/mixed for :generate and
        # hands sessions to decode/mixed replicas); every replica still
        # serves every endpoint, so a degraded fleet keeps working
        self.role = getattr(args, "role", "mixed") or "mixed"
        self._bind_host = getattr(args, "host", "127.0.0.1") or "127.0.0.1"
        self._advertise_host = getattr(args, "advertise_host", None)
        self._migrator = None           # lazy kvtransfer.MigrationEngine
        self._batcher = None
        self._draining = threading.Event()
        wait_ms = getattr(args, "batch_wait_ms", 0) or 0
        if wait_ms > 0:
            self._batcher = _MicroBatcher(
                self._predict_rows, wait_ms=wait_ms,
                max_batch=getattr(args, "batch_size", 64) or 64)

    def predict(self, instances):
        cols, n = _instances_to_columns(
            instances, getattr(self._predict_rows, "input_names", None))
        if self._batcher is not None:
            outputs = self._batcher.submit(cols, n)
            with self._lock:
                self.requests += 1
            return _rows_from_outputs(outputs, n)
        with self._lock:   # one device: serialize executions
            outputs = self._predict_rows(cols, n)
            self.requests += 1
        return _rows_from_outputs(outputs, n)

    def generate_service(self):
        """Lazily-built GenerateService, or None when the export's builder
        does not rebuild a decoder LM (probed once)."""
        with self._gen_lock:
            if self._gen is None:
                try:
                    self._gen = GenerateService(
                        self.export_dir,
                        max_new_tokens_limit=self._max_new_limit,
                        draft_export_dir=self._draft_dir,
                        draft_k=self._draft_k,
                        spec_draft=self._spec_draft,
                        slots=self._gen_slots,
                        read_chunk=self._gen_read_chunk,
                        prefill_chunk=self._gen_prefill_chunk,
                        prefill_rows=self._gen_prefill_rows,
                        prefill_budget=self._gen_prefill_budget,
                        request_timeout_s=self._gen_timeout_s,
                        kv_page_size=self._gen_kv_page_size,
                        kv_pages=self._gen_kv_pages,
                        host_cache_mb=self._gen_host_cache_mb,
                        quantize_mode=self._gen_quantize,
                        lora_rank=self._gen_lora_rank,
                        lora_capacity=self._gen_lora_capacity,
                        lora_adapters=self._gen_lora,
                        kv_dtype=self._gen_kv_dtype,
                        paged_attn_impl=self._gen_paged_attn,
                        paged_prefill_impl=self._gen_paged_prefill,
                        engine=self._gen_engine,
                        pipeline_depth=self._gen_pipeline_depth,
                        prio_weight=self._gen_prio_weight,
                        preempt_ms=self._gen_preempt_ms,
                        park_capacity=self._gen_park_capacity,
                        long_prompt_threshold=self._gen_long_threshold,
                        trace_ring=self._gen_trace_ring,
                        trace_decode_sample=self._gen_trace_sample)
                except TypeError as e:
                    # genuinely not a decoder LM: the documented 404
                    logger.info(":generate unavailable: %s", e)
                    self._gen = False
                    self._gen_error = str(e)
                except ValueError as e:
                    # a CONFIG error (page size vs max_seq_len, draft
                    # vocab mismatch, ...) must not masquerade as "not a
                    # decoder LM": log loudly and carry the reason into
                    # the endpoint's error body
                    logger.error(":generate misconfigured: %s", e)
                    self._gen = False
                    self._gen_error = str(e)
            return self._gen or None

    def migration_engine(self):
        """Lazily-built kvtransfer.MigrationEngine, or None when this
        export cannot generate (nothing to migrate)."""
        gen = self.generate_service()
        if gen is None:
            return None
        with self._gen_lock:
            if self._migrator is None:
                from . import kvtransfer

                host = self._bind_host
                if host in ("", "0.0.0.0", "::"):
                    host = "0.0.0.0"
                self._migrator = kvtransfer.MigrationEngine(
                    gen.batcher, model_name=self.model_name,
                    host=host,
                    advertise_host=(self._advertise_host
                                    or ("127.0.0.1"
                                        if host == "0.0.0.0" else host)),
                    # kv:prefix pulls read the batcher's host tier (an
                    # empty answer when the tier is off/cold — peers
                    # just prefill)
                    prefix_provider=gen.batcher.host_prefix_provider)
            return self._migrator

    def kv_export(self, body):
        """``POST /v1/kv:export``: move live sessions to the given
        destination replica(s).  Body: ``{"dest": {"host", "port"}}``
        or ``{"dests": [...]}``, optional ``timeout_s`` /
        ``max_sessions``."""
        eng = self.migration_engine()
        if eng is None:
            raise ValueError(
                ":generate is unavailable on this export — no kv to "
                "export")
        raw = body.get("dests") or ([body["dest"]]
                                    if body.get("dest") else [])
        dests = []
        for d in raw:
            if (not isinstance(d, dict) or not d.get("host")
                    or not _is_int(d.get("port"))):
                raise ValueError(
                    '"dest(s)" entries must be {"host": ..., "port": ...}')
            dests.append((str(d["host"]), int(d["port"])))
        if not dests:
            raise ValueError('kv:export needs "dest" or "dests"')
        timeout_s = body.get("timeout_s")
        if timeout_s is not None and not (
                isinstance(timeout_s, (int, float)) and timeout_s > 0):
            raise ValueError('"timeout_s" must be a positive number')
        max_sessions = body.get("max_sessions")
        if max_sessions is not None and not _is_int(max_sessions):
            raise ValueError('"max_sessions" must be an int')
        return eng.migrate_all(dests, max_sessions=max_sessions,
                               timeout_s=timeout_s)

    def auto_migrate_hook(self, dest_spec):
        """Per-request handoff callback for ``X-Fleet-Migrate-To``
        (host:port): the gateway plants the header when it routed a
        :generate to a prefill-role replica; the session migrates to
        the named decode replica as soon as its first decode tokens
        flush.  Returns None (and logs) on a malformed spec — the
        session just stays here."""
        host, _, port = str(dest_spec).rpartition(":")
        if not host or not port.isdigit():
            logger.warning("ignoring malformed X-Fleet-Migrate-To %r",
                           dest_spec)
            return None
        eng = self.migration_engine()
        if eng is None:
            return None

        def kick(handle):
            eng.migrate_async(handle, (host, int(port)))
        return kick

    @property
    def draining(self):
        return self._draining.is_set()

    def begin_drain(self):
        """Fence admissions: :predict/:generate start 503ing (with
        Retry-After) and /readyz flips to 503, while in-flight slot
        generations keep decoding to completion."""
        self._draining.set()

    def drain(self, timeout_s=60.0, poll_s=0.05):
        """The replica-side drain hook (``POST /v1/fleet:drain``): fence
        admissions, then wait until the :generate slot engine is idle —
        no busy slots, no queued prompts, no admission in progress.
        :predict needs no wait of its own (each request holds its HTTP
        thread until the device returns, so by the time the gateway has
        seen its in-flight proxied requests settle there is nothing
        left).  Returns {"drained": bool, "waited_s": s, ...}."""
        self.begin_drain()
        t0 = time.monotonic()
        deadline = t0 + float(timeout_s)
        with self._gen_lock:
            gen = self._gen or None   # never FORCE-build an engine just
            # to watch it be idle: un-probed == nothing ever generated
        pending = 0
        while gen is not None:
            st = gen.batcher.stats()
            pending = (st["slots_busy"] + st["pending"]
                       + int(st["admitting"])
                       + int(st.get("parked_sessions", 0)))
            if pending == 0 or time.monotonic() >= deadline:
                break
            time.sleep(poll_s)
        return {"drained": pending == 0, "draining": True,
                "in_flight": pending,
                "waited_s": round(time.monotonic() - t0, 3)}

    def close(self):
        """Release serving resources: stops the slot batcher's driver
        thread (otherwise it busy-polls forever after server teardown)."""
        with self._gen_lock:
            if self._migrator is not None:
                try:
                    self._migrator.close()
                except Exception:
                    logger.warning("migration engine close failed",
                                   exc_info=True)
                self._migrator = None
            if self._gen:
                try:
                    self._gen.batcher.stop()
                except Exception:
                    logger.warning("batcher stop failed", exc_info=True)
            self._gen = False   # later :generate probes refuse cleanly

    def metadata(self):
        out = {"model": {"export_dir": self.export_dir,
                         "engine": self.desc,
                         "role": self.role,
                         "requests_served": self.requests},
               "status": "draining" if self.draining else "ok"}
        if self._batcher is not None:
            out["model"]["batched_executions"] = self._batcher.executions
        if self._gen is not None:      # only report once probed (lazily)
            out["model"]["generate"] = ("available" if self._gen
                                        else "unavailable")
            if self._gen and self._gen.batcher is not None:
                out["model"]["generate_slots"] = self._gen.batcher.n_slots
                out["model"]["generate_stats"] = self._gen.batcher.stats()
            if self._gen and self._gen.quantize_mode != "none":
                # sizes were computed ONCE at engine build (a full
                # param-tree walk) — fleet heartbeats probe metadata,
                # so this must stay O(1) per probe
                out["model"]["generate_quantize"] = {
                    "mode": self._gen.quantize_mode,
                    "weight_bytes": self._gen.weight_bytes,
                    "float_equivalent_bytes":
                        self._gen.float_equivalent_bytes}
        return out

    def metrics_text(self):
        """``GET /metrics``: Prometheus text exposition generated from
        the same ``stats()`` dict the fleet probes — every counter,
        gauge, and LatencyWindow key, plus histogram triplets.  Never
        force-builds the :generate engine (an un-probed replica scrapes
        its HTTP-level stats only)."""
        from . import metrics as metrics_mod

        groups = [("replica", None,
                   {"http_requests": self.requests,
                    "draining": self.draining})]
        with self._gen_lock:
            gen = self._gen or None
        if gen is not None:
            groups.append(("replica", None, gen.batcher.stats()))
        return metrics_mod.prometheus_text(groups)

    def trace_spans(self, trace_id):
        """``GET /v1/trace/<id>``: this replica's retained spans for a
        trace (empty when :generate never ran here — the gateway's
        stitcher treats that as "this replica saw nothing")."""
        with self._gen_lock:
            gen = self._gen or None
        if gen is None:
            return []
        return gen.batcher.trace.spans(trace_id)

    def debug_profile(self, body):
        """``POST /v1/debug:profile``: run a time-bounded
        ``jax.profiler.trace`` capture and return the artifact dir.
        Returns ``(status_code, payload)``: 409 while another capture
        holds the (single) profiler, 503 when the runtime cannot
        profile here (CPU-only jaxlib, missing plugin) — serving is
        untouched either way."""
        dur = body.get("duration_ms", 500)
        if (not isinstance(dur, (int, float)) or isinstance(dur, bool)
                or not 0 < dur <= 10000):
            raise ValueError('"duration_ms" must be a number in '
                             "(0, 10000]")
        out_dir = body.get("dir")
        if out_dir is not None and not isinstance(out_dir, str):
            raise ValueError('"dir" must be a string path')
        if not self._profile_lock.acquire(blocking=False):
            return 409, {"error": "a profile capture is already running"}
        try:
            if out_dir is None:
                import tempfile

                out_dir = tempfile.mkdtemp(prefix="tpu-profile-")
            try:
                import jax

                with jax.profiler.trace(out_dir):
                    time.sleep(float(dur) / 1000.0)
            except Exception as e:
                # degrade, don't die: profiling is best-effort
                logger.warning("profiler capture failed: %s", e)
                return 503, {"error": "profiler unavailable: "
                             f"{type(e).__name__}: {e}"}
            return 200, {"artifact": out_dir,
                         "duration_ms": float(dur)}
        finally:
            self._profile_lock.release()


class SlotHandle:
    """One in-flight generation in the continuous batcher: tokens stream
    into `.tokens` as they decode; `.result()` blocks for the full
    sequence."""

    def __init__(self, prompt):
        import queue as queue_mod

        self.prompt = list(prompt)
        # BATCHES of ints (one list per host tick — the engine delivers
        # every token a tick committed for this request in one put, not
        # one queue round-trip per token), then the None sentinel
        self.tokens = queue_mod.Queue()
        self.cancelled = threading.Event()
        self._done = threading.Event()
        self._outcome_lock = threading.Lock()   # finish/fail are
        # first-wins and may race across engine threads
        self._seq = None
        self._err = None
        self._on_done = None   # fired exactly once at finish/fail (the
        # batcher releases per-request resources here, e.g. the LoRA
        # adapter's in-flight reference)
        # --- kv migration (kvtransfer.MigrationEngine) ---
        # the engine sets migrate_requested; the host thread performs
        # the freeze cut at its next token commit for this row, parks
        # the snapshot in `frozen`, and signals freeze_done.  The row
        # then emits no tokens until complete/rollback decides which
        # replica owns the continuation.
        self.migrate_requested = threading.Event()
        self.freeze_done = threading.Event()
        self.frozen = None

    def cancel(self):
        """Stop decoding for this request (client gone): the batcher
        retires its slot at the next readback boundary."""
        self.cancelled.set()

    def _settle(self):
        cb, self._on_done = self._on_done, None
        if cb is not None:
            try:
                cb()
            except Exception:
                logger.warning("handle on_done callback failed",
                               exc_info=True)

    def _finish(self, seq):
        # first outcome wins: with the async engine the host thread
        # finishes handles while stop()/death-drain may fail them — a
        # late second settle must not overwrite the recorded result
        with self._outcome_lock:
            if self._done.is_set():
                return
            self._settle()
            self._seq = seq
            self._done.set()
            self.tokens.put(None)

    def _fail(self, err):
        with self._outcome_lock:
            if self._done.is_set():
                return
            self._settle()
            self._err = err
            self._done.set()
            self.tokens.put(None)

    def result(self, timeout=None):
        if not self._done.wait(timeout):
            raise TimeoutError("generation did not complete in time")
        if self._err is not None:
            raise self._err
        return self._seq


class ContinuousBatcher:
    """THE serving decode engine: slot-based continuous batching over the
    per-row kv cache (models.decode `decode_slots`).  New requests
    PREFILL into a free slot in chunks interleaved with decode steps (a
    long prompt admission never stalls in-flight streams for more than
    one chunk); finished slots retire immediately.  The device runs one
    fused step per token for the whole slot batch, so N concurrent
    streams cost ~one stream's step rate (decode is weight-read-bound,
    so rows are near-free; the ratio on this chip: not measured).

    Every :generate request routes here (round 5 unified the grouped and
    slot paths), so identical requests produce identical tokens by
    construction at ANY dtype.  Greedy decoding is token-identical to a
    solo `decode.generate` in f32; sampled rows draw from the SHARED
    schedule ``fold_in(key(seed), ordinal)`` (decode.step_keys), so a
    sampled slot run reproduces the solo call too.  With speculation
    (``spec_draft``: a draft model or model-free n-gram lookup), slots
    advance by fused speculative rounds (k proposals + one verify
    dispatch, per-row acceptance, adaptive k): greedy rows commit the
    target's own argmax — tokens unchanged — and sampled rows verify by
    rejection sampling against the target's filtered distribution —
    distribution-preserving and seed-deterministic (the accept/resample
    key schedule is keyed per POSITION, not per round, so tokens don't
    depend on round boundaries or the adaptive-k trajectory).  Net-new
    beyond the reference (no generation serving there at all).
    """

    def __init__(self, model, params, n_slots=8, max_pending=1024,
                 read_chunk=8, prefill_chunk=512, prefill_rows=4,
                 prefill_budget=0, draft_model=None,
                 draft_params=None, draft_k=4, spec_draft=None,
                 kv_page_size=0, kv_pages=0,
                 host_cache_mb=0,
                 lora_rank=0, lora_capacity=8, kv_dtype=None,
                 paged_attn_impl=None, paged_prefill_impl=None,
                 engine="async", pipeline_depth=2,
                 prio_weight=4, preempt_ms=0.0, park_capacity=8,
                 long_prompt_threshold=0, long_chunk_quota=1,
                 trace_recorder=None, trace_ring=4096,
                 trace_decode_sample=16):
        import itertools
        import queue as queue_mod

        import jax.numpy as jnp

        from .metrics import Counters, Gauge, LatencyWindow
        from .models import decode as decode_mod

        # "async" (the default) splits the engine into a DEVICE thread
        # (dispatch + admission; owns every device buffer) feeding a
        # HOST thread (readback, stop conditions, stream delivery)
        # through a bounded chunk queue — up to `pipeline_depth` flushed
        # chunks stay in flight, so the device keeps stepping while the
        # host works.  "serial" is the single-thread reference engine
        # (byte-identical tokens; the parity baseline).
        if engine not in ("async", "serial"):
            raise ValueError(f"engine={engine!r} not in "
                             "('async', 'serial')")
        self.engine = engine
        self.pipeline_depth = max(1, int(pipeline_depth))

        self.model, self.params = model, params
        # host-side event counters (sink-write accounting below);
        # stats() folds snapshot() in, so the fleet gateway and
        # GET /v1/metadata see every counter without extra plumbing
        self.counters = Counters()
        # request tracing: per-process bounded span ring; an injected
        # recorder lets in-process tests share one ring across paired
        # batchers.  All span clocks are host time.monotonic() —
        # recording NEVER reads a device value
        self.trace = trace_recorder or trace.Recorder(
            capacity=trace_ring, decode_sample=trace_decode_sample)
        # "int8" stores the slot kv cache quantized (int8 payload +
        # per-(token, head) f32 scales — TransformerConfig.kv_dtype):
        # ~2x less resident kv vs bf16, composing with paging (pool
        # pages quantize too) and every sampling control.  "auto" (the
        # CLI default GenerateService forwards) normalizes to None HERE
        # so a directly-constructed batcher behaves identically and
        # stats() never reports a phantom quantized cache
        self.kv_dtype = None if kv_dtype == "auto" else kv_dtype
        kv_dtype = self.kv_dtype
        self.kv_page_size = int(kv_page_size or 0)
        if self.kv_page_size and int(kv_pages) < 1:
            raise ValueError(
                "kv_page_size > 0 requires kv_pages >= 1 (the shared "
                "pool's size; --generate_kv_pages on the CLI)")
        if int(host_cache_mb or 0) > 0 and not self.kv_page_size:
            raise ValueError(
                "host_cache_mb > 0 requires a paged kv cache "
                "(--generate_kv_page_size): the host tier holds "
                "demoted PAGES")
        self.long_prompt_threshold = int(long_prompt_threshold or 0)
        if self.long_prompt_threshold < 0:
            raise ValueError("long_prompt_threshold must be >= 0")
        if self.long_prompt_threshold and not self.kv_page_size:
            raise ValueError(
                "long_prompt_threshold > 0 requires a paged kv cache "
                "(--generate_kv_page_size): the mega-prompt lane "
                "allocates pages lazily as chunks land")
        self.long_chunk_quota = max(1, int(long_chunk_quota or 1))
        if self.kv_page_size:
            # PAGED kv: rows draw pages from a shared pool sized by
            # kv_pages instead of reserving max_seq_len each — n_slots
            # can exceed the dense-cache HBM limit when requests are
            # shorter than max_seq (vLLM-style; decode.init_paged_slot_
            # cache).  Admission allocates a row's whole projected need
            # from the free list and retirement returns it; when the
            # pool is empty, admissions WAIT (natural backpressure).
            # One EXTRA page is the garbage SINK: free rows keep
            # decoding junk until re-occupied (the device loop steps
            # every row; the _gen filter drops their tokens), and with
            # a shared pool those junk writes must never land in pages
            # another row now owns — a freed row's table is pointed at
            # the sink, where writes are harmless.
            self._sink = int(kv_pages)
            self._total_pages = int(kv_pages)
            # GROWABLE page tables: rows start at a small pow2 width and
            # widen geometrically (decode._jitted_grow_page_table) the
            # first time an admission's projected need exceeds it — a
            # short-prompt workload never allocates a max_seq-capable
            # table.  _table_cap is the one sizing authority (the old
            # per-site `max_seq_len // page_size` computations).
            self._table_cap = max_table_pages(
                model.cfg.max_seq_len, self.kv_page_size)
            self._table_width = min(self._table_cap, _INIT_TABLE_PAGES)
            self.slot_model, self._cache = decode_mod.init_paged_slot_cache(
                model, n_slots, self.kv_page_size, int(kv_pages) + 1,
                kv_dtype=kv_dtype, paged_attn_impl=paged_attn_impl,
                paged_prefill_impl=paged_prefill_impl,
                table_pages=self._table_width)
            # host-side mirror of the model's S>1 prefill gate (the
            # branch resolves at trace time, so the jit itself cannot
            # count): drives the prefill_kernel_dispatches /
            # prefill_blend_fallbacks observability split
            self._prefill_kernel_active = (
                self.slot_model.cfg.paged_prefill_impl == "kernel")
            self._set_table = decode_mod._jitted_set_row_page_table(
                self.slot_model)
            # device-thread-owned free list; stats() only takes len() of a
            # momentary snapshot (monitoring skew is fine)
            # graftcheck: disable-next-line=thread-race
            self._free_pages = list(range(int(kv_pages)))
            self._row_pages = [None] * n_slots
            # prefix cache state (see the prefix-cache section below);
            # mutated on the device thread only — stats() len() reads
            # tolerate skew  # graftcheck: disable-next-line=thread-race
            self._prefix = {}        # cumulative-prefix key -> pool page
            self._prefix_lru = {}    # key -> lru tick
            self._page_rc = {}       # page -> live-row refcount (managed)
            self._lru_tick = 0
            self._row_shared_n = [0] * n_slots
            self._row_prefix_keys = [None] * n_slots
            self.prefill_tokens_shared = 0
            # host-DRAM page tier (hierarchical kv cache): evicted and
            # retired full-prefix pages demote into this bounded LRU
            # pool and promote back on a later prefix match, skipping
            # their prefill.  The tier is its own module (kvtier) —
            # the batcher only gathers/scatters on the device thread
            if int(host_cache_mb or 0) > 0:
                from . import kvtier

                self._host_tier = kvtier.HostPageTier(
                    int(host_cache_mb) << 20)
            else:
                self._host_tier = None
            # sized to the CURRENT table width; _grow_table rebuilds it
            self._sink_entries = jnp.full((self._table_width,), self._sink,
                                          jnp.int32)
            for row in range(n_slots):   # unoccupied rows start at sink
                self._cache = self._set_table(
                    self._cache, jnp.asarray(row, jnp.int32),
                    self._sink_entries)
        else:
            self.slot_model, self._cache = decode_mod.init_slot_cache(
                model, n_slots, kv_dtype=kv_dtype)
            self._host_tier = None
        # swap-to-None teardown in stop()/_die() runs after the worker
        # threads are joined/dead (happens-after, not a live race)
        # graftcheck: disable-next-line=thread-race
        self._parked = None    # admission waiting for pool pages (FIFO)
        # ---- multi-adapter LoRA bank (lora_rank > 0) --------------------
        # N tenants share the batched step: per-layer stacked A/B banks
        # ([capacity+1, ...]; index 0 = the all-zero NULL adapter, so
        # un-adapted rows are exactly the base model) plus a resident
        # [n_slots] adapter-id array.  transformer.Attention._proj applies
        # the per-row delta; registration swaps in new bank arrays
        # atomically (the driver thread reads the rebound reference at
        # its next dispatch).  S-LoRA-style; net-new beyond the reference.
        self.lora_rank = int(lora_rank or 0)
        if self.lora_rank:
            # speculation composes with LoRA since v2: the draft (model
            # or n-gram) proposes on BASE weights and the verify pass
            # applies the per-row adapter banks — any draft/adapter
            # divergence just lowers acceptance; verification corrects
            # it, so the output is still exactly the adapted model's
            cfg = model.cfg
            head_dim = cfg.d_model // cfg.n_heads
            n_kv = (cfg.n_heads if cfg.n_kv_heads is None
                    else cfg.n_kv_heads)
            self._lora_dims = {
                "query": (cfg.d_model, cfg.d_model),
                "key": (cfg.d_model, n_kv * head_dim),
                "value": (cfg.d_model, n_kv * head_dim),
                "out": (cfg.d_model, cfg.d_model)}
            L = int(lora_capacity) + 1
            self._lora_banks = {
                f"layer_{i}": {"attn": {
                    **{f"{p}_a": jnp.zeros((L, di, self.lora_rank),
                                           jnp.float32)
                       for p, (di, _) in self._lora_dims.items()},
                    **{f"{p}_b": jnp.zeros((L, self.lora_rank, do),
                                           jnp.float32)
                       for p, (_, do) in self._lora_dims.items()}}}
                for i in range(cfg.n_layers)}
            self._lora_ids = jnp.zeros((n_slots,), jnp.int32)
            self._adapters = {}          # name -> bank index
            self._free_lora = list(range(1, L))
            self._adapter_refs = {}      # index -> in-flight requests
            # prefix-cache identity: kv prefilled under an adapter
            # carries its k/v deltas, so prefix keys root on a UNIQUE
            # per-registration token (never reused — a re-registered
            # index gets a fresh token, so stale cached pages can never
            # serve a different tenant; they age out via LRU)
            self._adapter_token = {0: 0}  # bank index -> registration token
            self._token_counter = itertools.count(1)
            self._lora_lock = threading.Lock()
            self._prefill_many = decode_mod._jitted_slot_prefill_many_lora(
                self.slot_model)
            self._step = decode_mod._jitted_slot_step_lora(self.slot_model)
        else:
            self._prefill_many = decode_mod._jitted_slot_prefill_many(
                self.slot_model)
            self._step = decode_mod._jitted_slot_step(self.slot_model)
        self._set_row = decode_mod._jitted_set_row(self.slot_model)
        # ---- speculative decoding (v2: lossless for sampled rows) ------
        # spec_draft picks the proposer: "model" = a separate draft
        # transformer (requires draft_model), "ngram" = model-free
        # prompt-lookup from a per-slot on-device context table, "off" =
        # plain decode.  None keeps the historical default: model when a
        # draft was passed, off otherwise.
        mode = spec_draft
        if mode is None:
            mode = "model" if draft_model is not None else "off"
        if mode not in ("model", "ngram", "off"):
            raise ValueError(
                f"spec_draft={mode!r} not in ('model', 'ngram', 'off')")
        if mode == "model" and draft_model is None:
            raise ValueError(
                "spec_draft='model' requires a draft model "
                "(--draft_export_dir)")
        if mode == "ngram" and draft_model is not None:
            raise ValueError(
                "spec_draft='ngram' is model-free — drop the draft "
                "model (or pick spec_draft='model')")
        if mode == "off":
            draft_model = draft_params = None
        self.spec_mode = mode
        self.draft_model = self.draft_params = None
        self.draft_k = draft_k
        if draft_model is not None:
            if draft_model.cfg.vocab_size != model.cfg.vocab_size:
                raise ValueError(
                    f"draft vocab {draft_model.cfg.vocab_size} != target "
                    f"vocab {model.cfg.vocab_size}")
            self.draft_model, self.draft_params = draft_model, draft_params
            self.d_slot_model, self._d_cache = decode_mod.init_slot_cache(
                draft_model, n_slots, kv_dtype=kv_dtype)
            self._d_prefill_many = decode_mod._jitted_slot_prefill_many(
                self.d_slot_model)
        self.n_slots = n_slots
        self.max_seq = self.slot_model.cfg.max_seq_len
        if draft_model is not None:
            # both caches hold the sequence (spec-eligible requests also
            # reserve draft_k verify-overshoot headroom — in submit())
            self.max_seq = min(self.max_seq, draft_model.cfg.max_seq_len)
        if self.spec_mode == "ngram":
            # per-slot n-gram table: the row's committed tokens (prompt
            # + delivered output), resident on device so proposals and
            # commit-time appends stay inside the spec-round program
            self._spec_ctx = jnp.zeros((n_slots, self.max_seq), jnp.int32)
            self._spec_ctx_len = jnp.zeros((n_slots,), jnp.int32)
            self._set_row_ctx = decode_mod._jitted_set_row_ctx()
        # adaptive draft length: the host thread EWMAs per-row acceptance
        # (`_spec_ewma`, host-thread-owned) and publishes a suggested
        # round width through `_speck_q`; the device thread drains the
        # queue at dispatch (latest wins) into the device-thread-owned
        # `_spec_k` — cross-thread state moves only through the queue,
        # the same discipline as _retire_q
        self._spec_k = self.draft_k     # device-thread-owned round width
        self._spec_k_sum = 0            # device-thread-owned (mean-k)
        self._speck_q = queue_mod.Queue(8)
        self._spec_ewma = [1.0] * n_slots   # host-thread-owned
        self._spec_k_pub = self.draft_k     # host-thread-owned
        self.read_chunk = max(1, read_chunk)
        self.prefill_chunk = _aligned_prefill_chunk(prefill_chunk,
                                                    self.kv_page_size)
        # admission pipeline width: up to this many waiting requests
        # prefill one chunk each per batched dispatch (1 = the strict
        # sequential admission path, the parity baseline)
        self.prefill_rows = max(1, int(prefill_rows or 1))
        # Sarathi-style stall-free budget: prefill tokens dispatched
        # between two decode steps never exceed this (the head admission
        # always runs, so a single over-budget chunk cannot wedge)
        self.prefill_budget = (int(prefill_budget or 0)
                               or self.prefill_rows * self.prefill_chunk)
        self._pending = queue_mod.Queue(max_pending)
        # ---- SLO-aware multi-tenant scheduling ------------------------
        # `_pending` stays the thread-safe ingress; the device thread
        # drains it into per-class deques (`_drain_ingress`) and admits
        # from them in weighted-fair order (`_next_item`): up to
        # `prio_weight` interactive admissions per batch admission while
        # both classes wait, so a batch-heavy tenant cannot starve
        # interactive sessions but batch work never starves outright.
        if int(prio_weight) < 1:
            raise ValueError("prio_weight must be >= 1")
        self.prio_weight = int(prio_weight)
        self.preempt_ms = float(preempt_ms or 0.0)
        if self.preempt_ms < 0:
            raise ValueError("preempt_ms must be >= 0")
        self.park_capacity = int(park_capacity)
        if self.park_capacity < 1:
            raise ValueError("park_capacity must be >= 1")
        # device-thread-owned admission queues; stats() only len()s them
        # graftcheck: disable-next-line=thread-race
        self._classq = {c: collections.deque() for c in PRIORITY_CLASSES}
        self._batch_credit = 0   # interactive picks since last batch pick
        # mega-prompt lane: prompts above long_prompt_threshold queue
        # here and admit one at a time (lazy page allocation; prefill
        # streams chunk-by-chunk under long_chunk_quota).  Device-thread
        # owned; stats() only len()s it
        # graftcheck: disable-next-line=thread-race
        self._longq = collections.deque()
        self._long_credit = 0    # normal picks since last long pick
        # preemption controller state: parked sessions are frozen
        # host-side snapshots (no device pages held) awaiting resume;
        # the deque is shared between the controller thread and the
        # teardown sweeps, hence the lock
        self._park_pool = collections.deque()
        self._park_lock = threading.Lock()
        self._park_depth = Gauge()
        # fixed-length lists: cells are rebound (never resized), and the
        # generation protocol below makes stale host-side reads self-
        # invalidating — cross-thread cell access is the design
        # graftcheck: disable-next-line=thread-race
        self._slots = [None] * n_slots
        # graftcheck: disable-next-line=thread-race
        self._gen = [0] * n_slots      # occupant generation per row: tokens
        # decoded for a previous occupant must never reach a new one
        # device-thread-owned pipeline; stats() only len()s it
        # graftcheck: disable-next-line=thread-race
        self._admissions = []          # in-flight chunked admissions (the
        # prefill engine's queue; each entry is one request mid-prefill)
        # admission->first-token latency (TTFT): percentile window +
        # monotone count/sum that GET /v1/fleet aggregates
        self._ttft = LatencyWindow()
        # per-class windows: TTFT split by priority class, plus queueing
        # delay (submit -> admission pick), the preemption controller's
        # pressure signal.  count/sum are monotone and fleet-summable;
        # percentiles stay window-local
        self._ttft_cls = {c: LatencyWindow() for c in PRIORITY_CLASSES}
        self._qdelay = {c: LatencyWindow() for c in PRIORITY_CLASSES}
        # device-resident chains: ONE dispatch per decoded token
        self._toks = jnp.zeros((n_slots,), jnp.int32)
        self._temps = jnp.zeros((n_slots,), jnp.float32)
        self._seeds = jnp.zeros((n_slots,), jnp.int32)
        self._ords = jnp.zeros((n_slots,), jnp.int32)
        # per-row sampling filters (top-k / nucleus); the step only pays
        # the filter program while a filtered row is active
        self._topks = jnp.zeros((n_slots,), jnp.int32)
        self._topps = jnp.ones((n_slots,), jnp.float32)
        self._minps = jnp.zeros((n_slots,), jnp.float32)
        self._n_filtered = 0
        # per-row repetition penalty: seen-token mask + rate (1.0 =
        # disabled; rows at 1.0 are bit-exact identity even while other
        # rows penalize, since x/1.0 == x).  [n_slots, V] int8 is a few
        # hundred KB — resident unconditionally
        self._seen = jnp.zeros((n_slots, self.slot_model.cfg.vocab_size),
                               jnp.int8)
        self._reps = jnp.ones((n_slots,), jnp.float32)
        self._n_penalized = 0
        # per-row on-device stop bookkeeping: remaining token budget,
        # eos id, and whether an eos is configured.  The step decrements
        # rems and ships a `done` flag down with each token block, so
        # the host never inspects token VALUES to decide whether the
        # device may keep dispatching (the async engine's enabling
        # invariant; the serial engine runs the same program so the two
        # stay byte-identical)
        self._rems = jnp.zeros((n_slots,), jnp.int32)
        self._eoss = jnp.zeros((n_slots,), jnp.int32)
        self._eos_on = jnp.zeros((n_slots,), jnp.bool_)
        self._steps = 0
        self._spec_rounds = 0
        # device->host handoff: flushed chunks ride here; the bound IS
        # the pipeline depth (backpressure when the host falls behind)
        self._ready = queue_mod.Queue(self.pipeline_depth)
        # host->device retirement requests (row, gen, ack): _free_row
        # mutates pool/table device state, so only the device thread
        # applies it; the host blocks on the ack so a finished handle
        # always observes consistent pool accounting
        self._retire_q = queue_mod.Queue()
        # host->device migration requests (freeze/rollback).  Same ack
        # discipline as _retire_q: the device thread applies the device-
        # state half (gen bump + page gather, or row-state reinstall)
        # and the requester blocks on the ack event
        self._freeze_q = queue_mod.Queue()
        # jitted migration kernels (traced on first migration)
        if kv_page_size:
            self._gather_kv = decode_mod._jitted_gather_pages(
                self.slot_model)
            self._scatter_kv = decode_mod._jitted_scatter_pages(
                self.slot_model)
        else:
            self._gather_kv = decode_mod._jitted_gather_row_kv(
                self.slot_model)
            self._scatter_kv = decode_mod._jitted_scatter_row_kv(
                self.slot_model)
        self._set_row_index = decode_mod._jitted_set_row_index(
            self.slot_model)
        self._depth = Gauge()   # steps dispatched but not host-processed
        self._t0 = time.monotonic()   # device_idle_fraction time base
        self._dead = None     # set to the fatal exception if the loop dies
        self._stop = threading.Event()
        # requests_served lives in self.counters: the device thread counts
        # admission-time completions and the host thread counts retirement-
        # time ones, so a bare `self.requests += 1` would lose updates
        # (graftcheck thread-race caught exactly that)
        self._thread = threading.Thread(target=self._loop,
                                        name="slot-batcher", daemon=True)
        self._host_thread = None
        if engine == "async":
            self._host_thread = threading.Thread(
                target=self._host_loop, name="slot-host", daemon=True)
            self._host_thread.start()
        # the preemption controller runs on its own thread because
        # freeze_session/submit_resume both BLOCK on device-thread acks —
        # parking from the device or host loop would deadlock the engine
        self._preempt_thread = None
        if self.preempt_ms > 0:
            if draft_model is not None:
                raise ValueError(
                    "preempt_ms > 0 does not compose with draft "
                    "speculation (freeze_session cannot cut a "
                    "speculating row) — drop --draft_export_dir or "
                    "--generate_preempt_ms")
            self._preempt_thread = threading.Thread(
                target=self._preempt_loop, name="preempt-controller",
                daemon=True)
            self._preempt_thread.start()
        self._thread.start()

    def stats(self):
        """Operational snapshot for the metadata endpoint: occupancy,
        queue depth, dispatch counters, and (paged mode) pool state.
        Mostly read without locks — monotone counters and small lists
        whose momentary skew is fine for monitoring; the LoRA registry
        (a dict concurrent register_adapter calls resize) is the one
        read snapshotted under its lock."""
        out = {
            "slots_busy": sum(s is not None for s in self._slots),
            "pending": (self._pending.qsize()
                        + sum(len(q) for q in self._classq.values())),
            "admitting": bool(self._admissions),
            "admissions_inflight": len(self._admissions),
            "prefill_rows": self.prefill_rows,
            "prefill_budget": self.prefill_budget,
            "requests_served": self.counters.get("requests_served"),
            "decode_steps": self._steps,
            "spec_rounds": self._spec_rounds,
            "spec_mode": self.spec_mode,
            "engine": self.engine,
            "pipeline_depth": self.pipeline_depth,
            # high-water mark of dispatched-but-unprocessed steps: > 1
            # is the observable proof the double buffer overlapped host
            # work with device steps
            "pipeline_depth_peak": self._depth.peak,
            # explicit at zero (like kv_sink_writes): a non-zero value
            # means copy_to_host_async is unsupported here and readback
            # degraded to the synchronous path
            "copy_to_host_fallbacks": self.counters.get(
                "copy_to_host_fallbacks"),
        }
        # fraction of wall time the DEVICE thread spent blocked on host
        # work (serial: processing chunks inline; async: waiting for the
        # host to drain the full pipeline) — the quantity the async
        # engine exists to shrink
        elapsed_ms = (time.monotonic() - self._t0) * 1000.0
        wait_ms = self.counters.get("device_wait_ms")
        out["device_idle_fraction"] = (
            round(min(1.0, wait_ms / elapsed_ms), 4) if elapsed_ms > 0
            else 0.0)
        # speculative decoding: proposal/acceptance volume (monotone,
        # fleet-summable; present-at-zero so dashboards see the keys on
        # a spec-off or cold replica), the derived accept rate, the
        # adaptive round width and its running mean, and the injected-
        # fault fallback count
        for key in ("spec_tokens_proposed", "spec_tokens_accepted",
                    "spec_draft_fallbacks"):
            out[key] = self.counters.get(key)
        proposed = out["spec_tokens_proposed"]
        out["spec_accept_rate"] = (
            round(out["spec_tokens_accepted"] / proposed, 4) if proposed
            else 0.0)
        out["spec_k_current"] = self._spec_k
        out["spec_k_mean"] = (
            round(self._spec_k_sum / self._spec_rounds, 4)
            if self._spec_rounds else 0.0)
        # admission->first-token latency: count/sum (monotone, fleet-
        # aggregable) + p50/p95 over the recent window
        out.update(self._ttft.stats("ttft"))
        if self.kv_page_size:
            free = len(self._free_pages)
            out["kv_pages_free"] = free
            out["kv_pages_total"] = self._total_pages
            out["kv_pages_used"] = self._total_pages - free
            out["kv_page_size"] = self.kv_page_size
            out["paged_attn_impl"] = self.slot_model.cfg.paged_attn_impl
            out["paged_prefill_impl"] = (
                self.slot_model.cfg.paged_prefill_impl)
            # S>1 prefill path split (kernel vs blend), present-at-zero
            # so fleet totals see the keys before the first dispatch
            for key in ("prefill_kernel_dispatches",
                        "prefill_blend_fallbacks"):
                out[key] = self.counters.get(key)
            out["admission_waiting_for_pages"] = self._parked is not None
            out["prefix_pages_cached"] = len(self._prefix)
            out["prefill_tokens_shared"] = self.prefill_tokens_shared
            # hierarchical kv cache: page-granular hit accounting
            # (device-cache hits / host-tier promotions / cold-prefilled
            # full pages) plus the host tier's own gauges.  All present-
            # at-zero — fleet totals and dashboards must see them on a
            # replica that has not served a warm turn yet (or runs with
            # the tier disabled)
            for key in ("prefix_hits", "prefix_misses", "host_hits"):
                out[key] = self.counters.get(key)
            tier = self._host_tier
            tstats = tier.stats() if tier is not None else {}
            out["host_cache_bytes"] = int(
                tstats.get("host_cache_bytes", 0))
            out["host_pages_cached"] = int(
                tstats.get("host_pages_cached", 0))
            out["host_demotions"] = int(tstats.get("host_demotions", 0))
            out["host_evictions"] = int(tstats.get("host_evictions", 0))
            # demote-apply latency (worker-thread batches): exported
            # whole so /metrics renders the histogram per-replica
            for k, v in tstats.items():
                if k.startswith("host_demote_apply"):
                    out[k] = v
            # explicit (not just via the counter fold): present-at-zero
            # so dashboards see the gauge before the first sink write
            out["kv_sink_writes"] = self.counters.get("kv_sink_writes")
            # growable page tables: current global width vs the full-
            # sequence cap (width only ever grows; jit retraces once
            # per pow2 step)
            out["kv_table_width"] = self._table_width
            out["kv_table_cap"] = self._table_cap
        if self.lora_rank:
            out["lora_rank"] = self.lora_rank
            # the one mutable-container read: snapshot under _lora_lock so
            # a concurrent register_adapter cannot resize the dict
            # mid-iteration ("dictionary changed size during iteration")
            with self._lora_lock:
                adapters = sorted(self._adapters)
                free = len(self._free_lora)
            out["lora_adapters"] = adapters
            out["lora_capacity_free"] = free
        if self.kv_dtype:
            out["kv_dtype"] = self.kv_dtype
        # migration counters: present-at-zero (fleet_stats sums them
        # across replicas like the TTFT keys, and dashboards should see
        # the gauges before the first handoff)
        for key in ("migrations_started", "migrations_completed",
                    "migrations_failed", "kv_pages_exported"):
            out[key] = self.counters.get(key)
        # scheduling: per-class latency windows plus preemption state.
        # All present-at-zero so fleet aggregation never sees a replica
        # with a missing class key
        out["priority_weight"] = self.prio_weight
        out["preempt_ms"] = self.preempt_ms
        out["park_capacity"] = self.park_capacity
        with self._park_lock:
            out["parked_sessions"] = len(self._park_pool)
        out["parked_sessions_peak"] = self._park_depth.peak
        for key in ("sessions_parked", "sessions_unparked", "park_spills",
                    "park_restore_failures"):
            out[key] = self.counters.get(key)
        for cls in PRIORITY_CLASSES:
            out.update(self._ttft_cls[cls].stats(f"ttft_{cls}"))
            out.update(self._qdelay[cls].stats(f"qdelay_{cls}"))
        # mega-prompt lane: present-at-zero counters (fleet totals sum
        # them) plus a skew-tolerant active gauge — queued, mid-prefill,
        # and decoding long prompts all count as "active"
        for key in ("kv_table_grows", "kv_pages_demoted_overflow",
                    "long_chunks_dispatched"):
            out[key] = self.counters.get(key)
        out["long_prompt_threshold"] = self.long_prompt_threshold
        n_long = len(self._longq)
        n_long += sum(1 for adm in list(self._admissions)
                      if (adm.get("item") or {}).get("long"))
        n_long += sum(1 for s in list(self._slots)
                      if s is not None and (s.get("item") or {}).get("long"))
        out["long_prompts_active"] = n_long
        out.update(self.trace.stats())
        # event counters (kv_sink_writes, ...) ride along by name
        out.update(self.counters.snapshot())
        return out

    # ---- multi-adapter LoRA registry ------------------------------------

    def register_adapter(self, name, adapters, scale=1.0):
        """Install a LoRA adapter under `name` (requests select it via
        ``submit(..., adapter=name)``).  `adapters` is the
        `lora.init`-shaped tree ({"layer_i/attn/proj/kernel": {"a", "b"}},
        attention projections only — the bank lives in Attention); `scale`
        (alpha/rank) folds into the stored b.  Paths the adapter does not
        cover stay zero (no delta).  Thread-safe; visible to the decode
        loop from its next dispatch."""
        import jax.numpy as jnp

        if not self.lora_rank:
            raise ValueError("no LoRA bank configured (lora_rank=0; pass "
                             "lora_rank / --generate_lora_rank)")
        by_slot = {}
        for path, ab in adapters.items():
            parts = path.split("/")
            if (len(parts) != 4 or parts[1] != "attn"
                    or parts[0] not in self._lora_banks
                    or parts[2] not in self._lora_dims
                    or parts[3] != "kernel"):
                raise ValueError(
                    f"adapter path {path!r} is not an attention projection "
                    "of this model (expected layer_<i>/attn/"
                    "<query|key|value|out>/kernel)")
            a, b = ab["a"], ab["b"]
            di, do = self._lora_dims[parts[2]]
            if a.shape != (di, self.lora_rank) or \
                    b.shape != (self.lora_rank, do):
                raise ValueError(
                    f"adapter {path!r} shapes a{tuple(a.shape)} "
                    f"b{tuple(b.shape)} do not match bank "
                    f"([{di}, {self.lora_rank}], [{self.lora_rank}, {do}])")
            by_slot[(parts[0], parts[2])] = (a, b)
        with self._lora_lock:
            if name in self._adapters:
                raise ValueError(f"adapter {name!r} already registered")
            if not self._free_lora:
                raise ValueError(
                    f"adapter bank full ({len(self._adapters)} registered; "
                    "raise lora_capacity / --generate_lora_capacity)")
            idx = self._free_lora.pop()
            try:
                banks = self._lora_banks
                new = {}
                for layer, sub in banks.items():
                    attn = dict(sub["attn"])
                    for proj in self._lora_dims:
                        ab = by_slot.get((layer, proj))
                        if ab is None:   # uncovered: zero this index
                            attn[f"{proj}_a"] = \
                                attn[f"{proj}_a"].at[idx].set(0.0)
                            attn[f"{proj}_b"] = \
                                attn[f"{proj}_b"].at[idx].set(0.0)
                        else:
                            a, b = ab
                            attn[f"{proj}_a"] = attn[f"{proj}_a"].at[idx].set(
                                jnp.asarray(a, jnp.float32))
                            attn[f"{proj}_b"] = attn[f"{proj}_b"].at[idx].set(
                                jnp.asarray(b, jnp.float32) * float(scale))
                    new[layer] = {"attn": attn}
            except BaseException:
                # lifecycle-leak: a device OOM (or bad array) mid-build
                # must not strand the popped bank index outside the pool
                self._free_lora.append(idx)
                raise
            self._lora_banks = new       # atomic rebind: the driver thread
            self._adapters[name] = idx   # picks it up at its next dispatch
            self._adapter_refs.setdefault(idx, 0)
            # fresh prefix-cache identity for this registration (paged
            # mode): pages prefilled under a PREVIOUS tenant of this
            # index must never serve the new one
            self._adapter_token[idx] = next(self._token_counter)
        logger.info("registered LoRA adapter %r at bank index %d "
                    "(%d paths, scale %.3g)", name, idx, len(adapters),
                    scale)
        return idx

    def unregister_adapter(self, name):
        """Remove `name`; refuses while requests using it are in flight
        (their rows would silently decode under a freed/reused index)."""
        with self._lora_lock:
            idx = self._adapters.get(name)
            if idx is None:
                raise ValueError(f"adapter {name!r} is not registered")
            if self._adapter_refs.get(idx, 0) > 0:
                raise ValueError(
                    f"adapter {name!r} has {self._adapter_refs[idx]} "
                    "requests in flight")
            del self._adapters[name]
            self._free_lora.append(idx)

    def _release_adapter(self, idx):
        with self._lora_lock:
            self._adapter_refs[idx] = max(
                0, self._adapter_refs.get(idx, 0) - 1)

    def stop(self, timeout=30):
        """Shut the engine threads down cleanly (benches/tests teardown):
        both loops exit at their next iteration boundary; queued,
        in-flight, AND mid-admission requests fail with RuntimeError."""
        self._stop.set()
        self._thread.join(timeout)
        if self._host_thread is not None:
            self._host_thread.join(timeout)
        if self._preempt_thread is not None:
            self._preempt_thread.join(timeout)
        err = RuntimeError("batcher stopped")
        self._dead = self._dead or err
        adms, self._admissions = self._admissions, []
        for adm in adms:
            adm["item"]["h"]._fail(err)
        parked, self._parked = self._parked, None
        if parked is not None:
            parked[1]["h"]._fail(err)
        for s in self._slots:
            if s is not None:
                s["handle"]._fail(err)
        self._slots = [None] * self.n_slots
        self._drain_pending(err)
        self._sweep_park_pool(err)
        self._ack_retire_waiters()
        if self._host_tier is not None:
            self._host_tier.close()

    def _ack_retire_waiters(self):
        """Release any host-side `_retire` waiter after the device thread
        is gone (stop/death): their rows are already failed; leaving the
        acks unset would hang the host thread forever."""
        import queue as queue_mod

        while True:
            try:
                _, _, ev = self._retire_q.get_nowait()
            except queue_mod.Empty:
                break
            ev.set()
        while True:   # freeze/rollback waiters hang the same way
            try:
                entry = self._freeze_q.get_nowait()
            except queue_mod.Empty:
                return
            entry[-1].set()

    def submit(self, prompt, max_new, temperature=0.0, eos_id=None, seed=0,
               adapter=None, top_k=0, top_p=1.0, min_p=0.0, stop=None,
               repetition_penalty=1.0, priority=None, trace_id=None):
        if self._dead is not None:
            raise RuntimeError(f"batcher died: {self._dead}")
        # tracing is best-effort by construction: a malformed id is
        # dropped here rather than 400ing a generation that would
        # otherwise succeed (byte-parity with the untraced request)
        tid = trace_id if trace.valid_id(trace_id) else None
        cls = priority or "interactive"
        if cls not in PRIORITY_CLASSES:
            raise ValueError(
                f"priority={priority!r} not in {PRIORITY_CLASSES}")
        if adapter is not None and not self.lora_rank:
            raise ValueError(
                "this server has no LoRA bank (start it with "
                "--generate_lora_rank and --generate_lora)")
        if not (_is_int(top_k) and 0 <= top_k < (1 << 31)):
            # the upper bound matters: these become int32 device scalars
            # on the single driver thread, where an overflow would brick
            # the whole engine instead of 400ing one request (and bools
            # are excluded: JSON true would silently mean top_k=1)
            raise ValueError(f"top_k={top_k!r} must be an int32 >= 0")
        if not 0.0 < top_p <= 1.0:
            raise ValueError(f"top_p={top_p!r} must be in (0, 1]")
        if not 0.0 <= min_p < 1.0:
            raise ValueError(f"min_p={min_p!r} must be in [0, 1)")
        if (top_k or top_p < 1.0 or min_p > 0.0) and temperature <= 0:
            raise ValueError("top_k/top_p/min_p filter the SAMPLED "
                             "distribution — they require temperature > 0")
        stops = []
        for st in (stop or []):
            if (not isinstance(st, (list, tuple)) or not st
                    or not all(_is_int(t) for t in st)):
                raise ValueError('"stop" must be a list of non-empty '
                                 "token-id lists")
            stops.append(list(st))
        if len(stops) > 16 or any(len(st) > 32 for st in stops):
            raise ValueError("at most 16 stop sequences of at most 32 "
                             "tokens each")
        if not 0 < repetition_penalty <= 1e6:
            # the finite cap matters: inf times a zero-valued seen logit
            # is NaN, poisoning the row's pick instead of 400ing here
            raise ValueError(
                f"repetition_penalty={repetition_penalty!r} must be in "
                "(0, 1e6] (1.0 disables; >1 discourages repeats)")
        # spec-eligible requests on a speculating server need draft_k
        # cache headroom for the verify overshoot.  Since v2 sampled
        # rows speculate too (rejection-sampled verification), so only
        # repetition-penalized requests — which disable spec rounds
        # while active and never speculate — keep the full window
        headroom = (self.draft_k if (self.spec_mode != "off"
                                     and repetition_penalty == 1.0) else 0)
        if len(prompt) + max_new + headroom > self.max_seq:
            raise ValueError(
                f"prompt {len(prompt)} + max_new_tokens {max_new}"
                + (f" + speculation headroom {headroom}" if headroom else "")
                + f" exceeds max_seq_len {self.max_seq}")
        if self.kv_page_size:
            need = self._pages_needed(len(prompt), max_new,
                                      rep=repetition_penalty)
            if need > self._total_pages:
                # a request the WHOLE pool cannot hold would park forever
                # at the head of the line, wedging every later admission
                raise ValueError(
                    f"request needs {need} kv pages but the pool only "
                    f"has {self._total_pages}; raise --generate_kv_pages "
                    "or shorten the request")
        # resolve the adapter LAST: the in-flight refcount must only be
        # taken once every validation above has passed (a rejected
        # request would otherwise leak its ref and wedge unregister)
        aidx = 0
        if adapter is not None:
            with self._lora_lock:
                if adapter not in self._adapters:
                    raise ValueError(
                        f"unknown adapter {adapter!r}; registered: "
                        f"{sorted(self._adapters)}")
                aidx = self._adapters[adapter]
                self._adapter_refs[aidx] = self._adapter_refs.get(aidx,
                                                                  0) + 1
        h = SlotHandle(prompt)
        if aidx:
            h._on_done = lambda idx=aidx: self._release_adapter(idx)
        # mega-prompt lane flag: decided ONCE at submit (threshold reads
        # are config, not state) so every later hop — ingress drain, WFQ
        # pick, lazy allocation, chunk quota — keys off the item itself
        is_long = bool(self.long_prompt_threshold and self.kv_page_size
                       and len(prompt) > self.long_prompt_threshold)
        self._pending.put({
            "h": h, "prompt": list(prompt), "max_new": max_new,
            "temp": float(temperature), "eos": eos_id, "seed": int(seed),
            "aidx": aidx, "topk": int(top_k), "topp": float(top_p),
            "minp": float(min_p), "stops": stops,
            "rep": float(repetition_penalty), "adapter": adapter,
            "cls": cls, "long": is_long, "trace": tid,
            "t_submit": time.monotonic()})  # TTFT clock starts at submit
        self.trace.event(tid, "submit", cls=cls, prompt_len=len(prompt),
                         max_new=max_new)
        if self._dead is not None:
            # the loop may have died between the check above and the put
            # (its death-drain already ran): fail whatever is queued,
            # including our own item, so no handler blocks forever
            self._drain_pending(RuntimeError(f"batcher died: {self._dead}"))
        return h

    def _drain_pending(self, err):
        import queue as queue_mod

        # class queues first (older items — they were pulled off
        # `_pending` already), then the mega-prompt lane, then the raw
        # ingress queue
        for q in self._classq.values():
            while q:
                q.popleft()["h"]._fail(err)
        while self._longq:
            self._longq.popleft()["h"]._fail(err)
        while True:
            try:
                item = self._pending.get_nowait()
            except queue_mod.Empty:
                return
            item["h"]._fail(err)

    # ---- device loop (single driver thread owns the cache) --------------

    def _pick_first(self, logits_row, temperature, seed, top_k=0,
                    top_p=1.0, min_p=0.0, rep=1.0, prompt=None):
        import jax
        import jax.numpy as jnp

        from .models import decode as decode_mod

        if rep != 1.0:
            # first token's penalty sees the prompt tokens (the shared
            # seen-state the solo paths start from)
            seen = decode_mod.seen_from_prompt(
                jnp.asarray([prompt], jnp.int32), logits_row.shape[-1])
            logits_row = decode_mod.apply_repetition_penalty(
                logits_row[None, :], seen,
                jnp.asarray([rep], jnp.float32))[0]
        # THE solo pick (decode._solo_pick_fn — one implementation, not a
        # re-derivation): ordinal 0 of the shared key schedule, so the
        # first slot token matches a solo generate(rng=key(seed))
        # including its filters
        pick = decode_mod._solo_pick_fn(temperature, top_k, top_p, min_p)
        # deliberate sync: the admission path needs the first token as a
        # Python int before the row joins the decode chain (TTFT delivery
        # + stop-sequence check); one readback per ADMISSION, not per step
        # graftcheck: disable-next-line=hostsync
        return int(pick(logits_row[None, :],
                        jax.random.fold_in(jax.random.key(seed), 0))[0])

    @staticmethod
    def _hit_stop(seq, stops, gen_start):
        """True when `seq` ends with any of the request's stop token
        sequences, matched ENTIRELY within the generated region (a stop
        straddling the prompt/generation boundary does not count —
        standard serving semantics).  Checked after every appended
        token; matched stop tokens stay in the output, like eos."""
        return any(len(seq) - len(st) >= gen_start
                   and seq[-len(st):] == st for st in stops)

    def _prefill_chunk_sizes(self, length):
        """Split a prompt into chunk lengths: full `prefill_chunk` pieces
        with a bucket-padded tail (power-of-2 buckets bound compile
        variants)."""
        sizes = []
        rest = length
        while rest > self.prefill_chunk:
            sizes.append(self.prefill_chunk)
            rest -= self.prefill_chunk
        sizes.append(rest)
        return sizes

    def _pages_needed(self, prompt_len, max_new, rep=1.0):
        # verify-overshoot headroom: every spec-eligible request (see
        # submit — only penalized rows are exempt since v2)
        headroom = (self.draft_k if (self.spec_mode != "off"
                                     and rep == 1.0) else 0)
        return -(-(prompt_len + max_new + headroom) // self.kv_page_size)

    # ---- prefix cache (paged mode) --------------------------------------
    # Page-granular KV reuse: a full prompt page whose CUMULATIVE token
    # prefix was already computed by an earlier request maps to the same
    # pool page read-only (causal attention + absolute rope make prefix
    # kv a pure function of the prefix tokens, so reuse is exact).  A
    # row's prefill then starts AFTER its shared pages — a repeated
    # prompt admits with ~zero prefill compute.  Shared pages are
    # refcounted; at rc==0 they stay cached (evicted LRU only under pool
    # pressure).  At most len(prompt)-1 tokens can be shared: the last
    # prompt token must run through prefill to produce the first-token
    # logits.

    def _prefix_keys(self, prompt, upto_tokens, root=()):
        """Rolling cumulative-prefix keys for each FULL page up to
        `upto_tokens` (exclusive page count bound).  Keys are NESTED
        TUPLES (prev_key, page_tokens) — structural equality makes the
        cache lookup EXACT (hash() alone would let two colliding
        prefixes serve each other's kv: silent wrong output and
        cross-request content leakage); structure sharing keeps each
        key O(1) extra memory.  ``root`` seeds the chain with the
        request's LoRA identity: adapter-prefilled kv carries that
        adapter's k/v deltas, so pages are only ever shared between
        requests of the same registration (base requests keep the empty
        root and the exact pre-LoRA keys)."""
        P = self.kv_page_size
        keys, k = [], root
        n_full = upto_tokens // P
        for i in range(n_full):
            k = (k, tuple(prompt[i * P:(i + 1) * P]))
            keys.append(k)
        return keys

    def _lora_prefix_root(self, aidx):
        """Prefix-key root for bank index `aidx`: () for the base model;
        a never-reused per-registration token otherwise (a re-registered
        index gets a fresh token, so stale cached pages can never serve
        a different tenant — they just age out via LRU)."""
        if not self.lora_rank or not aidx:
            return ()
        # registration threads rewrite the token map under _lora_lock
        # (register_adapter); take it for the read too — dict.get during a
        # concurrent insert is not guaranteed safe across interpreters
        with self._lora_lock:
            return ("lora", self._adapter_token.get(aidx, -1))

    def _prefix_lookup(self, prompt, root=()):
        """(shared_pages, keys_for_all_full_pages): the longest cached
        run of full prompt pages, capped at len(prompt)-1 tokens."""
        keys = self._prefix_keys(prompt, len(prompt) - 1, root=root)
        shared = []
        for key in keys:
            page = self._prefix.get(key)
            if page is None:
                break
            shared.append(page)
            self._lru_tick += 1
            self._prefix_lru[key] = self._lru_tick
        return shared, keys

    def _evict_cached_pages(self, want):
        """Free up to `want` pages by evicting rc==0 cached prefix pages,
        least recently used first.  Returns number freed.  With the host
        tier enabled, victims DEMOTE before their pool pages are reused:
        the gather snapshots their bytes into fresh buffers, so the tier
        keeps serving the prefix after the device copy is overwritten."""
        evictable = sorted(
            (k for k, p in self._prefix.items()
             if self._page_rc.get(p, 0) == 0),
            key=lambda k: self._prefix_lru.get(k, 0))
        victims = [(k, self._prefix[k]) for k in evictable[:max(0, want)]]
        if not victims:
            return 0
        self._demote_pages([k for k, _ in victims],
                           [p for _, p in victims])
        for key, page in victims:
            self._prefix.pop(key)
            self._prefix_lru.pop(key, None)
            self._page_rc.pop(page, None)
            self._free_pages.append(page)
        return len(victims)

    def _demote_pages(self, keys, pages):
        """Device thread: snapshot `pages` (still device-valid — the
        caller frees them only AFTER this returns) and hand them to the
        host tier.  One batched gather covers every victim; the jitted
        take produces fresh buffers, and copy_to_host_async starts the
        device->host move under the continuing decode steps so the
        tier's worker mostly finds the bytes waiting.  Best-effort by
        design: any failure just means those prefixes run cold later."""
        tier = self._host_tier
        if tier is None or not keys:
            return
        todo = [(k, p) for k, p in zip(keys, pages)
                if not tier.contains(k)]
        if not todo or faults.deny("serve.host_demote"):
            return
        import jax.numpy as jnp

        n = len(todo)
        width = _pow2_width(n)
        ids = jnp.asarray([p for _, p in todo]
                          + [self._sink] * (width - n), jnp.int32)
        try:
            kv = self._gather_kv(self._cache, ids)
        except Exception:
            logger.warning("host-tier demote gather failed",
                           exc_info=True)
            return
        for arr in kv.values():
            try:
                arr.copy_to_host_async()
            except (AttributeError, NotImplementedError):
                self.counters.inc("copy_to_host_fallbacks")
                break
        tier.demote([k for k, _ in todo], kv, n)

    def drop_prefix_cache(self, timeout_s=30.0):
        """Evict every rc==0 page from the DEVICE prefix cache (each
        full-prefix page demotes to the host tier first when it is
        armed); pages still shared with live rows stay.  Thread-safe:
        from any host thread this posts a device-loop op and blocks on
        the ack.  Ops hook: after it a returning conversation can only
        be served by host->device promotion, and an operator can use it
        to return a quiesced replica's pool to 100% free.  Returns the
        number of pages evicted."""
        if not self.kv_page_size:
            return 0
        if self._dead is not None:
            raise RuntimeError(f"batcher died: {self._dead}")
        if threading.current_thread() is self._thread:
            # device thread: apply in place
            return self._evict_cached_pages(self._total_pages)
        box = {}
        ev = threading.Event()
        self._freeze_q.put(("drop_prefix", box, ev))
        deadline = time.monotonic() + timeout_s
        while not ev.wait(0.05):
            if self._stop.is_set() or self._dead is not None:
                return 0    # device thread gone: stop()/death drains acks
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"prefix-cache drop did not land in {timeout_s:.1f}s")
        return box.get("n", 0)

    def _host_tier_lookup(self, keys, start):
        """The contiguous run of host-tier pages extending a device
        prefix-cache run of `start` pages: ``[(key, blocks), ...]``.
        The entries stay cached until the promote COMMITS (peek, not
        pop) — a parked admission must not strand pages outside both
        tiers."""
        tier = self._host_tier
        if tier is None or start >= len(keys):
            return []
        run = []
        for key in keys[start:]:
            blocks = tier.peek(key)
            if blocks is None:
                break
            run.append((key, blocks))
        if run and faults.deny("serve.host_promote"):
            return []        # tier reads as cold; prefill runs normally
        return run

    def host_prefix_provider(self, tokens, page_size):
        """``kv:prefix`` pull path (PageServer callback): the longest
        host-tier run of full-page prefixes of `tokens`, flattened to
        kvtransfer wire blocks.  Base-model keys only — LoRA roots are
        replica-local registration tokens, so adapter pages never match
        across replicas (exactly the tenant-isolation property the
        per-registration root exists for)."""
        from . import kvtier as kvtier_mod

        meta = {"kind": "prefix", "page_size": int(self.kv_page_size),
                "n_pages": 0}
        tier = self._host_tier
        if (tier is None or not self.kv_page_size
                or int(page_size) != self.kv_page_size):
            return meta, {}
        keys = self._prefix_keys(list(tokens), len(tokens))
        blocks, n = {}, 0
        for i, key in enumerate(keys):
            page = tier.peek(key)
            if page is None:
                break
            for path, arr in page.items():
                blocks[kvtier_mod.block_name(i, path)] = arr
            n += 1
        meta["n_pages"] = n
        return meta, blocks

    def prefetch_prefix(self, peer, prompt, trace_id=None):
        """HTTP-thread warm-up for a gateway-planted kv peer
        (``X-Fleet-KV-Peer``): pull the prefix pages the local host
        tier lacks from the peer's PageServer and insert them, so this
        request's admission promotes them instead of prefilling.  Pure
        pre-warming — any failure (or a cold peer) inserts nothing and
        admission falls through to normal prefill.  Returns the number
        of pages inserted."""
        tier = self._host_tier
        if tier is None or not self.kv_page_size or not peer:
            return 0
        host, _, port = str(peer).rpartition(":")
        if not host or not port.isdigit():
            logger.warning("ignoring malformed X-Fleet-KV-Peer %r", peer)
            return 0
        keys = self._prefix_keys(prompt, len(prompt) - 1)
        start = 0
        for key in keys:       # skip the locally-warm head of the run
            if not tier.contains(key):
                break
            start += 1
        if start >= len(keys):
            return 0
        from . import kvtransfer

        t0 = time.monotonic()
        try:
            meta, pages = kvtransfer.pull_prefix(
                (host, int(port)),
                prompt[:len(keys) * self.kv_page_size],
                self.kv_page_size)
        except (OSError, ValueError) as e:
            self.counters.inc("prefix_pull_failures")
            self.trace.span_at(trace_id, "prefix_pull", t0,
                               time.monotonic(), peer=str(peer),
                               failed=True)
            logger.debug("kv peer prefix pull failed: %s", e)
            return 0
        n = 0
        for i, page in enumerate(pages):
            if i >= len(keys):
                break
            if tier.put(keys[i], page):
                n += 1
        if n:
            self.counters.inc("prefix_pull_pages", n)
        self.trace.span_at(trace_id, "prefix_pull", t0, time.monotonic(),
                           peer=str(peer), pages=n)
        return n

    def _assert_no_sink(self, pages):
        """The sink page absorbs garbage writes from EVERY free row and
        every bucket-padded prefill overshoot: handing it to a request
        would let that garbage corrupt live kv (decode.init_paged_slot_
        cache caller contract).  Every allocation passes through here;
        a trip means the free list / prefix cache was corrupted."""
        assert self._sink not in pages, (
            f"page allocator handed out the reserved sink page "
            f"{self._sink} (allocated {pages}); the free list or prefix "
            f"cache is corrupted — the sink must never be owned by a row")
        return pages

    def _row_entries(self, pages):
        """One row's page-table entries at the CURRENT table width:
        `pages` then sink padding for the unallocated tail (never page
        0 — that may belong to someone)."""
        import jax.numpy as jnp

        return jnp.asarray(
            pages + [self._sink] * (self._table_width - len(pages)),
            jnp.int32)

    def _map_row(self, row, pages, spare=0):
        """Point `row`'s table at `pages`, widening every table first
        where `pages` and `spare` sink entries past them outrun it.
        A lane row passes ``spare=1`` until its last chunk: it sits
        through other rows' decode rounds, each writing at ITS
        cache_index too, just past its pages, and the write path clamps
        an overshoot to the table's LAST entry — which must then be the
        sink, not the row's own last page."""
        import jax.numpy as jnp

        if len(pages) + spare > self._table_width:
            self._grow_table(len(pages) + spare)
        self._cache = self._set_table(self._cache,
                                      jnp.asarray(row, jnp.int32),
                                      self._row_entries(pages))

    def _grow_table(self, need):
        """Widen every row's page table to cover `need` entries: pow2
        geometric steps (at least doubling) clamped at the full-
        sequence cap, so the step jit retraces O(log cap) times over
        the replica's lifetime — same bounded-compile-variants
        reasoning as `_bucket_len`.  New tail entries alias the sink
        (decode._jitted_grow_page_table), so rows mid-decode are
        untouched: growth changes no mapped page.  Device thread
        only; callers keep it inside their allocation rollback scope
        (a raise here must conserve the pool like any other
        allocation failure)."""
        import jax.numpy as jnp

        from .models import decode as decode_mod

        faults.check("serve.table_grow")
        new_w = min(self._table_cap,
                    max(_pow2_width(need), 2 * self._table_width))
        if new_w <= self._table_width:
            return
        grow = decode_mod._jitted_grow_page_table(self.slot_model, new_w)
        self._cache = grow(self._cache,
                           jnp.asarray(self._sink, jnp.int32))
        self._table_width = new_w
        self._sink_entries = jnp.full((new_w,), self._sink, jnp.int32)
        self.counters.inc("kv_table_grows")

    def _overflow_reclaim(self, want):
        """Mega-prompt overflow valve: free up to `want` pool pages by
        evicting cold (rc==0) prefix-cache pages, least recently used
        first.  With the host tier armed the victims DEMOTE before
        their pool pages are reused (`_evict_cached_pages`), so they
        promote back on a later prefix hit instead of re-prefilling.
        Returns the number freed; 0 under a `serve.overflow_demote`
        fault (the lane then stalls or fails typed — admission never
        wedges)."""
        if want <= 0:
            return 0
        if faults.deny("serve.overflow_demote"):
            return 0
        freed = self._evict_cached_pages(want)
        if freed:
            self.counters.inc("kv_pages_demoted_overflow", freed)
        return freed

    def _ensure_long_pages(self, adm):
        """Mega-prompt lane lazy allocation: map pool pages covering
        the positions `adm`'s NEXT chunk writes (plus the decode tail
        when that chunk is final — decode allocates nothing after
        admission).  Returns False when the chunk cannot run this
        round: either a transient stall (other rows will retire and
        free pages) or — when the replica is otherwise IDLE and still
        cannot cover the need even after the overflow valve — a
        definitive failure that fails the request with a typed
        KVOverflowError instead of wedging the lane forever."""
        if adm["di"] < len(adm["d_sizes"]):
            return True     # draft catch-up: dense draft cache, no pages
        item, row = adm["item"], adm["row"]
        upto = adm["offset"] + adm["sizes"][adm["i"]]
        final = upto >= len(adm["src"])
        if final:
            need = self._pages_needed(len(item["prompt"]),
                                      item["max_new"],
                                      rep=item["rep"])
        else:
            need = -(-upto // self.kv_page_size)
        have = len(self._row_pages[row] or [])
        if need <= have:
            return True
        k = need - have
        if len(self._free_pages) < k:
            self._overflow_reclaim(k - len(self._free_pages))
        if len(self._free_pages) < k:
            if (all(s is None for s in self._slots)
                    and len(self._admissions) <= 1
                    and self._parked is None):
                # nothing left to retire, nothing left to evict: no
                # future round can do better — fail loud and typed
                self._admissions.remove(adm)
                self._free_row(row)
                item["h"]._fail(KVOverflowError(
                    f"mega-prompt needs {k} more kv pages but only "
                    f"{len(self._free_pages)} are free with the replica "
                    "otherwise idle; raise --generate_kv_pages or "
                    "--generate_host_cache_mb"))
                return False
            return False    # stall this round; decode keeps retiring
        fresh = [self._free_pages.pop() for _ in range(k)]
        try:
            pages = self._assert_no_sink(
                (self._row_pages[row] or []) + fresh)
            self._map_row(row, pages, spare=0 if final else 1)
        except BaseException:
            # conservation: a grow kill / device OOM between the pops
            # and the table write must not strand the fresh pages
            self._free_pages.extend(fresh)
            raise
        self._row_pages[row] = pages
        return True

    def _try_allocate(self, row, item, lazy=False):
        """Reserve `item`'s page need for `row` — reusing cached prefix
        pages where the prompt matches — or False when the pool (after
        LRU eviction of unreferenced cached pages) cannot cover the
        rest; the caller parks the item until pages free.

        ``lazy`` (the mega-prompt lane): map only the already-computed
        pages (device prefix hits + host-tier promotions) now; FRESH
        pages are allocated chunk-by-chunk as the lane's prefill
        advances (`_ensure_long_pages`), so admitting a 100k-token
        prompt does not reserve its whole footprint up front."""
        if faults.deny("serve.alloc"):
            return False

        prompt, max_new = item["prompt"], item["max_new"]
        need = self._pages_needed(len(prompt), max_new, rep=item["rep"])
        shared, keys = self._prefix_lookup(
            prompt, root=self._lora_prefix_root(item["aidx"]))
        # hold refs BEFORE any eviction: rc==0 shared pages would
        # otherwise be evictable by our own eviction pass, get re-popped
        # as "fresh", and end up mapped twice in this row's table
        # (corrupted kv + a permanently leaked page via negative rc)
        for page in shared:
            self._page_rc[page] = self._page_rc.get(page, 0) + 1
        # host-tier promotion: a run of demoted pages extending the
        # device-cache run fills from the host copies instead of
        # prefilling — they occupy FRESH pool pages (popped below), get
        # scattered, and re-enter the prefix cache at rc=1
        host_run = self._host_tier_lookup(keys, len(shared))
        if lazy:
            need = len(shared) + len(host_run)
        fresh_need = need - len(shared)
        if len(self._free_pages) < fresh_need:
            self._evict_cached_pages(fresh_need - len(self._free_pages))
        if len(self._free_pages) < fresh_need:
            for page in shared:                  # roll back before parking
                self._page_rc[page] -= 1
            return False
        fresh = [self._free_pages.pop() for _ in range(fresh_need)]
        promo = fresh[:len(host_run)]
        try:
            pages = self._assert_no_sink(shared + fresh)
            self._map_row(row, pages, spare=1 if lazy else 0)
            if host_run:
                self._promote_scatter(promo, host_run)
        except BaseException:
            # lifecycle-leak: a device OOM (or the sink assert) between
            # the pops and the table write must not strand the fresh
            # pages outside the pool or hold phantom refs on the shared
            # ones — the pool must conserve free+owned+cached+sink
            self._free_pages.extend(fresh)
            for page in shared:
                self._page_rc[page] -= 1
            raise
        # row bookkeeping only after the slot table committed, so a
        # failed allocation leaves no row state behind.  Promoted pages
        # publish into the prefix cache NOW (rc=1, this row): the kv is
        # resident and key-exact, so a concurrent twin shares it like
        # any cached page; the host copy retires (it would go stale
        # relative to LRU bookkeeping, and re-demotion recreates it)
        for (key, _), page in zip(host_run, promo):
            self._prefix[key] = page
            self._lru_tick += 1
            self._prefix_lru[key] = self._lru_tick
            self._page_rc[page] = 1
            self._host_tier.discard(key)
        n_shared = len(shared) + len(host_run)
        self._row_pages[row] = pages
        self._row_shared_n[row] = n_shared
        self._row_prefix_keys[row] = keys        # for post-prefill registration
        self.prefill_tokens_shared += n_shared * self.kv_page_size
        if shared:
            self.counters.inc("prefix_hits", len(shared))
        if host_run:
            self.counters.inc("host_hits", len(host_run))
            self.trace.event(item.get("trace"), "promote", row=row,
                             pages=len(host_run))
        if len(keys) > n_shared:
            self.counters.inc("prefix_misses", len(keys) - n_shared)
        return True

    def _promote_scatter(self, promo, host_run):
        """Device thread: upload `host_run`'s tier blocks into the
        freshly allocated pool pages `promo` (sink-padded pow2 ids for
        compile reuse, like the migration scatter).  Bit-exact: the
        blocks are gather copies at the pool dtype, so astype in the
        scatter is the identity."""
        import numpy as np

        import jax.numpy as jnp

        n = len(host_run)
        width = _pow2_width(n)
        ids = jnp.asarray(list(promo) + [self._sink] * (width - n),
                          jnp.int32)
        blocks = {}
        for path in host_run[0][1]:
            stacked = np.stack([blk[path] for _, blk in host_run])
            if width > n:
                # pad rows land in the sink; their content is ignored
                pad = np.broadcast_to(stacked[-1:],
                                      (width - n,) + stacked.shape[1:])
                stacked = np.concatenate([stacked, pad], axis=0)
            blocks[path] = stacked
        self._cache = self._scatter_kv(self._cache, ids, blocks)

    def _register_prefix_pages(self, row):
        """After `row`'s prefill completed, publish its freshly computed
        full-prefix pages into the cache so later identical prompts can
        share them.  Invariant: a page is prefix-managed iff it is in
        ``_page_rc``; the count is the number of LIVE rows using it (the
        cache may hold rc==0 pages until eviction).  A concurrent twin
        that lost the registration race keeps its copy exclusively owned
        (freed normally at retirement)."""
        keys = self._row_prefix_keys[row] or []
        pages = self._row_pages[row] or []
        for i, key in enumerate(keys):
            if i >= len(pages):
                break
            if i < self._row_shared_n[row]:
                continue                 # already managed + held by us
            if key not in self._prefix:
                self._prefix[key] = pages[i]
                self._lru_tick += 1
                self._prefix_lru[key] = self._lru_tick
                self._page_rc[pages[i]] = 1   # this row's live reference

    def _free_row(self, row):
        """Retire `row`: release prefix-cached pages (rc--; they STAY
        cached at rc==0 for future reuse), return exclusively-owned
        pages to the free list, and point the row's table at the sink
        page so post-retirement garbage decode can never write into
        pages a later owner holds (paged mode; no-op otherwise)."""
        import jax.numpy as jnp

        s = self._slots[row]
        if s is not None and s.get("filtered"):
            self._n_filtered -= 1
        if s is not None and s.get("pen"):
            self._n_penalized -= 1
            self._reps = self._reps.at[row].set(1.0)  # identity for the
            # row's garbage decode AND for the next (unpenalized) tenant
        self._slots[row] = None
        if self.lora_rank:
            # back to the null adapter: the freed row's garbage decode
            # runs the base model (harmless either way — its tokens are
            # dropped by the generation filter)
            self._lora_ids = self._lora_ids.at[row].set(0)
        if self.kv_page_size and self._row_pages[row] is not None:
            if self._host_tier is not None and s is not None:
                # cross-turn demotion: the retiring session's full-page
                # prefix (prompt AND generated tokens — kv committed
                # for positions [0, len(seq)-1), same cut freeze uses)
                # snapshots into the host tier while the table is still
                # valid, so the conversation's NEXT turn promotes
                # instead of re-prefilling its history
                try:
                    item = s.get("item") or {}
                    seq = s.get("seq") or []
                    # migrated-in kv keeps the existing rule — only
                    # pages this replica computed itself are published
                    # (device cache OR host tier)
                    if "kv" not in (item.get("resume") or {}):
                        root = self._lora_prefix_root(
                            item.get("aidx", 0))
                        rkeys = self._prefix_keys(seq, len(seq) - 1,
                                                  root=root)
                        owned = self._row_pages[row]
                        n = min(len(rkeys), len(owned))
                        self._demote_pages(rkeys[:n], owned[:n])
                except Exception:
                    logger.warning("retirement demote failed",
                                   exc_info=True)
            for page in self._row_pages[row]:
                if page in self._page_rc:
                    self._page_rc[page] -= 1     # cached: stays in pool
                else:
                    self._free_pages.append(page)
            self._row_pages[row] = None
            self._row_shared_n[row] = 0
            self._row_prefix_keys[row] = None
            self._cache = self._set_table(
                self._cache, jnp.asarray(row, jnp.int32),
                self._sink_entries)

    def _start_admission(self, row, item):
        faults.check("serve.admission")
        h, prompt = item["h"], item["prompt"]
        if h.cancelled.is_set():        # client gone before admission
            h._finish(list(prompt))
            return
        if "resume" in item and "kv" in item["resume"]:
            # a migrated-in session: no prefill — upload its kv and
            # occupy the row mid-sequence (parks like any admission
            # when the pool is full)
            if not self._install_resume(row, item):
                self._parked = (row, item)
            return
        # a kv-less "resume" is a REPLAY (crash recovery): the dead
        # replica's pages are gone, so the committed sequence minus its
        # last token re-prefills here — the splice registers are then
        # installed exactly as a migration would, and decode continues
        # byte-identically (seed + ordinal reconstruct the RNG chain)
        src = item["resume"]["seq"][:-1] if "resume" in item else prompt
        if self.kv_page_size and not self._try_allocate(
                row, item, lazy=item.get("long") is True):
            self._parked = (row, item)   # wait for pages (FIFO: nothing
            return                       # else admits while parked)
        if item.get("long"):
            # lane span anchor: admission happened (pages map lazily;
            # per-chunk progress shows up as long.chunk events)
            self.trace.event(item.get("trace"), "long.admit", row=row,
                             prompt_len=len(prompt))
        # prefix-shared pages already hold their kv: the TARGET prefill
        # starts after them (a fully cached prompt prefills only its
        # last page).  The DRAFT's dense per-row cache shares nothing:
        # it must still see positions [0, shared) or speculation
        # proposes from garbage context — those catch-up chunks run
        # through the same one-chunk-per-round cadence (d_off below),
        # preserving the at-most-one-chunk stall bound.
        shared_tokens = (self._row_shared_n[row] * self.kv_page_size
                         if self.kv_page_size else 0)
        self._admissions.append({
            "row": row, "item": item, "offset": shared_tokens, "i": 0,
            "src": src, "t_admit": time.monotonic(),
            "sizes": self._prefill_chunk_sizes(len(src) - shared_tokens),
            "d_off": 0, "di": 0,
            "d_sizes": (self._prefill_chunk_sizes(shared_tokens)
                        if shared_tokens and self.draft_model is not None
                        else [])})

    # ---- batched prefill engine ------------------------------------------
    # Admission is a PIPELINE, not a one-at-a-time state machine: up to
    # `prefill_rows` waiting requests each contribute their next chunk to
    # ONE batched dispatch per round (decode.build_prefill_batch — per-row
    # row indices / offsets / lengths, bucket-padded to a shared shape so
    # compile count stays O(log chunk x log rows)).  Rounds interleave
    # with decode steps under `prefill_budget` tokens (Sarathi-style
    # stall-free scheduling): the head admission ALWAYS runs so one
    # over-budget chunk cannot wedge the queue, and decode slots stall by
    # at most one round's worth of prefill between steps.  Token parity
    # with the sequential path is exact: chunk boundaries, bucket sizes,
    # the per-row skip offsets, and the first-token pick are all
    # unchanged — only the batch width of the prefill dispatch differs.

    def _next_chunk_len(self, adm):
        """Length of the chunk this admission would run next (draft
        catch-up chunks count against the budget like any other)."""
        if adm["di"] < len(adm["d_sizes"]):
            return adm["d_sizes"][adm["di"]]
        return adm["sizes"][adm["i"]]

    def _select_prefill(self):
        """Priority-aware slice of the admission queue for this round:
        at most `prefill_rows` entries whose summed next-chunk lengths
        fit the token budget.  The HEAD is always selected (stall-free
        rule — budget caps batching, it never blocks progress); the
        remaining lanes consider interactive admissions before
        batch-class ones, stable within a class, so a single-class
        workload keeps the sequential path's exact FIFO chunk schedule
        (the parity baseline) while a mixed round spends the Sarathi
        budget on interactive prompts first.

        Mega-prompt lane: long admissions rank AFTER both normal
        classes and at most `long_chunk_quota` of them join a round —
        the lane streams its prompt across many rounds instead of
        monopolizing the budget.  Each long pick must first map pool
        pages for its chunk (`_ensure_long_pages`); a page-starved
        long HEAD is the one documented exception to the head-always
        rule, because dispatching its chunk through unmapped (sink)
        table entries would corrupt nothing but compute garbage —
        the round's budget goes to the other admissions instead."""
        if not self._admissions:
            return []

        def _is_long(a):
            return (a["item"] or {}).get("long") is True

        head = self._admissions[0]
        if _is_long(head) and not self._ensure_long_pages(head):
            head = None
        # _ensure_long_pages may have FAILED the head out of the queue
        pool = list(self._admissions)
        if head is not None and head not in pool:
            head = None
        rest = [a for a in pool if a is not head]
        order = [head] if head is not None else []
        order += [a for a in rest if not _is_long(a)
                  and (a["item"] or {}).get("cls") != "batch"]
        order += [a for a in rest if not _is_long(a)
                  and (a["item"] or {}).get("cls") == "batch"]
        order += [a for a in rest if _is_long(a)]
        selected, spent, long_picked = [], 0, 0
        for adm in order:
            if _is_long(adm):
                if long_picked >= self.long_chunk_quota:
                    continue
                if adm is not head and not self._ensure_long_pages(adm):
                    continue
            size = self._next_chunk_len(adm)
            if selected and (len(selected) >= self.prefill_rows
                             or spent + size > self.prefill_budget):
                break
            selected.append(adm)
            spent += size
            if _is_long(adm):
                long_picked += 1
        return selected

    def _sink_page(self):
        # dense mode has no sink; pad rows are dropped by index anyway,
        # so any in-range page value works for the batched jit signature
        return self._sink if self.kv_page_size else 0

    def _prefill_args(self, entries, count_sink=False):
        """Pad a round's (row, chunk, start) entries to shared bucket
        shapes and build the device arrays.  Bucket = power-of-2 over the
        LONGEST chunk (capped at prefill_chunk), width = power-of-2 over
        the entry count: compile variants stay bounded while short
        chunks ride along with long ones."""
        from .models import decode as decode_mod

        longest = max(len(c) for _, c, _ in entries)
        bucket = _bucket_len(longest, self.prefill_chunk)
        width = _pow2_width(len(entries))
        if count_sink and self.kv_page_size:
            # bucket-padding overshoot of real rows lands in their tail
            # table entries (the sink past their allocation); pad rows
            # write their whole bucket through the sink-only table
            pad = sum(bucket - len(c) for _, c, _ in entries)
            pad += (width - len(entries)) * bucket
            if pad:
                self.counters.inc("kv_sink_writes", pad)
        return decode_mod.build_prefill_batch(entries, width, bucket,
                                              self.n_slots)

    def _run_prefill_round(self):
        """One batched prefill dispatch over the admission queue; on each
        finishing row, pick the first token and occupy the slot.  Decode
        keeps stepping between rounds, so in-flight slots stall by at
        most one budget's worth of prefill latency."""
        import jax.numpy as jnp

        # cancellation sweep first: a client gone mid-admission must not
        # occupy a batch lane (or its pages) for the rest of its prompt
        live = []
        for adm in self._admissions:
            item = adm["item"]
            if item["h"].cancelled.is_set():
                self._free_row(adm["row"])   # release pages, sink table
                item["h"]._finish(list(item["prompt"]))
            else:
                live.append(adm)
        self._admissions = live
        selected = self._select_prefill()
        if not selected:
            return
        # draft catch-up rounds batch separately from main rounds: they
        # advance the DRAFT cache over prefix-shared positions the target
        # never re-computes, so the two groups take different jits
        catchup = [a for a in selected if a["di"] < len(a["d_sizes"])]
        if catchup:
            entries = []
            for adm in catchup:
                size = adm["d_sizes"][adm["di"]]
                d_off = adm["d_off"]
                chunk = adm["item"]["prompt"][d_off:d_off + size]
                entries.append((adm["row"], chunk, d_off))
                adm["d_off"] = d_off + size
                adm["di"] += 1
            chunks, rows, starts, n_valids = self._prefill_args(entries)
            _, self._d_cache = self._d_prefill_many(
                self.draft_params, self._d_cache, chunks, rows, starts,
                n_valids, jnp.asarray(0, jnp.int32))
            self.counters.inc("prefill_dispatches")
            for (erow, chunk, off), adm in zip(entries, catchup):
                self.trace.event(adm["item"].get("trace"), "prefill",
                                 row=erow, chunk=len(chunk), offset=off,
                                 draft_catchup=True)
            return
        entries, finishing = [], []
        for adm in selected:
            off = adm["offset"]
            size = adm["sizes"][adm["i"]]
            # "src" is the prefill target: the prompt for a fresh
            # admission, prompt+emitted-minus-last for a crash replay
            chunk = adm["src"][off:off + size]
            entries.append((adm["row"], chunk, off))
            adm["offset"] = off + len(chunk)
            adm["i"] += 1
            if adm["offset"] >= len(adm["src"]):
                finishing.append(adm)
        chunks, rows, starts, n_valids = self._prefill_args(
            entries, count_sink=True)
        sink = jnp.asarray(self._sink_page(), jnp.int32)
        if self.lora_rank:
            aidxs = [adm["item"]["aidx"] for adm in selected]
            aidxs += [0] * (int(rows.shape[0]) - len(aidxs))
            logits, self._cache = self._prefill_many(
                self.params, self._lora_banks, self._cache, chunks, rows,
                starts, n_valids, sink, jnp.asarray(aidxs, jnp.int32))
        else:
            logits, self._cache = self._prefill_many(
                self.params, self._cache, chunks, rows, starts, n_valids,
                sink)
        if self.draft_model is not None:
            # the draft's dense cache mirrors every target chunk (same
            # rows/offsets; its writes mask at the row's true length)
            _, self._d_cache = self._d_prefill_many(
                self.draft_params, self._d_cache, chunks, rows, starts,
                n_valids, jnp.asarray(0, jnp.int32))
        self.counters.inc("prefill_dispatches")
        # per-chunk prefill spans: host-clocked at dispatch (the jit
        # call returns asynchronously; no device value is read here).
        # Mega-prompt chunks get their own event name (+ counter) so
        # the lane's progress reads directly off the trace timeline
        for (erow, chunk, off), adm in zip(entries, selected):
            if (adm["item"] or {}).get("long"):
                self.counters.inc("long_chunks_dispatched")
                self.trace.event(adm["item"].get("trace"), "long.chunk",
                                 row=erow, chunk=len(chunk), offset=off)
            else:
                self.trace.event(adm["item"].get("trace"), "prefill",
                                 row=erow, chunk=len(chunk), offset=off)
        if self.kv_page_size:
            # which S>1 path served this dispatch: the Pallas paged-
            # prefill kernels or the einsum blend (impl="blend", or
            # pallas-tpu unavailable on this jaxlib)
            self.counters.inc("prefill_kernel_dispatches"
                              if self._prefill_kernel_active
                              else "prefill_blend_fallbacks")
        for i, adm in enumerate(selected):
            if adm not in finishing:
                continue
            self._admissions.remove(adm)
            self._finish_admission(adm, logits[i])

    def _finish_admission(self, adm, logits_row):
        """Final chunk done: pick the first token (exact solo parity),
        record TTFT, and occupy the row for decode."""
        import jax.numpy as jnp

        if "resume" in adm["item"]:
            # crash replay: the final chunk's logits correspond to a
            # token the dead replica already emitted — no pick, no
            # emission; splice the registers mid-sequence instead
            self._finish_replay(adm)
            return
        item, row = adm["item"], adm["row"]
        h, prompt, max_new = item["h"], item["prompt"], item["max_new"]
        temp, eos_id, seed = item["temp"], item["eos"], item["seed"]
        aidx = item["aidx"]
        if self.kv_page_size:
            # this row's full-prefix pages now hold computed kv: publish
            # them so later identical prompts skip their prefill
            self._register_prefix_pages(row)
        topk, topp, minp = item["topk"], item["topp"], item["minp"]
        stops, rep = item["stops"], item["rep"]
        tok = self._pick_first(logits_row, temp, seed, topk, topp, minp,
                               rep, prompt)
        # TTFT: clock runs from submit() to the instant the first token
        # becomes pullable (picked on the driver thread, so the record
        # needs no lock beyond LatencyWindow's own)
        t0 = item.get("t_submit")
        if t0 is not None:
            elapsed = time.monotonic() - t0
            self._ttft.record(elapsed)
            self._ttft_cls[item.get("cls") or "interactive"].record(elapsed)
        tid = item.get("trace")
        if tid:
            now = time.monotonic()
            t_adm = adm.get("t_admit", now)
            if t0 is not None:
                self.trace.span_at(tid, "queue", t0, t_adm)
            self.trace.span_at(tid, "admit", t_adm, now, row=row,
                               prompt_len=len(prompt))
        h.tokens.put([tok])
        seq = prompt + [tok]
        if (max_new <= 1 or (eos_id is not None and tok == eos_id)
                or self._hit_stop(seq, stops, len(prompt))):
            self._free_row(row)
            h._finish(seq)
            self.counters.inc("requests_served")
            self.trace.event(tid, "retire", row=row, reason="first_token")
            return
        self._gen[row] += 1
        (self._toks, self._temps, self._seeds, self._ords,
         self._topks, self._topps, self._minps, self._rems,
         self._eoss, self._eos_on) = self._set_row(
            self._toks, self._temps, self._seeds, self._ords,
            self._topks, self._topps, self._minps, self._rems,
            self._eoss, self._eos_on,
            jnp.asarray(row, jnp.int32), jnp.asarray(tok, jnp.int32),
            jnp.asarray(temp, jnp.float32), jnp.asarray(seed, jnp.int32),
            jnp.asarray(1, jnp.int32), jnp.asarray(topk, jnp.int32),
            jnp.asarray(topp, jnp.float32), jnp.asarray(minp, jnp.float32),
            jnp.asarray(max_new - 1, jnp.int32),
            jnp.asarray(eos_id if eos_id is not None else 0, jnp.int32),
            jnp.asarray(eos_id is not None, jnp.bool_))
        if self.lora_rank:
            self._lora_ids = self._lora_ids.at[row].set(aidx)
        filtered = bool(topk or topp < 1.0 or minp > 0.0)
        if filtered:
            self._n_filtered += 1
        penalized = rep != 1.0
        if penalized:
            self._seen = self._seen.at[row].set(0).at[
                row, jnp.asarray(prompt, jnp.int32)].set(1)
            self._reps = self._reps.at[row].set(rep)
            self._n_penalized += 1
        self._slots[row] = {"handle": h, "seq": seq,
                            "remaining": max_new - 1, "temp": temp,
                            "eos": eos_id, "stops": stops,
                            "plen": len(prompt), "filtered": filtered,
                            "pen": penalized,
                            # the full request record: migration rebuilds
                            # every resident register from it (the device
                            # arrays alone can't be read back mid-flight)
                            "item": item}
        self._install_ctx(row, seq)

    def _install_ctx(self, row, seq):
        """Seed the n-gram table with a row's committed tokens (prompt +
        first token at admission; the whole sequence on a migration
        splice / rollback / replay, which keeps n-gram speculation
        composable with every lifecycle the plain path supports).
        Pow2-padded to bound compile variants; no-op outside ngram
        mode."""
        import jax.numpy as jnp

        if self.spec_mode != "ngram":
            return
        width = min(_pow2_width(len(seq)), self.max_seq)
        toks = list(seq) + [0] * (width - len(seq))
        self._spec_ctx, self._spec_ctx_len = self._set_row_ctx(
            self._spec_ctx, self._spec_ctx_len,
            jnp.asarray(row, jnp.int32), jnp.asarray(toks, jnp.int32),
            jnp.asarray(len(seq), jnp.int32))

    def _finish_replay(self, adm):
        """Final replay chunk done: the row's cache now holds kv for
        every committed position except the last token's (written by
        the first decode step, exactly like a migration splice);
        install the mid-sequence registers and occupy the row.  The
        last committed token was already delivered to the client by the
        dead replica, so nothing is emitted here — the handle's first
        tokens are the continuation."""
        item, row = adm["item"], adm["row"]
        res = item["resume"]
        h, seq, remaining = item["h"], res["seq"], res["remaining"]
        self.trace.event(item.get("trace"), "replay", row=row,
                         committed=len(seq), remaining=remaining)
        if self.kv_page_size:
            # the replayed prompt's full-prefix pages are real computed
            # kv: publish them like any admission's
            self._register_prefix_pages(row)
        self._gen[row] += 1
        self._install_row_state(row, seq, len(item["prompt"]),
                                remaining, item)
        if self.lora_rank:
            self._lora_ids = self._lora_ids.at[row].set(item["aidx"])
        filtered = bool(item["topk"] or item["topp"] < 1.0
                        or item["minp"] > 0.0)
        if filtered:
            self._n_filtered += 1
        penalized = item["rep"] != 1.0   # seen-bits/rep arrays were set
        if penalized:                    # by _install_row_state
            self._n_penalized += 1
        self._slots[row] = {"handle": h, "seq": list(seq),
                            "remaining": remaining, "temp": item["temp"],
                            "eos": item["eos"], "stops": item["stops"],
                            "plen": len(item["prompt"]),
                            "filtered": filtered, "pen": penalized,
                            "item": item}
        self.counters.inc("replays_resumed")
        res["installed"].set()

    def _admit_one(self, row, item):
        """One admission, with the item's handle tied to its fate: a
        raise mid-admission happens AFTER the item left `_pending` but
        (possibly) before it joined `_admissions`, so `_die`'s sweeps
        cannot see it — without this tie the client would hang until
        its own timeout instead of hearing the engine died (the chaos
        suite's mid-prefill kill found exactly that orphan)."""
        try:
            self._start_admission(row, item)
        except BaseException as e:
            item["h"]._fail(e)      # idempotent if _die also sweeps it
            raise

    def _drain_ingress(self, block=False):
        """Move everything waiting on the thread-safe ingress queue into
        the per-class admission deques.  Runs on the device thread every
        `_admit` call — even when no row is free — so the class queues
        (the preemption controller's pressure signal and the weighted
        pick's input) always reflect what is actually waiting.  `block`
        waits briefly for the FIRST item (the idle-engine wake path),
        unless a class queue already holds work."""
        import queue as queue_mod

        if block and (any(self._classq.values()) or self._longq):
            block = False
        while True:
            try:
                item = self._pending.get(timeout=0.05 if block else 0)
            except queue_mod.Empty:
                return
            block = False
            if item.get("long"):
                self._longq.append(item)   # the mega-prompt lane
            else:
                self._classq[item.get("cls") or "interactive"].append(item)

    def _long_admitting(self):
        """Mega-prompt admissions currently mid-prefill: the lane
        admits ONE at a time (its prompt spans many rounds; a second
        would just split the same chunk quota)."""
        return sum(1 for adm in self._admissions
                   if (adm["item"] or {}).get("long"))

    def _next_item(self):
        """Weighted-fair pick across the class queues: while both
        classes wait, up to `prio_weight` interactive admissions run per
        batch admission (interactive wins ties; batch alone drains
        freely).  Records the picked item's queueing delay — the
        per-class window the preemption controller and the fleet
        dashboards watch.

        The mega-prompt lane rides the same credit idiom one level up:
        while normal work waits, up to `prio_weight` normal admissions
        run per long admission, and a waiting mega-prompt admits
        immediately when the classes are idle — long prompts neither
        starve the batch nor wait for it to drain, and at most one is
        mid-prefill at a time."""
        inter = self._classq["interactive"]
        batch = self._classq["batch"]
        if self._longq and not self._long_admitting() and (
                self._long_credit >= self.prio_weight
                or not (inter or batch)):
            self._long_credit = 0
            item = self._longq.popleft()
        elif inter and batch:
            if self._batch_credit >= self.prio_weight:
                self._batch_credit = 0
                item = batch.popleft()
            else:
                self._batch_credit += 1
                item = inter.popleft()
        elif inter:
            item = inter.popleft()
        elif batch:
            self._batch_credit = 0
            item = batch.popleft()
        else:
            return None
        if self._longq and not item.get("long"):
            self._long_credit += 1
        t0 = item.get("t_submit")
        if t0 is not None:
            self._qdelay[item.get("cls") or "interactive"].record(
                time.monotonic() - t0)
        return item

    def _admit(self, block=False):
        """Pull waiting requests into the admission pipeline until it is
        `prefill_rows` wide (or rows/requests run out).  Mid-prefill
        admissions hold their row via `claimed` — a row is free only
        when no slot occupies it AND no admission is prefilling it."""
        self._drain_ingress(block=block)
        claimed = {adm["row"] for adm in self._admissions}

        def _free_row_index():
            return next((r for r in range(self.n_slots)
                         if self._slots[r] is None and r not in claimed),
                        None)

        if self._parked is not None:
            # a pool-starved admission waits at the head of the line;
            # retirement may have freed its pages by now
            row, item = self._parked
            self._parked = None
            if self._slots[row] is not None or row in claimed:
                row = _free_row_index()    # original row got taken
                if row is None:
                    self._parked = (0, item)
                    return
            self._admit_one(row, item)
            if self._parked is not None:
                return      # still starved: FIFO — nothing else admits
            claimed.add(row)
        while len(self._admissions) < self.prefill_rows:
            row = _free_row_index()
            if row is None:
                return
            item = self._next_item()
            if item is None:
                return
            self._admit_one(row, item)
            if self._parked is not None:
                return      # pool starved: later arrivals wait (FIFO)
            claimed.add(row)

    def _retire(self, row, gen):
        """Retire `row` (occupant generation `gen`).  `_free_row` mutates
        DEVICE state (page pool, table writes, resident arrays), so only
        the device thread applies it; from the host thread this posts a
        retirement request and BLOCKS on the ack — after it returns,
        `_slots[row]` is None, so later readback entries for the old
        occupant are dropped and a waiter woken by the handle observes
        consistent pool accounting."""
        if threading.current_thread() is self._thread:
            if self._slots[row] is not None and self._gen[row] == gen:
                self._free_row(row)
            return
        ev = threading.Event()
        self._retire_q.put((row, gen, ev))
        while not ev.wait(0.05):
            if self._stop.is_set() or self._dead is not None:
                return      # device thread gone: stop()/death drains acks

    def _apply_retirements(self, timeout=0.0):
        """Device thread: drain pending host-requested retirements and
        ack each.  With `timeout`, waits up to that long for the first
        one (the nothing-to-dispatch idle path)."""
        import queue as queue_mod

        self._apply_migrations()
        while True:
            try:
                row, gen, ev = (self._retire_q.get(timeout=timeout)
                                if timeout else self._retire_q.get_nowait())
            except queue_mod.Empty:
                return
            timeout = 0
            if self._slots[row] is not None and self._gen[row] == gen:
                self._free_row(row)
            ev.set()
            self._apply_migrations()

    # ---- kv migration (the kvtransfer.MigrationEngine substrate) ---------
    # A live session moves replicas in three acts.  FREEZE (source): the
    # host thread stops committing tokens for the row at a tick boundary
    # and the device thread bumps the row's generation (in-flight tokens
    # drop; determinism regenerates them at the destination) and gathers
    # the occupied pages to host memory.  RESUME (destination): a
    # prefill-skipping admission allocates fresh pages, uploads the
    # blocks, splices the page table, and rebuilds every resident
    # register from the committed sequence.  Then either COMPLETE
    # (source frees the row once the destination acks) or ROLLBACK
    # (source reinstalls its own registers and decodes on).  Pages are
    # owned by exactly one replica at every instant: the source keeps
    # them until the ack, the destination allocates its own — a failed
    # or even double-driven migration can never double-free.

    def _freeze_row(self, row, s):
        """Host-tick side of the freeze cut for `row` (slot dict `s`):
        delegate the device half, then publish the frozen record on the
        handle.  The committed ``seq`` at this instant IS the resume
        point — everything the device ran beyond it is garbage that
        either side regenerates."""
        h = s["handle"]
        box = {}
        if threading.current_thread() is self._thread:
            self._apply_freeze(row, box)     # serial engine: inline
        else:
            ev = threading.Event()
            self._freeze_q.put(("freeze", row, box, ev))
            while not ev.wait(0.05):
                if self._stop.is_set() or self._dead is not None:
                    return
        if not box.get("ok"):
            return
        s["frozen"] = True
        h.frozen = {"row": row, "gen": self._gen[row],
                    "seq": list(s["seq"]), "plen": s["plen"],
                    "remaining": s["remaining"], "item": s["item"],
                    "kind": "paged" if self.kv_page_size else "dense",
                    "kv": box["kv"], "n_pages": box.get("n_pages", 0)}
        self.trace.event(s["item"].get("trace"), "freeze", row=row,
                         committed=len(s["seq"]),
                         n_pages=box.get("n_pages", 0))
        h.freeze_done.set()

    def _apply_migrations(self):
        """Device thread: drain pending freeze/rollback requests (the
        migration analogue of `_apply_retirements`) and ack each."""
        import queue as queue_mod

        while True:
            try:
                entry = self._freeze_q.get_nowait()
            except queue_mod.Empty:
                return
            if entry[0] == "freeze":
                _, row, box, ev = entry
                self._apply_freeze(row, box)
            elif entry[0] == "drop_prefix":
                _, box, ev = entry
                box["n"] = self._evict_cached_pages(self._total_pages)
            else:
                _, row, frozen, box, ev = entry
                self._apply_rollback(row, frozen, box)
            ev.set()

    def _apply_freeze(self, row, box):
        """Device thread: bump `row`'s generation and gather its
        committed kv into fresh (host-bound) buffers.  The gather is
        not donated — the pool keeps stepping; the garbage the frozen
        row keeps writing lands beyond the committed cut (its own
        pages' tail or the sink), which neither continuation reads
        before overwriting."""
        import jax.numpy as jnp

        s = self._slots[row]
        if s is None:
            return
        self._gen[row] += 1
        n_pos = len(s["seq"]) - 1   # kv positions [0, n_pos) committed;
        # position n_pos is (re)written by the fed token on resume
        if self.kv_page_size:
            owned = self._row_pages[row] or []
            n_have = min(max(1, -(-n_pos // self.kv_page_size)),
                         len(owned))
            width = _pow2_width(n_have)
            ids = jnp.asarray(
                list(owned[:n_have]) + [self._sink] * (width - n_have),
                jnp.int32)
            kv = self._gather_kv(self._cache, ids)
            box["n_pages"] = n_have
        else:
            kv = self._gather_kv(self._cache, jnp.asarray(row, jnp.int32))
        for arr in kv.values():
            try:
                # start device->host now, riding under decode steps; the
                # wire serialization's np.asarray then finds bytes ready
                arr.copy_to_host_async()
            except (AttributeError, NotImplementedError):
                self.counters.inc("copy_to_host_fallbacks")
                break
        box["kv"] = kv
        box["ok"] = True

    def _apply_rollback(self, row, frozen, box):
        """Device thread: the migration failed pre-ack — reinstall the
        row's resident registers from the frozen cut and let it decode
        on.  Pages never left the row, so this is pure register repair;
        pool conservation is untouched."""
        s = self._slots[row]
        if s is None or self._gen[row] != frozen["gen"]:
            return      # stop()/death already tore the row down
        self._gen[row] += 1   # drop the frozen period's in-flight junk
        self._install_row_state(row, frozen["seq"], frozen["plen"],
                                frozen["remaining"], frozen["item"])
        s["frozen"] = False
        box["ok"] = True

    def _install_row_state(self, row, seq, plen, remaining, item):
        """Rebuild every resident device register for `row` from a
        committed sequence (shared by rollback on the source and
        resume-install on the destination): the write cursor points at
        the next position, the fed token is the last committed one, and
        ordinal/budget/seen-bits equal what a never-migrated row would
        hold — the byte-parity invariant."""
        import jax.numpy as jnp

        eos_id = item["eos"]
        self._cache = self._set_row_index(
            self._cache, jnp.asarray(row, jnp.int32),
            jnp.asarray(len(seq) - 1, jnp.int32))
        (self._toks, self._temps, self._seeds, self._ords,
         self._topks, self._topps, self._minps, self._rems,
         self._eoss, self._eos_on) = self._set_row(
            self._toks, self._temps, self._seeds, self._ords,
            self._topks, self._topps, self._minps, self._rems,
            self._eoss, self._eos_on,
            jnp.asarray(row, jnp.int32),
            jnp.asarray(seq[-1], jnp.int32),
            jnp.asarray(item["temp"], jnp.float32),
            jnp.asarray(item["seed"], jnp.int32),
            jnp.asarray(len(seq) - plen, jnp.int32),
            jnp.asarray(item["topk"], jnp.int32),
            jnp.asarray(item["topp"], jnp.float32),
            jnp.asarray(item["minp"], jnp.float32),
            jnp.asarray(remaining, jnp.int32),
            jnp.asarray(eos_id if eos_id is not None else 0, jnp.int32),
            jnp.asarray(eos_id is not None, jnp.bool_))
        if item["rep"] != 1.0:
            # seen-bits hold everything EXCEPT the fed token — the step
            # adds it before picking, exactly like the admission path
            self._seen = self._seen.at[row].set(0).at[
                row, jnp.asarray(seq[:-1], jnp.int32)].set(1)
            self._reps = self._reps.at[row].set(item["rep"])
        self._install_ctx(row, seq)

    def freeze_session(self, h, timeout_s=10.0):
        """Cut a live session for migration: ask the host thread to
        stop committing at its next tick for the row, and return the
        frozen record (seq snapshot + host-bound kv).  Returns None if
        the session completed before the cut landed; raises
        TimeoutError when no cut lands in `timeout_s` (an idle/wedged
        stream), leaving the session running untouched."""
        if self._dead is not None:
            raise RuntimeError(f"batcher died: {self._dead}")
        if self.draft_model is not None:
            raise ValueError(
                "kv migration does not compose with speculative "
                "decoding (the draft model's cache is not shipped)")
        h.migrate_requested.set()
        if not h.freeze_done.wait(timeout_s):
            h.migrate_requested.clear()
            # the cut may have landed concurrently with the clear
            if not h.freeze_done.wait(0.2):
                if h._done.is_set():
                    return None      # finished first: nothing to move
                raise TimeoutError(
                    f"freeze did not land within {timeout_s:.1f}s")
        frozen = h.frozen
        if frozen is None:
            return None
        return frozen

    def complete_migration(self, frozen):
        """Destination acked the splice: free the source row.  Pages
        flow back through the normal retirement path (prefix-shared
        rc--, exclusive ones to the free list); the destination holds
        its own fresh copies, so each side frees only its own."""
        self._retire(frozen["row"], frozen["gen"])
        self.counters.inc("migrations_completed")
        self.counters.inc("kv_pages_exported", frozen.get("n_pages", 0))

    def rollback_migration(self, frozen):
        """Migration failed before the destination acked: reinstall the
        row's registers from the frozen cut and resume decoding HERE.
        The client's stream continues as if nothing happened.  Returns
        False only when the engine is stopping (the handle fails
        through the normal death path instead)."""
        h = frozen["item"]["h"]
        box = {}
        if threading.current_thread() is self._thread:
            self._apply_rollback(frozen["row"], frozen, box)
        else:
            ev = threading.Event()
            self._freeze_q.put(("rollback", frozen["row"], frozen, box,
                                ev))
            while not ev.wait(0.05):
                if self._stop.is_set() or self._dead is not None:
                    return False
        # clear migrate_requested FIRST: with it down, the host thread
        # cannot re-enter the freeze branch between the two clears
        h.migrate_requested.clear()
        h.freeze_done.clear()
        h.frozen = None
        return bool(box.get("ok"))

    def live_handles(self):
        """Handles of sessions currently occupying rows (the
        drain-by-migration snapshot).  Racy by design: a row finishing
        concurrently just yields a handle whose migration reports
        completed_locally."""
        # graftcheck: disable-next-line=thread-race
        return [s["handle"] for s in self._slots
                if s is not None and not s.get("frozen")]

    # ---- preemption controller (park / resume) --------------------------
    # Parking reuses the migration machinery end to end: freeze_session
    # cuts the victim at a token commit, wire_snapshot flattens the cut
    # host-side, complete_migration frees the row AND its kv pages (a
    # parked session holds no device state at all), and submit_resume
    # re-admits it byte-identically when pressure drops.  Because the
    # host tick delivers a row's tokens BEFORE freezing it, everything
    # committed pre-park already reached the client (and the gateway's
    # stream journal) — so if this process dies holding parked
    # snapshots, failing their handles is enough: the journal re-drives
    # each stream on a live replica from its token record.  Note parks
    # ride the migration counters (migrations_completed /
    # kv_pages_exported include them); sessions_parked/unparked count
    # the preemption traffic itself.

    def _park_gather(self, h):
        """Cut a running session and pull its snapshot host-side.  On
        success the row and its pages are freed and the returned entry
        OWNS the session: every entry must reach exactly one of
        `_park_restore` (pressure dropped) or `_park_discard`
        (teardown / client gone) — the parked-session graftcheck lease.
        Returns None when the session finished before the cut landed;
        on a snapshot failure the session resumes decoding in place."""
        from . import kvtransfer

        frozen = self.freeze_session(h)
        if frozen is None:
            return None
        try:
            faults.check("serve.park_gather")
            meta, blocks = kvtransfer.wire_snapshot(
                frozen, "parked", self.kv_page_size)
        except BaseException:
            self.rollback_migration(frozen)
            raise
        self.complete_migration(frozen)
        self.counters.inc("sessions_parked")
        self.trace.event(meta.get("trace"), "park",
                         committed=len(meta["seq"]),
                         n_pages=meta.get("n_pages", 0))
        return {"h": h, "meta": meta, "blocks": blocks,
                "t_parked": time.monotonic()}

    def _park_restore(self, entry):
        """Resume a parked session through the :resume admission path
        (byte-identical continuation) and splice the resumed stream
        into the original client handle, which never learns its tokens
        crossed a park/resume hop."""
        faults.check("serve.park_restore")
        h2, _installed = self.submit_resume(entry["meta"],
                                            entry["blocks"])
        self.counters.inc("sessions_unparked")
        self.trace.event(
            entry["meta"].get("trace"), "unpark",
            parked_ms=round(
                (time.monotonic() - entry["t_parked"]) * 1000.0, 3))
        threading.Thread(target=self._pump_resumed,
                         args=(entry["h"], h2),
                         name="park-splice", daemon=True).start()
        return h2

    def _park_discard(self, entry, err=None):
        """Drop a parked session without resuming it: fail the original
        handle (teardown — breaking the stream is what lets the
        gateway's journal re-drive the work elsewhere) or finish it at
        its parked sequence (the client cancelled while parked)."""
        h = entry["h"]
        if err is not None:
            h._fail(err)
        else:
            h._finish([int(t) for t in entry["meta"]["seq"]])

    def _sweep_park_pool(self, err):
        """stop()/_die(): every parked snapshot dies with this process;
        failing the handles hands the sessions to the journal."""
        with self._park_lock:
            entries = list(self._park_pool)
            self._park_pool.clear()
        for entry in entries:
            self._park_discard(entry, err)

    def _pump_resumed(self, h, h2):
        """Forward the resumed handle's stream into the original (own
        thread per restore; exits with the resumed stream)."""
        import queue as queue_mod

        try:
            while True:
                if h.cancelled.is_set():
                    h2.cancel()
                try:
                    batch = h2.tokens.get(timeout=0.1)
                except queue_mod.Empty:
                    continue
                if batch is None:
                    break
                h.tokens.put(batch)
            h._finish(h2.result(timeout=10.0))
        except BaseException as e:
            h._fail(e)

    def _pick_victim(self):
        """Lowest-priority running session, most remaining work first.
        Racy scan by design — the device thread owns the slot table; a
        stale pick just means freeze_session returns None."""
        victim, most = None, -1
        # graftcheck: disable-next-line=thread-race
        for s in self._slots:
            if s is None or s.get("frozen"):
                continue
            if (s["item"].get("cls") or "interactive") != "batch":
                continue
            h = s["handle"]
            if h.migrate_requested.is_set() or h._done.is_set():
                continue
            if s["remaining"] > most:
                victim, most = h, s["remaining"]
        return victim

    def _preempt_loop(self):
        """Controller thread body: freeze_session and submit_resume
        both block on device-thread acks, so preemption cannot run on
        the engine loops — it watches from here instead."""
        while not self._stop.is_set():
            try:
                self._preempt_tick()
            except BaseException:
                if self._stop.is_set() or self._dead is not None:
                    return
                logger.warning("preemption tick failed", exc_info=True)
            self._stop.wait(0.02)

    def _preempt_tick(self):
        """One controller decision: park when the oldest waiting
        interactive admission has queued past `preempt_ms`; resume the
        oldest parked session when no interactive work waits and a row
        is free.  Reads of the class deques and slot table are racy by
        design (the device thread owns them) — a stale view shifts a
        decision by one 20ms tick, nothing more."""
        now = time.monotonic()
        try:
            head = self._classq["interactive"][0]
        except IndexError:
            head = None
        if head is not None:
            t0 = head.get("t_submit")
            if t0 is None or (now - t0) * 1000.0 <= self.preempt_ms:
                return
            with self._park_lock:
                if len(self._park_pool) >= self.park_capacity:
                    self.counters.inc("park_spills")
                    return
            victim = self._pick_victim()
            if victim is None:
                return
            try:
                entry = self._park_gather(victim)
            except TimeoutError:
                return      # no commit landed in time; session runs on
            except BaseException:
                self.counters.inc("park_failures")
                logger.warning("park failed; session continues",
                               exc_info=True)
                return
            if entry is None:
                return      # finished before the cut landed
            with self._park_lock:
                self._park_pool.append(entry)
                self._park_depth.set(len(self._park_pool))
            return
        # no interactive pressure: resume oldest-first into a free row
        # graftcheck: disable-next-line=thread-race
        if not any(s is None for s in self._slots):
            return
        with self._park_lock:
            if not self._park_pool:
                return
            entry = self._park_pool.popleft()
            self._park_depth.set(len(self._park_pool))
        if entry["h"].cancelled.is_set():
            self._park_discard(entry)   # client gone while parked
            return
        try:
            self._park_restore(entry)
        except BaseException:
            self.counters.inc("park_restore_failures")
            logger.warning("park restore failed; session stays parked",
                           exc_info=True)
            with self._park_lock:
                self._park_pool.appendleft(entry)
                self._park_depth.set(len(self._park_pool))

    def submit_resume(self, meta, blocks):
        """Admission that SKIPS prefill: occupy a row with a migrated
        session's committed sequence and uploaded kv blocks.  Validates
        eagerly (HTTP thread) so malformed snapshots 400 instead of
        killing the device loop.  Returns ``(handle, installed)``;
        the event sets once the row is live — the :resume surface's
        splice ack gate."""
        import jax
        import numpy as np

        from .models import decode as decode_mod

        if self._dead is not None:
            raise RuntimeError(f"batcher died: {self._dead}")
        if self.draft_model is not None:
            raise ValueError("this replica runs speculative decoding; "
                             "it cannot resume migrated sessions")
        kind = "paged" if self.kv_page_size else "dense"
        if meta.get("kind") != kind:
            raise ValueError(
                f"kv layout mismatch: snapshot is {meta.get('kind')!r}, "
                f"this replica serves {kind!r} caches")
        if (self.kv_page_size
                and int(meta.get("page_size") or 0) != self.kv_page_size):
            raise ValueError(
                f"page size mismatch: snapshot uses "
                f"{meta.get('page_size')}, this replica "
                f"{self.kv_page_size}")
        seq = [int(t) for t in (meta.get("seq") or ())]
        plen = int(meta.get("plen") or 0)
        max_new = int(meta.get("max_new") or 0)
        remaining = int(meta.get("remaining") or 0)
        vocab = self.slot_model.cfg.vocab_size
        if not (0 < plen < len(seq)):
            raise ValueError("resume needs a prompt and at least one "
                             "decoded token")
        if any(not 0 <= t < vocab for t in seq):
            raise ValueError(f"sequence token out of vocab range {vocab}")
        if remaining <= 0 or remaining != max_new - (len(seq) - plen):
            raise ValueError(
                f"inconsistent budget: remaining={remaining} with "
                f"{len(seq) - plen} of max_new={max_new} decoded")
        if len(seq) + remaining > self.max_seq:
            raise ValueError(
                f"resumed sequence needs {len(seq) + remaining} "
                f"positions; this replica's max_seq_len is "
                f"{self.max_seq}")
        temp = float(meta.get("temp") or 0.0)
        n_pages = int(meta.get("n_pages") or 0)
        if self.kv_page_size:
            expect_pages = -(-(len(seq) - 1) // self.kv_page_size)
            if n_pages != max(1, expect_pages):
                raise ValueError(
                    f"snapshot ships {n_pages} pages; "
                    f"{len(seq) - 1} committed positions need "
                    f"{max(1, expect_pages)}")
            if self._pages_needed(plen, max_new,
                                  rep=float(meta.get("rep", 1.0))
                                  ) > self._total_pages:
                raise ValueError(
                    "resumed request does not fit this replica's kv "
                    "pool; raise --generate_kv_pages")
        leaf_names = (decode_mod._POOL_LEAVES if self.kv_page_size
                      else decode_mod._DENSE_KV_LEAVES)
        paths = jax.tree_util.tree_flatten_with_path(self._cache)[0]
        expected = {decode_mod._path_str(p): leaf for p, leaf in paths
                    if decode_mod._leaf_name(p) in leaf_names}
        missing = sorted(set(expected) - set(blocks))
        if missing:
            raise ValueError(f"snapshot is missing kv blocks {missing}")
        # normalize + pre-pad HERE (HTTP thread): the device loop must
        # not pay host-side copies, and the jitted scatter wants pow2-
        # width blocks whose pad rows land in the sink page
        kv = {}
        pad_to = _pow2_width(n_pages) if self.kv_page_size else 0
        for name, leaf in expected.items():
            want = ((n_pages,) + tuple(leaf.shape[1:])
                    if self.kv_page_size else tuple(leaf.shape[1:]))
            a = np.ascontiguousarray(blocks[name])
            if tuple(a.shape) != want:
                raise ValueError(
                    f"kv block {name!r} has shape {tuple(a.shape)}; "
                    f"this replica expects {want}")
            if self.kv_page_size and a.shape[0] < pad_to:
                pad = np.zeros((pad_to - a.shape[0],) + a.shape[1:],
                               a.dtype)
                a = np.concatenate([a, pad], axis=0)
            kv[name] = a
        eos = meta.get("eos")
        stops = [list(map(int, st)) for st in (meta.get("stops") or ())]
        adapter = meta.get("adapter")
        aidx = 0
        if adapter is not None:
            if not self.lora_rank:
                raise ValueError(
                    f"session uses adapter {adapter!r} but this replica "
                    "has no LoRA bank")
            with self._lora_lock:
                if adapter not in self._adapters:
                    raise ValueError(
                        f"unknown adapter {adapter!r} on this replica")
                aidx = self._adapters[adapter]
                self._adapter_refs[aidx] = self._adapter_refs.get(aidx,
                                                                  0) + 1
        h = SlotHandle(seq[:plen])
        if aidx:
            h._on_done = lambda idx=aidx: self._release_adapter(idx)
        installed = threading.Event()
        self._pending.put({
            "h": h, "prompt": seq[:plen], "max_new": max_new,
            "temp": temp, "eos": int(eos) if eos is not None else None,
            "seed": int(meta.get("seed") or 0), "aidx": aidx,
            "topk": int(meta.get("topk") or 0),
            "topp": float(meta.get("topp", 1.0)),
            "minp": float(meta.get("minp") or 0.0),
            "stops": stops, "rep": float(meta.get("rep", 1.0)),
            "adapter": adapter, "t_submit": time.monotonic(),
            "cls": (meta.get("priority")
                    if meta.get("priority") in PRIORITY_CLASSES
                    else "interactive"),
            "trace": (meta.get("trace")
                      if trace.valid_id(meta.get("trace")) else None),
            "resume": {"seq": seq, "remaining": remaining,
                       "n_pages": n_pages, "kv": kv,
                       "installed": installed}})
        if self._dead is not None:
            self._drain_pending(RuntimeError(f"batcher died: {self._dead}"))
        return h, installed

    def submit_replay(self, meta):
        """Admission that REBUILDS a lost session from its token record
        alone: no kv arrives (the dead replica's pages are gone) — the
        committed sequence re-prefills here and the splice registers
        install as a migration's would, so decode continues
        byte-identically (the sampling chain is a pure function of
        (seed, ordinal)).  ``meta`` uses :func:`kvtransfer.wire_snapshot`
        key names minus the kv-layout fields, so a journal entry works
        against any layout — dense, paged, int8-kv — unlike a page
        snapshot.  Returns ``(handle, installed)`` like
        :meth:`submit_resume`."""
        if self._dead is not None:
            raise RuntimeError(f"batcher died: {self._dead}")
        if self.draft_model is not None:
            raise ValueError("this replica runs speculative decoding; "
                             "it cannot replay recovered sessions")
        seq = [int(t) for t in (meta.get("seq") or ())]
        plen = int(meta.get("plen") or 0)
        max_new = int(meta.get("max_new") or 0)
        remaining = int(meta.get("remaining") or 0)
        vocab = self.slot_model.cfg.vocab_size
        if not (0 < plen < len(seq)):
            raise ValueError("replay needs a prompt and at least one "
                             "decoded token")
        if any(not 0 <= t < vocab for t in seq):
            raise ValueError(f"sequence token out of vocab range {vocab}")
        if remaining <= 0 or remaining != max_new - (len(seq) - plen):
            raise ValueError(
                f"inconsistent budget: remaining={remaining} with "
                f"{len(seq) - plen} of max_new={max_new} decoded")
        if len(seq) + remaining > self.max_seq:
            raise ValueError(
                f"replayed sequence needs {len(seq) + remaining} "
                f"positions; this replica's max_seq_len is "
                f"{self.max_seq}")
        temp = float(meta.get("temp") or 0.0)
        if (self.kv_page_size
                and self._pages_needed(plen, max_new,
                                       rep=float(meta.get("rep", 1.0)))
                > self._total_pages):
            raise ValueError(
                "replayed request does not fit this replica's kv "
                "pool; raise --generate_kv_pages")
        eos = meta.get("eos")
        stops = [list(map(int, st)) for st in (meta.get("stops") or ())]
        adapter = meta.get("adapter")
        aidx = 0
        if adapter is not None:
            if not self.lora_rank:
                raise ValueError(
                    f"session uses adapter {adapter!r} but this replica "
                    "has no LoRA bank")
            with self._lora_lock:
                if adapter not in self._adapters:
                    raise ValueError(
                        f"unknown adapter {adapter!r} on this replica")
                aidx = self._adapters[adapter]
                self._adapter_refs[aidx] = self._adapter_refs.get(aidx,
                                                                  0) + 1
        h = SlotHandle(seq[:plen])
        if aidx:
            h._on_done = lambda idx=aidx: self._release_adapter(idx)
        installed = threading.Event()
        self._pending.put({
            "h": h, "prompt": seq[:plen], "max_new": max_new,
            "temp": temp, "eos": int(eos) if eos is not None else None,
            "seed": int(meta.get("seed") or 0), "aidx": aidx,
            "topk": int(meta.get("topk") or 0),
            "topp": float(meta.get("topp", 1.0)),
            "minp": float(meta.get("minp") or 0.0),
            "stops": stops, "rep": float(meta.get("rep", 1.0)),
            "adapter": adapter, "t_submit": time.monotonic(),
            "cls": (meta.get("priority")
                    if meta.get("priority") in PRIORITY_CLASSES
                    else "interactive"),
            "trace": (meta.get("trace")
                      if trace.valid_id(meta.get("trace")) else None),
            # no "kv" key: _start_admission reads that as "re-prefill"
            "resume": {"seq": seq, "remaining": remaining,
                       "installed": installed}})
        if self._dead is not None:
            self._drain_pending(RuntimeError(f"batcher died: {self._dead}"))
        return h, installed

    def _install_resume(self, row, item):
        """Device thread: allocate fresh pages, upload migrated kv,
        splice the page table, and occupy `row` mid-sequence.  Returns
        False when the pool cannot hold it yet (parks like a normal
        admission).  No prefix sharing in either direction: the pages
        were computed on another replica, and the prefix cache only
        publishes pages whose content this replica computed itself."""
        import jax.numpy as jnp

        faults.check("serve.resume_install")
        res = item["resume"]
        h, seq, remaining = item["h"], res["seq"], res["remaining"]
        if self.kv_page_size:
            n_have = res["n_pages"]
            need = max(n_have,
                       self._pages_needed(len(item["prompt"]),
                                          item["max_new"],
                                          rep=item["rep"]))
            if len(self._free_pages) < need:
                self._evict_cached_pages(need - len(self._free_pages))
            if len(self._free_pages) < need:
                return False
            pages = [self._free_pages.pop() for _ in range(need)]
            try:
                self._assert_no_sink(pages)
                self._map_row(row, pages)
                # kv blocks were normalized and pow2-padded in
                # submit_resume (host thread); pad rows land in the sink
                width = _pow2_width(n_have)
                ids = jnp.asarray(
                    pages[:n_have] + [self._sink] * (width - n_have),
                    jnp.int32)
                self._cache = self._scatter_kv(self._cache, ids,
                                               res["kv"])
            except BaseException:
                # same conservation contract as _try_allocate: a device
                # failure between the pops and the commit must hand the
                # pages back
                self._free_pages.extend(pages)
                raise
            self._row_pages[row] = pages
            self._row_shared_n[row] = 0
            self._row_prefix_keys[row] = None
        else:
            self._cache = self._scatter_kv(
                self._cache, jnp.asarray(row, jnp.int32), res["kv"])
        self._gen[row] += 1
        self._install_row_state(row, seq, len(item["prompt"]),
                                remaining, item)
        if self.lora_rank:
            self._lora_ids = self._lora_ids.at[row].set(item["aidx"])
        filtered = bool(item["topk"] or item["topp"] < 1.0
                        or item["minp"] > 0.0)
        if filtered:
            self._n_filtered += 1
        penalized = item["rep"] != 1.0
        if penalized:
            self._n_penalized += 1
        self._slots[row] = {"handle": h, "seq": list(seq),
                            "remaining": remaining, "temp": item["temp"],
                            "eos": item["eos"], "stops": item["stops"],
                            "plen": len(item["prompt"]),
                            "filtered": filtered, "pen": penalized,
                            "item": item}
        self.counters.inc("migrations_resumed")
        self.counters.inc("kv_pages_imported", res["n_pages"])
        self.trace.event(item.get("trace"), "resume", row=row,
                         committed=len(seq), n_pages=res["n_pages"])
        res["installed"].set()
        return True

    def _process_batch(self, batch):
        """One arrived chunk -> emissions/retires, in dispatch order
        (host side of the pipeline).  `batch` is (toks_dev [k, n] or
        [k, n, draft_k], counts [k, n] or None, done [k, n],
        [gen_snapshot per entry], [spec round k or None per entry]);
        counts (speculative rounds) say how many of each row's tokens
        are DELIVERABLE, and `done` carries the device-computed stop
        verdict (budget exhausted or eos among the delivered tokens) —
        the host never inspects token values to decide whether the
        device may continue; only the client-supplied stop SEQUENCES
        still need the host's substring check.  Tokens are delivered to
        each stream batched per tick (one queue put per handle per
        chunk, not per token).  The host copy was started at flush
        (copy_to_host_async), so the np.asarray here is usually free.

        Speculative entries also close the adaptive-draft-length loop
        here: per-row acceptance EWMAs (host-thread-owned) update from
        the delivered counts, and a new suggested round width goes back
        to the device thread through `_speck_q`."""
        import numpy as np
        import queue as queue_mod

        stacked, counts, done, gens_list, ks_list = batch
        block = np.asarray(stacked)
        counts = None if counts is None else np.asarray(counts)
        done = np.asarray(done)
        pend = {}     # row -> tokens accumulated this tick
        spec_pend = {}  # row -> [rounds, accepted, k] this tick

        def emit(r, s):
            toks = pend.pop(r, None)
            if toks:
                s["handle"].tokens.put(toks)
                tid = s["item"].get("trace") if s.get("item") else None
                if tid:
                    # SAMPLED decode spans, recorded here on the host
                    # drain thread at token-commit time — the device
                    # thread never sees tracing and stays
                    # hostsync-clean
                    n = self.trace.decode_sample
                    s["_trace_ticks"] = s.get("_trace_ticks", 0) + 1
                    if n and (s["_trace_ticks"] - 1) % n == 0:
                        self.trace.event(tid, "decode", row=r,
                                         tokens=len(toks),
                                         seq_len=len(s["seq"]),
                                         tick=s["_trace_ticks"])
                        sp = spec_pend.get(r)
                        if sp:
                            self.trace.event(tid, "spec.round", row=r,
                                             rounds=sp[0],
                                             accepted=sp[1], k=sp[2])
            spec_pend.pop(r, None)

        for i, (gens, row_toks) in enumerate(zip(gens_list, block)):
            for r, s in enumerate(self._slots):
                if s is None or self._gen[r] != gens[r]:
                    continue      # freed or re-occupied since dispatch
                if s.get("frozen"):
                    # mid-migration: the freeze bumped the row's gen, but
                    # chunks dispatched AFTER the bump match it again —
                    # their tokens are garbage continuations of a cut the
                    # destination (or a rollback) owns.  Cancel is also
                    # deferred: the relay/rollback path settles the handle
                    continue
                if (s["handle"].migrate_requested.is_set()
                        and not s["handle"].freeze_done.is_set()
                        and s["remaining"] > 0):
                    # the freeze cut: deliver what this tick committed,
                    # then snapshot at a host-tick boundary so the
                    # committed seq IS the resume point
                    emit(r, s)
                    self._freeze_row(r, s)
                    continue
                if s["handle"].cancelled.is_set():
                    # client gone: stop burning device time on this slot.
                    # retire BEFORE finishing the handle (see _retire)
                    emit(r, s)
                    self._retire(r, gens[r])
                    s["handle"]._finish(s["seq"])
                    self.counters.inc("requests_served")
                    self.trace.event(s["item"].get("trace"), "retire",
                                     row=r, reason="cancelled")
                    continue
                if counts is None:
                    toks = [int(row_toks[r])]
                else:             # speculative round: n_del[r] tokens
                    toks = [int(t) for t in
                            np.atleast_1d(row_toks[r])[:counts[i][r]]]
                    k_e = ks_list[i]
                    if k_e:       # acceptance feedback (adaptive k)
                        c = int(counts[i][r])
                        acc = k_e if c >= k_e else max(0, c - 1)
                        self.counters.inc("spec_tokens_accepted", acc)
                        w = self._spec_ewma
                        w[r] = 0.5 * w[r] + 0.5 * (acc / k_e)
                        sp = spec_pend.setdefault(r, [0, 0, k_e])
                        sp[0] += 1
                        sp[1] += acc
                        sp[2] = k_e
                ended = False
                for tok in toks:
                    s["seq"].append(tok)
                    s["remaining"] -= 1
                    pend.setdefault(r, []).append(tok)
                    if self._hit_stop(s["seq"], s["stops"], s["plen"]):
                        ended = True
                        break
                if ended or bool(done[i][r]):
                    emit(r, s)
                    self._retire(r, gens[r])
                    s["handle"]._finish(s["seq"])
                    self.counters.inc("requests_served")
                    self.trace.event(s["item"].get("trace"), "retire",
                                     row=r, reason="stop",
                                     seq_len=len(s["seq"]))
        # per-tick delivery for every stream that did NOT finish this
        # chunk: all its tokens in one put
        for r, s in enumerate(self._slots):
            if s is not None and r in pend:
                emit(r, s)
        if any(ks_list):
            # suggest the next round width: the max of the per-row
            # desired lengths (pow2-bucketed to bound compile variants)
            # — an all-disagreeing burst degrades to k=1, ~plain decode,
            # while one agreeing row keeps its long drafts.  Token
            # streams are invariant to WHEN the device adopts a new k
            # (round-boundary-invariant proposals + key streams), so
            # this feedback loop may lag freely
            desired = 1
            for r, s in enumerate(self._slots):
                if s is not None:
                    desired = max(desired,
                                  1 + round(self._spec_ewma[r]
                                            * (self.draft_k - 1)))
            k_next = min(_pow2_width(desired), self.draft_k)
            if k_next != self._spec_k_pub:
                try:
                    self._speck_q.put_nowait(k_next)
                    self._spec_k_pub = k_next
                except queue_mod.Full:
                    pass
        self.counters.inc("host_ticks")

    def _host_loop(self):
        """Host side of the async pipeline: drain flushed chunks, commit
        tokens, deliver to streams, retire finished rows (via the
        device thread)."""
        import queue as queue_mod

        try:
            while not self._stop.is_set():
                try:
                    batch = self._ready.get(timeout=0.05)
                except queue_mod.Empty:
                    continue
                self._process_batch(batch)
                self._depth.add(-len(batch[3]))
        except BaseException as e:
            self._die(e, "continuous batcher host thread died")

    def _dispatch(self):
        """One decode advance for all active slots: a fused speculative
        round (v2 — greedy AND sampled rows speculate, proposals from
        the draft model or the n-gram table) unless speculation is off
        or a repetition-penalized row is active, else one plain step.
        Returns the readback entry (toks, counts, done, gens, spec_k) —
        everything the host needs, shipped down in one copy; no host
        sync happens here."""
        import queue as queue_mod

        from .models import decode as decode_mod

        if self.kv_page_size:
            # every dispatch steps ALL rows; the unoccupied ones write
            # their junk token into the sink page (the reason it exists)
            idle = sum(s is None for s in self._slots)
            if idle:
                self.counters.inc("kv_sink_writes", idle)
        # a penalized row samples from history-adjusted logits the
        # verify block does not reproduce position-by-position, so any
        # penalized occupant gates speculation off globally (penalized
        # requests also skip the verify-overshoot headroom — see submit)
        use_spec = self.spec_mode != "off" and not self._n_penalized
        if use_spec:
            try:
                faults.check("serve.spec_verify")
            except Exception:
                # injected verify failure: fall back to a plain step and
                # re-probe next dispatch.  Greedy rows are byte-identical
                # either way; a sampled fallback step draws from the same
                # distribution via the plain path's shared (seed, ordinal)
                # schedule, so a PERSISTENT failure degrades to exactly
                # the non-spec engine (solo-parity), while an isolated
                # one stays distribution-preserving
                self.counters.inc("spec_draft_fallbacks")
                use_spec = False
        if use_spec:
            # adaptive draft length: adopt the host thread's latest
            # suggestion (latest wins; the queue is the only channel)
            try:
                while True:
                    self._spec_k = self._speck_q.get_nowait()
            except queue_mod.Empty:
                pass
            k = self._spec_k
            ngram = self.spec_mode == "ngram"
            fn = decode_mod._jitted_slot_spec_round_v2(
                self.slot_model, None if ngram else self.d_slot_model,
                k, lora=bool(self.lora_rank))
            kw = {}
            if self._n_filtered:
                kw.update(topks=self._topks, topps=self._topps,
                          minps=self._minps)
            if self.lora_rank:
                kw.update(lora_tree=self._lora_banks, ids=self._lora_ids)
            if ngram:
                kw.update(ctx=self._spec_ctx, ctx_len=self._spec_ctx_len)
            else:
                kw.update(d_params=self.draft_params,
                          d_cache=self._d_cache)
            ret = fn(self.params, self._cache, self._toks, self._temps,
                     self._seeds, self._ords, self._rems, self._eoss,
                     self._eos_on, **kw)
            (self._toks, c_tok, _commit, n_del, sdone, self._rems,
             self._ords, self._cache) = ret[:8]
            if ngram:
                self._spec_ctx, self._spec_ctx_len = ret[8], ret[9]
            else:
                self._d_cache = ret[8]
            self._spec_rounds += 1
            self._spec_k_sum += k
            n_live = sum(s is not None for s in self._slots)
            self.counters.inc("spec_tokens_proposed", k * n_live)
            return (c_tok, n_del, sdone, tuple(self._gen), k)
        # filter/penalty arrays are passed only while such a row is
        # active: their PRESENCE is static under jit, so plain workloads
        # run the exact pre-feature program (no per-step sort / mask);
        # the stop arrays are ALWAYS passed — both engines share one
        # program, which is what keeps them byte-identical
        kw = dict(rems=self._rems, eoss=self._eoss, eos_on=self._eos_on)
        if self._n_filtered:
            kw.update(topks=self._topks, topps=self._topps,
                      minps=self._minps)
        if self._n_penalized:
            kw.update(seen=self._seen, reps=self._reps)
        if self.lora_rank:
            ret = self._step(
                self.params, self._lora_banks, self._cache, self._toks,
                self._temps, self._seeds, self._ords, self._lora_ids,
                **kw)
        else:
            ret = self._step(
                self.params, self._cache, self._toks, self._temps,
                self._seeds, self._ords, **kw)
        if self._n_penalized:
            nxt, self._cache, self._ords, self._seen, self._rems, done = ret
        else:
            nxt, self._cache, self._ords, self._rems, done = ret
        self._toks = nxt
        self._steps += 1
        return (nxt, None, done, tuple(self._gen), None)

    def _flush_entries(self, reads):
        """Stack this chunk's entries for one async host copy.  Plain
        steps stack to [k, n]; speculative rounds to [k, n, draft_k] with
        a [k, n] counts plane.  Mixed chunks pad every entry to width
        draft_k — plain steps with count 1, adaptive rounds at k <
        draft_k with their own counts (n_del never exceeds the round's
        k).  The done plane stacks to [k, n] always."""
        import jax.numpy as jnp

        done = jnp.stack([e[2] for e in reads])
        if all(e[1] is None for e in reads):
            return jnp.stack([e[0] for e in reads]), None, done
        k = self.draft_k

        def widen(e):
            toks, counts = e[0], e[1]
            if counts is None:
                toks = toks[:, None]
                counts = jnp.ones(toks.shape[0], jnp.int32)
            if toks.shape[1] < k:
                toks = jnp.pad(toks, ((0, 0), (0, k - toks.shape[1])))
            return toks, counts

        wide = [widen(e) for e in reads]
        return (jnp.stack([w[0] for w in wide]),
                jnp.stack([w[1] for w in wide]), done)

    def _flush(self, reads):
        """Stack a chunk and START its host copies asynchronously; the
        np.asarray in `_process_batch` then usually finds the bytes
        already landed.  Backends without copy_to_host_async degrade to
        the synchronous copy — counted, so the regression shows in
        stats() instead of silently eating the pipeline's win."""
        stacked, counts, done = self._flush_entries(reads)
        arrays = ((stacked, done) if counts is None
                  else (stacked, counts, done))
        for arr in arrays:
            try:
                arr.copy_to_host_async()
            except (AttributeError, NotImplementedError):
                # the backend-unsupported cases; anything else (device
                # failure mid-copy) must kill the engine, not pass
                self.counters.inc("copy_to_host_fallbacks")
                break
        return (stacked, counts, done, [e[3] for e in reads],
                [e[4] for e in reads])

    def _flush_due(self, n_reads, active):
        """Whether the accumulated reads should flush now: a full chunk,
        nothing left to dispatch, or a LIVE slot is within `n_reads`
        tokens of finishing (flushing early bounds its retirement
        latency).  Rows whose budget already hit zero are only waiting
        for retirement — they cannot need more tokens, so they must not
        shrink the chunk (a single such straggler used to force
        per-step flushes via the min(..., default=0) path)."""
        if not n_reads:
            return False
        if n_reads >= self.read_chunk or not active:
            return True
        near = min((s["remaining"] for s in self._slots
                    if s is not None and s["remaining"] > 0
                    and not s.get("frozen")),
                   default=None)
        return near is not None and near <= n_reads

    def _loop(self):
        if self.engine == "async":
            self._loop_async()
        else:
            self._loop_serial()

    def _loop_serial(self):
        """The single-thread reference engine: dispatch, flush, process
        the PREVIOUS chunk inline (double-buffered readback — the copy
        rides under the next chunk's compute).  Byte-identical tokens to
        the async engine; kept as the parity baseline."""
        try:
            reads = []       # dispatched this chunk: [(toks, counts,
            inflight = None  # done, gens)]; previous chunk in host copy
            while not self._stop.is_set():
                idle = (all(s is None for s in self._slots)
                        and not self._admissions
                        and self._parked is None
                        and not reads and inflight is None)
                self._admit(block=idle)
                # one batched prefill round per loop iteration: up to
                # prefill_rows admissions advance one chunk each, then
                # decode steps below — the budget bounds the stall
                self._run_prefill_round()
                active = any(s is not None for s in self._slots)
                if active:
                    reads.append(self._dispatch())
                    self._depth.add(1)
                # Readback protocol (a per-token sync d2h stalls the
                # loop for a full round trip, whatever its size): stack a
                # chunk, START its host copy asynchronously, and process
                # the PREVIOUS chunk — whose copy has been riding under
                # this chunk's compute and is now free to read.  Steps
                # may overshoot a retiring slot by up to ~2 chunks; the
                # generation filter drops those tokens and the masked
                # cache write makes out-of-range positions no-ops.
                if self._flush_due(len(reads), active):
                    prev, inflight = inflight, self._flush(reads)
                    reads = []
                    if prev is not None:
                        # host work runs INLINE here — the serial
                        # engine's defining cost, counted as device wait
                        t0 = time.monotonic()
                        self._process_batch(prev)
                        self._depth.add(-len(prev[3]))
                        self.counters.inc(
                            "device_wait_ms",
                            (time.monotonic() - t0) * 1000.0)
                elif inflight is not None and not active and not reads:
                    # nothing more to dispatch: drain the in-flight chunk
                    self._process_batch(inflight)
                    self._depth.add(-len(inflight[3]))
                    inflight = None
        except BaseException as e:     # device failure: fail everything
            self._die(e, "continuous batcher died")

    def _loop_async(self):
        """Device side of the async pipeline: admission + dispatch only.
        Flushed chunks go to the host thread through the bounded
        `_ready` queue (its bound IS the pipeline depth); the only time
        this thread waits on host progress is when that queue is full —
        counted as device wait, the quantity stats() reports as
        device_idle_fraction."""
        import queue as queue_mod

        try:
            reads = []   # dispatched this chunk: [(toks, counts, done,
            while not self._stop.is_set():          # gens)]
                self._apply_retirements()
                idle = (all(s is None for s in self._slots)
                        and not self._admissions
                        and self._parked is None
                        and not reads
                        and self._depth.value == 0)
                self._admit(block=idle)
                self._run_prefill_round()
                active = any(s is not None for s in self._slots)
                if active:
                    reads.append(self._dispatch())
                    self._depth.add(1)
                if self._flush_due(len(reads), active):
                    chunk = self._flush(reads)
                    reads = []
                    t0 = time.monotonic()
                    waited = False
                    while not self._stop.is_set():
                        try:
                            self._ready.put(chunk, timeout=0.05)
                            break
                        except queue_mod.Full:
                            # host is behind: keep acks flowing (the
                            # host may be blocked on a retirement)
                            waited = True
                            self._apply_retirements()
                    if waited:
                        self.counters.inc(
                            "device_wait_ms",
                            (time.monotonic() - t0) * 1000.0)
                elif not active and not reads:
                    # nothing to dispatch: let retirements land promptly
                    self._apply_retirements(timeout=0.002)
        except BaseException as e:     # device failure: fail everything
            self._die(e, "continuous batcher died")

    def _die(self, e, msg):
        """Terminal failure of either engine thread: record the cause,
        stop the other thread, fail every queued / in-flight /
        mid-admission request, and release retire-ack waiters."""
        self._dead = e      # before the log line: a caller whose handle
        self._stop.set()    # already failed must find the engine dead
        logger.exception(msg)
        adms, self._admissions = self._admissions, []
        for adm in adms:
            adm["item"]["h"]._fail(e)
        parked, self._parked = self._parked, None
        if parked is not None:
            parked[1]["h"]._fail(e)
        for s in self._slots:
            if s is not None:
                s["handle"]._fail(e)
        self._slots = [None] * self.n_slots
        self._drain_pending(e)
        self._sweep_park_pool(e)
        self._ack_retire_waiters()


class GenerateService:
    """Autoregressive generation over an exported decoder LM.

    Rebuilds the exported module (export.load_model) and serves every
    request through ONE decode engine — the ContinuousBatcher (round 5
    unified the former grouped path onto slots: a request's tokens no
    longer depend on server flags, and concurrent requests always share
    the in-flight batch).  Only exports whose builder rebuilds a
    ``Transformer`` qualify; the endpoint reports 404 otherwise.
    Constructed LAZILY on the first :generate request so forward-only
    serving never pays a second param load.

    With speculation enabled (``--spec_draft`` / ``draft_export_dir``)
    decoding speculates inside the slots: greedy rows commit the
    target's own argmax (byte-identical by construction) and sampled
    rows verify by rejection sampling (distribution-preserving and
    seed-deterministic) — see decode._jitted_slot_spec_round_v2.
    ``spec_draft='ngram'`` needs no draft model at all: proposals come
    from suffix-matching the row's own context on device.
    """

    @staticmethod
    def _load_lm(export_dir, quantize_mode="none"):
        from . import export as export_mod
        from . import quantize as quantize_mod
        from .models.transformer import Transformer

        if quantize_mode not in (None,) + QUANTIZE_MODES:
            raise ValueError(
                f"quantize_mode={quantize_mode!r} not in {QUANTIZE_MODES}")
        # take the STORED tree: for an int8-quantized export served with
        # --generate_quantize int8 the artifact's qtree is used as-is —
        # no eager dequant + re-quantize round trip, and the full-width
        # tree never materializes (exactly the large-model case
        # quantization targets)
        built, params, spec = export_mod.load_model(export_dir,
                                                    dequantize=False)
        if not isinstance(built, Transformer):
            raise TypeError(
                f"export builder rebuilds {type(built).__name__}, not a "
                "Transformer — :generate serves decoder LMs only")
        import jax.numpy as jnp

        stored_q = spec.get("quantized") == "int8"
        if stored_q and quantize_mode != "int8":
            # the operator asked for full-width serving of a quantized
            # artifact: dequantize to the export's recorded width
            params = quantize_mod.dequantize_tree(
                params, dtype=spec.get("dequant_dtype"))
            stored_q = False
        if quantize_mode == "int8" and not stored_q:
            # weight-only W8A16: matmul kernels become {int8, f32 scale}
            # leaves that every jitted decode step consumes through the
            # Pallas fused-dequant matmul (decode._params_view ->
            # transformer.QuantDense -> ops.quant_matmul; inline dequant
            # under a mesh — either way the full-width kernel never
            # lands in HBM).  ~4x less resident weight memory and ~half
            # the per-token weight read vs the W16 store below; norm
            # scales / embeddings stay at compute width
            # (quantize.DEFAULT_TARGETS).  Quantize BEFORE the
            # compute-width cast: scales derive from the f32 masters,
            # not bf16-rounded copies, and the big kernels never pay a
            # cast that quantization then discards
            params = quantize_mod.quantize_tree(params)
        elif quantize_mode == "int4":
            # weight-only W4A16: 2-D kernels become nibble-packed
            # Int4Weight leaves (per-group scales) for the same fused
            # path — ~8x less resident weight vs f32, ~4x less weight
            # read per token vs bf16.  Exports never store int4 (the
            # artifact stays f32/int8), so packing always happens here;
            # a stored int8 artifact was dequantized just above
            params = quantize_mod.quantize_tree(params, mode="int4")
        compute = jnp.dtype(built.cfg.dtype)
        if jnp.issubdtype(compute, jnp.floating) and compute != jnp.float32:
            # serving reads every weight once per decoded token: store the
            # params at the model's compute width (W16) instead of the f32
            # masters — half the bytes per token.  Quantized leaves are
            # skipped: int8 payloads are already narrow and their scales
            # must stay f32
            params = quantize_mod.cast_float_leaves(params, compute)
        return built, params

    def __init__(self, export_dir, max_new_tokens_limit=512,
                 draft_export_dir=None, draft_k=4, spec_draft=None,
                 slots=8, read_chunk=8,
                 prefill_chunk=512, prefill_rows=4, prefill_budget=0,
                 request_timeout_s=None,
                 kv_page_size=0, kv_pages=0, host_cache_mb=0,
                 quantize_mode="none",
                 lora_rank=0, lora_capacity=8, lora_adapters=None,
                 kv_dtype="auto", paged_attn_impl=None,
                 paged_prefill_impl=None, engine="async",
                 pipeline_depth=2, prio_weight=4, preempt_ms=0.0,
                 park_capacity=8, long_prompt_threshold=0,
                 trace_ring=4096, trace_decode_sample=16):
        import itertools

        self.quantize_mode = quantize_mode or "none"
        self.model, self.params = self._load_lm(export_dir,
                                                self.quantize_mode)
        # weight-size accounting computed ONCE here: metadata() reports
        # it on every probe and fleet heartbeats probe metadata, so the
        # full param-tree walk must not run per probe
        self.weight_bytes = self.float_equivalent_bytes = 0
        if self.quantize_mode != "none":
            from . import quantize as quantize_mod

            self.weight_bytes, self.float_equivalent_bytes = (
                quantize_mod.quantized_bytes(self.params))
        draft_model = draft_params = None
        if draft_export_dir and spec_draft != "off":
            # speculative decoding: requests verify k draft tokens per
            # target pass — greedy rows commit EXACTLY the same tokens
            # and sampled rows the same distribution (the draft only
            # changes speed), so no request-level opt-in is needed.  The
            # draft quantizes with the target: speculation commits only
            # tokens the TARGET accepts, so draft quantization can never
            # change outputs, only the acceptance rate.  spec_draft
            # "off" skips the load entirely (A/B benching a replica
            # with the draft artifact still on disk)
            draft_model, draft_params = self._load_lm(draft_export_dir,
                                                      self.quantize_mode)
        self.batcher = ContinuousBatcher(
            self.model, self.params, n_slots=slots or 8,
            read_chunk=read_chunk, prefill_chunk=prefill_chunk,
            prefill_rows=prefill_rows, prefill_budget=prefill_budget,
            draft_model=draft_model, draft_params=draft_params,
            draft_k=draft_k, spec_draft=spec_draft,
            kv_page_size=kv_page_size, kv_pages=kv_pages,
            host_cache_mb=host_cache_mb,
            lora_rank=lora_rank, lora_capacity=lora_capacity,
            kv_dtype=(None if kv_dtype in (None, "auto") else kv_dtype),
            paged_attn_impl=paged_attn_impl,
            paged_prefill_impl=paged_prefill_impl,
            engine=engine or "async",
            pipeline_depth=pipeline_depth, prio_weight=prio_weight,
            preempt_ms=preempt_ms, park_capacity=park_capacity,
            long_prompt_threshold=long_prompt_threshold,
            trace_ring=trace_ring,
            trace_decode_sample=trace_decode_sample)
        try:
            for name, path in (lora_adapters or {}).items():
                # adapter files written by lora.save_adapters; a bad file
                # or mismatched shapes raises here (startup), not
                # per-request
                from . import lora as lora_mod

                adapters, scale = lora_mod.load_adapters(path)
                self.batcher.register_adapter(name, adapters, scale=scale)
        except Exception:
            # the batcher's driver thread is already running: a failed
            # startup registration must not leak it (and its device
            # cache) behind the propagating error
            self.batcher.stop()
            raise
        self.limit = max_new_tokens_limit
        # bound on a single request's wall time: decoding its own tokens
        # plus waiting behind a full house of equally-long requests, with
        # a generous floor for compiles (the first request pays them)
        self.timeout_s = request_timeout_s or max(
            600.0, 2.0 * max_new_tokens_limit)
        # requests that sample WITHOUT an explicit seed each get a fresh
        # one (identical unseeded prompts must not replay identical
        # noise); pass "seed" for reproducibility
        self._auto_seed = itertools.count(1 << 20)
        self.requests = 0
        # Idempotency-Key dedupe: the gateway attaches one key per
        # stream, so a recovery re-drive that lands back on a replica
        # still decoding the "lost" session (false-positive death: a
        # network blip, not a crash) cancels the orphan instead of
        # double-generating.  Recently-finished keys are kept for a TTL
        # so a late re-drive of a completed stream is observable
        # (counter) — the rerun itself is harmless: same seed, same
        # bytes.
        self._idem_lock = threading.Lock()
        self._idem_live = {}       # key -> live SlotHandle
        self._idem_done = {}       # key -> monotonic finish time
        self._idem_ttl_s = 120.0

    # values that reach the batcher's driver thread become int32 device
    # scalars there; an out-of-range int raising INSIDE the single driver
    # loop would kill the whole engine, so the range check happens here
    # (per-request 400, not a bricked server)
    _I32 = 1 << 31

    def _validate(self, req):
        inputs = req.get("inputs")
        if (not isinstance(inputs, list) or not inputs
                or not all(isinstance(p, list) and p and
                           all(_is_int(t)
                               and 0 <= t < self._I32 for t in p)
                           for p in inputs)):
            raise ValueError('"inputs" must be a non-empty list of '
                             "non-empty lists of token ids in [0, 2^31)")
        max_new = req.get("max_new_tokens", 16)
        if not _is_int(max_new) or not 1 <= max_new <= self.limit:
            raise ValueError(f'"max_new_tokens" must be an int in '
                             f"[1, {self.limit}]")
        temperature = float(req.get("temperature", 0.0))
        if temperature < 0:
            raise ValueError('"temperature" must be >= 0')
        eos_id = req.get("eos_id")
        if eos_id is not None and not (_is_int(eos_id)
                                       and -self._I32 <= eos_id < self._I32):
            raise ValueError('"eos_id" must be an int32')
        seed = req.get("seed")
        if seed is not None:
            if not (_is_int(seed)
                    and -self._I32 <= seed < self._I32 - len(inputs)):
                raise ValueError('"seed" must be an int32 (with headroom '
                                 "for per-prompt offsets)")
            seed = int(seed)
        adapter = req.get("adapter")
        if adapter is not None and not isinstance(adapter, str):
            raise ValueError('"adapter" must be a registered adapter name '
                             "(string)")
        top_k = req.get("top_k", 0)
        if not (_is_int(top_k) and 0 <= top_k < self._I32):
            raise ValueError('"top_k" must be an int >= 0')
        top_p = float(req.get("top_p", 1.0))
        if not 0.0 < top_p <= 1.0:
            raise ValueError('"top_p" must be in (0, 1]')
        min_p = float(req.get("min_p", 0.0))
        if not 0.0 <= min_p < 1.0:
            raise ValueError('"min_p" must be in [0, 1)')
        if (top_k or top_p < 1.0 or min_p > 0.0) and temperature <= 0:
            raise ValueError('"top_k"/"top_p"/"min_p" filter the sampled '
                             'distribution — set "temperature" > 0')
        stop = req.get("stop")
        if stop is not None:
            if (not isinstance(stop, list) or len(stop) > 16
                    or not all(isinstance(st, list) and st and len(st) <= 32
                               and all(_is_int(t)
                                       and -self._I32 <= t < self._I32
                                       for t in st)
                               for st in stop)):
                raise ValueError(
                    '"stop" must be a list (<= 16) of non-empty token-id '
                    "lists (<= 32 tokens each)")
        rep = req.get("repetition_penalty", 1.0)
        if not (isinstance(rep, (int, float)) and not isinstance(rep, bool)
                and 0 < rep <= 1e6):
            raise ValueError('"repetition_penalty" must be a number in '
                             "(0, 1e6] (1.0 disables)")
        priority = req.get("priority")
        if priority is not None and priority not in PRIORITY_CLASSES:
            raise ValueError(
                f'"priority" must be one of {list(PRIORITY_CLASSES)}')
        trace_id = req.get("trace")
        if trace_id is not None and not trace.valid_id(trace_id):
            raise ValueError(
                '"trace" must be a hex (dashes allowed) trace id of at '
                f"most {trace.MAX_ID_LEN} chars")
        return (inputs, max_new, temperature, eos_id, seed, adapter,
                top_k, top_p, min_p, stop, float(rep), priority,
                trace_id)

    def _idem_claim(self, key, h):
        """Register `h` as the live session for Idempotency-Key `key`,
        cancelling any prior live session under the same key: its
        consumer is gone (the gateway re-drives only streams whose
        relay broke), so letting it decode on would double-generate."""
        if key is None:
            return
        with self._idem_lock:
            now = time.monotonic()
            for k in [k for k, t in self._idem_done.items()
                      if now - t > self._idem_ttl_s]:
                del self._idem_done[k]
            prior = self._idem_live.get(key)
            if prior is not None and prior is not h:
                self.batcher.counters.inc("idempotency_cancels")
                prior.cancel()
            if key in self._idem_done:
                self.batcher.counters.inc("idempotency_reruns")
            self._idem_live[key] = h

    def _idem_finish(self, key, h):
        """Stream over: retire the live entry (only if still ours) and
        remember the key as recently finished."""
        if key is None:
            return
        with self._idem_lock:
            if self._idem_live.get(key) is h:
                del self._idem_live[key]
            self._idem_done[key] = time.monotonic()

    def _prompt_seeds(self, n, seed, temperature):
        """Per-prompt seeds: explicit seed s -> s, s+1, ... (documented
        reproducible); unseeded sampling -> a FRESH auto-seed per prompt
        (identical unseeded prompts must not replay identical noise, and
        consecutive requests must not overlap the way seed+i would);
        greedy keeps 0 so deterministic requests stay byte-stable."""
        if seed is not None:
            return [seed + i for i in range(n)]
        if temperature > 0:
            return [next(self._auto_seed) for _ in range(n)]
        return [0] * n

    def stream(self, req, on_handle=None, idem_key=None, kv_peer=None):
        """Yield JSON-able events for a single-prompt generation:
        ``{"token": t}`` per decoded token (eos-trimmed), then
        ``{"done": true, "output": [...full sequence...]}``.

        ``on_handle`` (the disaggregation hook) is called with the
        submitted SlotHandle before any event is produced — the
        prefill-role handoff arms migration there, so the session
        moves to a decode replica as soon as its first tokens flush."""
        # validate EAGERLY (before any response bytes): a malformed
        # request must 400, not die mid-stream after a 200 header
        (inputs, max_new, temperature, eos_id, seed, adapter,
         top_k, top_p, min_p, stop, rep, priority,
         trace_id) = self._validate(req)
        if len(inputs) != 1:
            raise ValueError('"stream": true serves exactly one prompt '
                             "per request")
        if kv_peer:
            # gateway-planted prefix peer: pull the pages the local
            # host tier lacks BEFORE submitting, so this admission
            # promotes them (failure = normal prefill, nothing to undo)
            self.batcher.prefetch_prefix(kv_peer, inputs[0],
                                         trace_id=trace_id)
        seed = self._prompt_seeds(1, seed, temperature)[0]
        h = self.batcher.submit(inputs[0], max_new, temperature=temperature,
                                eos_id=eos_id, seed=seed, adapter=adapter,
                                top_k=top_k, top_p=top_p, min_p=min_p,
                                stop=stop, repetition_penalty=rep,
                                priority=priority, trace_id=trace_id)
        self._idem_claim(idem_key, h)
        self.requests += 1
        if on_handle is not None:
            try:
                on_handle(h)
            except Exception:
                logger.warning("stream on_handle hook failed",
                               exc_info=True)

        def slot_events():
            try:
                while True:
                    batch = h.tokens.get()
                    if batch is None:
                        break
                    # the engine delivers token BATCHES (one per host
                    # tick); the event protocol stays per-token
                    for tok in batch:
                        yield {"token": tok}
                done = {"done": True, "output": h.result()}
                if trace_id:
                    # summary rides the FINAL event only — token events
                    # are byte-identical to an untraced stream
                    summ = self.batcher.trace.summary(trace_id)
                    if summ is not None:
                        done["trace"] = summ
                yield done
            finally:
                # consumer died/finished: free the slot instead of
                # decoding to max_new for a client nobody serves
                h.cancel()
                self._idem_finish(idem_key, h)

        return slot_events()

    def generate(self, req, kv_peer=None, idem_key=None):
        (inputs, max_new, temperature, eos_id, seed, adapter,
         top_k, top_p, min_p, stop, rep, priority,
         trace_id) = self._validate(req)
        if kv_peer:
            for p in inputs:
                self.batcher.prefetch_prefix(kv_peer, p,
                                             trace_id=trace_id)
        seeds = self._prompt_seeds(len(inputs), seed, temperature)
        # every prompt becomes a slot request; they decode concurrently
        # with each other AND with other HTTP requests' prompts (no
        # service lock -- the batcher's driver thread owns the device)
        handles = []
        claims = []
        try:
            for i, (p, s) in enumerate(zip(inputs, seeds)):
                h = self.batcher.submit(
                    p, max_new, temperature=temperature, eos_id=eos_id,
                    seed=s, adapter=adapter, top_k=top_k, top_p=top_p,
                    min_p=min_p, stop=stop, repetition_penalty=rep,
                    priority=priority, trace_id=trace_id)
                handles.append(h)
                if idem_key is not None:
                    # the one-shot dedupe (bulk jobs lean on this): a
                    # duplicate dispatch under the same key cancels the
                    # orphaned twin instead of double-generating
                    k = (idem_key if len(inputs) == 1
                         else f"{idem_key}/{i}")
                    self._idem_claim(k, h)
                    claims.append((k, h))
            outs = [h.result(timeout=self.timeout_s) for h in handles]
        except Exception:
            # a failed request (one prompt too long, a timeout) must not
            # leave its other prompts decoding for a client that already
            # got an error
            for h in handles:
                h.cancel()
            raise
        finally:
            for k, h in claims:
                self._idem_finish(k, h)
        self.requests += 1
        return outs

    def resume(self, req, idem_key=None):
        """``POST :resume`` — continue a session that left its replica.

        Two modes share the splice-ack event protocol.  With ``meta`` +
        ``pull`` (migration), the kv snapshot is pulled from the
        source's page server and installed without prefill.  With
        ``replay`` (crash recovery), there is no source left to pull
        from: the gateway's journaled token record re-prefills here and
        decode continues byte-identically.  Either way the FIRST event
        (``{"resumed": true}``) is the ack the caller keys off —
        migration sources free their pages on it, the gateway marks the
        re-drive live.  Validation (and the pull) happen eagerly
        (before any response bytes), so a bad snapshot 400s instead of
        dying mid-stream."""
        from . import kvtransfer

        replay = req.get("replay")
        if replay is not None:
            if not isinstance(replay, dict):
                raise ValueError('":resume" "replay" must be a meta '
                                 "object")
            h, installed = self.batcher.submit_replay(replay)
            self._idem_claim(idem_key, h)
            self.requests += 1
            return self._resume_events(h, installed, idem_key)
        meta, pull = req.get("meta"), req.get("pull")
        if not isinstance(meta, dict) or not isinstance(pull, dict):
            raise ValueError(':resume needs "meta" and "pull" objects '
                             '(or "replay")')
        if not pull.get("host") or not _is_int(pull.get("port")) \
                or not pull.get("ticket"):
            raise ValueError('"pull" must carry host, port and ticket')
        wire_meta, blocks = kvtransfer.pull_snapshot(
            (str(pull["host"]), int(pull["port"])), str(pull["ticket"]),
            timeout=min(60.0, self.timeout_s or 60.0))
        del wire_meta   # the HTTP meta is canonical; both come from the
        # same frozen record, the TCP copy just makes snapshots
        # self-describing for tooling
        h, installed = self.batcher.submit_resume(meta, blocks)
        self.requests += 1
        return self._resume_events(h, installed, None)

    def _resume_events(self, h, installed, idem_key):
        def resume_events():
            try:
                deadline = time.monotonic() + min(60.0,
                                                  self.timeout_s or 60.0)
                while not installed.wait(0.1):
                    if h._done.is_set():
                        # failed/cancelled before the row went live
                        try:
                            h.result(timeout=0)
                            yield {"error": "resume admission ended "
                                            "before install"}
                        except Exception as e:
                            yield {"error": f"{type(e).__name__}: {e}"}
                        return
                    if time.monotonic() >= deadline:
                        h.cancel()
                        yield {"error": "resume install timed out"}
                        return
                yield {"resumed": True}   # the splice ack — the source
                # frees its copy of the pages on reading this
                while True:
                    batch = h.tokens.get()
                    if batch is None:
                        break
                    for tok in batch:
                        yield {"token": tok}
                out = h.result()
                # tokens decoded on the SOURCE (prompt..resume point)
                # were already streamed from there; the relay appends
                # only what we produce, but `output` is the full
                # sequence so non-streaming consumers see one truth
                yield {"done": True, "output": out}
            finally:
                h.cancel()
                self._idem_finish(idem_key, h)

        return resume_events()


class _Handler(BaseHTTPRequestHandler):
    service = None   # injected by make_server
    # chunked transfer (the streaming :generate path) requires HTTP/1.1;
    # every non-stream response sets Content-Length, so keep-alive is safe
    protocol_version = "HTTP/1.1"

    def _send(self, code, payload, headers=()):
        body = json.dumps(payload).encode()
        self.send_response(code)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        for k, v in headers:
            self.send_header(k, v)
        self.end_headers()
        self.wfile.write(body)

    def _send_text(self, code, text):
        body = text.encode()
        self.send_response(code)
        self.send_header("Content-Type",
                         "text/plain; version=0.0.4; charset=utf-8")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def do_GET(self):
        name = self.service.model_name
        # EXACT path matching (modulo one trailing slash): endswith()
        # previously served metadata for /anything/v1/models/<name>
        path = self.path.rstrip("/") or "/"
        if path == "/healthz":
            # pure LIVENESS: the process answers.  Deliberately cheap and
            # unconditional — a draining or still-warming replica is
            # alive; restarts key off this, routing keys off /readyz.
            self._send(200, {"status": "ok"})
        elif path == "/readyz":
            # READINESS: should this replica receive new work?
            if self.service.draining:
                self._send(503, {"status": "draining"},
                           headers=[("Retry-After", "1")])
            else:
                self._send(200, {"status": "ok"})
        elif path in ("/metrics", "/v1/metrics"):
            # Prometheus scrape, generated from the same stats() the
            # fleet probes; an injected trace.export fault 500s the
            # SCRAPE only — serving never notices
            try:
                faults.check("trace.export")
                text = self.service.metrics_text()
            except Exception as e:
                self._send(500, {"error": f"{type(e).__name__}: {e}"})
                return
            self._send_text(200, text)
        elif path.startswith("/v1/trace/"):
            tid = path[len("/v1/trace/"):]
            if not trace.valid_id(tid):
                self._send(400, {"error": "malformed trace id"})
                return
            try:
                faults.check("trace.export")
                spans = self.service.trace_spans(tid)
            except Exception as e:
                self._send(500, {"error": f"{type(e).__name__}: {e}"})
                return
            self._send(200, {"id": tid, "spans": spans})
        elif path == "/" or path == f"/v1/models/{name}":
            self._send(200, self.service.metadata())
        else:
            self._send(404, {"error": f"unknown path {self.path}"})

    def do_POST(self):
        name = self.service.model_name
        if self.path.rstrip("/") == "/v1/fleet:drain":
            # replica-side drain hook: fence admissions, wait for the
            # slot engine to empty (fleet.Gateway.drain calls this after
            # its own proxied in-flight count reaches zero)
            self._send(200, self.service.drain())
            return
        if self.path.rstrip("/") == "/v1/kv:export":
            # migrate live sessions out (the :migrate drain mode's
            # replica hook).  Deliberately NOT fenced on draining — a
            # draining replica is exactly the one exporting its kv
            try:
                length = int(self.headers.get("Content-Length", 0))
                body = json.loads(self.rfile.read(length) or b"{}")
                if not isinstance(body, dict):
                    raise ValueError("request body must be a JSON object")
                self._send(200, self.service.kv_export(body))
            except (ValueError, KeyError, TypeError) as e:
                self._send(400, {"error": f"{type(e).__name__}: {e}"})
            except Exception as e:
                logger.exception("kv:export failed")
                self._send(500, {"error": f"{type(e).__name__}: {e}"})
            return
        if self.path.rstrip("/") == "/v1/debug:profile":
            # time-bounded on-device profile capture (jax.profiler) —
            # the "why is the device idle" layer under
            # device_idle_fraction.  Not fenced on draining: a
            # misbehaving replica is exactly the one worth profiling
            try:
                length = int(self.headers.get("Content-Length", 0))
                body = json.loads(self.rfile.read(length) or b"{}")
                if not isinstance(body, dict):
                    raise ValueError("request body must be a JSON object")
                code, payload = self.service.debug_profile(body)
                self._send(code, payload)
            except (ValueError, KeyError, TypeError) as e:
                self._send(400, {"error": f"{type(e).__name__}: {e}"})
            except Exception as e:
                logger.exception("debug:profile failed")
                self._send(500, {"error": f"{type(e).__name__}: {e}"})
            return
        is_predict = self.path == f"/v1/models/{name}:predict"
        is_generate = self.path == f"/v1/models/{name}:generate"
        is_resume = self.path == f"/v1/models/{name}:resume"
        if not (is_predict or is_generate or is_resume):
            self._send(404, {"error": f"unknown path {self.path} (serving "
                             f"model {name!r})"})
            return
        if self.service.draining:
            self._send(503, {"error": "replica is draining",
                             "type": "draining"},
                       headers=[("Retry-After", "1")])
            return
        try:
            length = int(self.headers.get("Content-Length", 0))
            req = json.loads(self.rfile.read(length) or b"{}")
            if not isinstance(req, dict):
                raise ValueError("request body must be a JSON object")
            if is_generate or is_resume:
                gen = self.service.generate_service()
                if gen is None:
                    reason = getattr(self.service, "_gen_error", None)
                    self._send(404, {"error": ":generate unavailable: "
                                     + (reason or "this export is not a "
                                        "decoder LM")})
                    return
                idem_key = self.headers.get("Idempotency-Key")
                prio = self.headers.get("X-Priority")
                if is_generate and prio and "priority" not in req:
                    # header form of the body field (the gateway resolves
                    # a tenant's class and forwards it this way); an
                    # invalid value 400s in _validate like the body form
                    req["priority"] = prio
                tid_hdr = self.headers.get("X-Trace-Id")
                if is_generate and tid_hdr and "trace" not in req:
                    # header form of the trace id, mirroring X-Priority
                    req["trace"] = tid_hdr
                if is_resume:
                    # always streams: the first ndjson event is the
                    # splice ack (migration or crash replay), the rest
                    # is the token relay back to the caller
                    self._stream_events(gen.resume(req,
                                                   idem_key=idem_key))
                elif req.get("stream"):
                    on_handle = None
                    migrate_to = self.headers.get("X-Fleet-Migrate-To")
                    if migrate_to:
                        # gateway-planted disaggregation handoff: this
                        # replica prefills, the named replica decodes
                        on_handle = self.service.auto_migrate_hook(
                            migrate_to)
                    # gateway-planted prefix peer (hierarchical kv
                    # cache): the affinity replica likely holds this
                    # conversation's demoted pages — prefetch them
                    kv_peer = self.headers.get("X-Fleet-KV-Peer")
                    self._stream_events(gen.stream(req,
                                                   on_handle=on_handle,
                                                   idem_key=idem_key,
                                                   kv_peer=kv_peer))
                else:
                    self._send(200, {"outputs": gen.generate(
                        req, kv_peer=self.headers.get("X-Fleet-KV-Peer"),
                        idem_key=idem_key)})
            else:
                preds = self.service.predict(req.get("instances"))
                self._send(200, {"predictions": preds})
        except (ValueError, KeyError, TypeError, AttributeError) as e:
            # malformed client input in any shape -> 400
            self._send(400, {"error": str(e) or type(e).__name__})
        except KVOverflowError as e:
            # the request is well-formed but cannot fit this replica's
            # kv (device pool + host tier, replica idle): typed 503 so
            # the gateway retries it on a peer with more headroom
            self._send(503, {"error": str(e), "type": "kv_overflow"})
        except Exception as e:   # keep the server alive on model errors
            logger.exception("predict failed")
            self._send(500, {"error": f"{type(e).__name__}: {e}"})

    def _stream_events(self, events):
        """Write newline-delimited JSON events with chunked framing, one
        chunk per event, so clients see tokens as they decode."""
        self.send_response(200)
        self.send_header("Content-Type", "application/x-ndjson")
        self.send_header("Transfer-Encoding", "chunked")
        self.end_headers()

        def chunk(data):
            self.wfile.write(f"{len(data):X}\r\n".encode() + data + b"\r\n")
            self.wfile.flush()

        try:
            for ev in events:
                chunk(json.dumps(ev).encode() + b"\n")
        except Exception as e:   # mid-stream: emit an error event, end clean
            logger.exception("stream failed")
            try:
                chunk(json.dumps(
                    {"error": f"{type(e).__name__}: {e}"}).encode() + b"\n")
            except OSError:
                pass
        try:
            self.wfile.write(b"0\r\n\r\n")
        except OSError:
            pass

    def log_message(self, fmt, *args):
        logger.debug("http: " + fmt, *args)


def make_server(args: Any) -> "tuple[ThreadingHTTPServer, ModelService]":
    """Build (server, service); caller runs serve_forever()."""
    # fail FAST on invalid combinations: GenerateService is constructed
    # lazily on the first :generate request, where a config error would
    # otherwise be swallowed by the is-this-a-decoder-LM probe and turn
    # into a misleading 404
    if getattr(args, "generate_slots", 8) < 1:
        raise ValueError("--generate_slots must be >= 1: slots are the "
                         ":generate decode engine (round 5 unified the "
                         "grouped path onto them)")
    if getattr(args, "generate_kv_page_size", 0) and \
            getattr(args, "generate_kv_pages", 0) < 1:
        raise ValueError("--generate_kv_page_size needs "
                         "--generate_kv_pages >= 1 (the shared pool size)")
    if getattr(args, "generate_host_cache_mb", 0) < 0:
        raise ValueError("--generate_host_cache_mb must be >= 0 "
                         "(0 disables the host-DRAM kv page tier)")
    if getattr(args, "generate_host_cache_mb", 0) and \
            not getattr(args, "generate_kv_page_size", 0):
        raise ValueError("--generate_host_cache_mb needs "
                         "--generate_kv_page_size > 0 (the host tier "
                         "holds demoted pages of the paged kv cache)")
    if getattr(args, "generate_long_prompt_threshold", 0) < 0:
        raise ValueError("--generate_long_prompt_threshold must be >= 0 "
                         "(0 disables the mega-prompt lane)")
    if getattr(args, "generate_long_prompt_threshold", 0) and \
            not getattr(args, "generate_kv_page_size", 0):
        raise ValueError("--generate_long_prompt_threshold needs "
                         "--generate_kv_page_size > 0 (the mega-prompt "
                         "lane allocates kv pages lazily per chunk)")
    if getattr(args, "generate_lora", None) and \
            not getattr(args, "generate_lora_rank", 0):
        raise ValueError("--generate_lora needs --generate_lora_rank > 0 "
                         "(the bank's adapter rank)")
    # spec_draft resolves inside ContinuousBatcher (None -> 'model' when
    # a draft export is given, else 'off'); the fail-fast checks here
    # mirror that resolution so a CLI typo surfaces at startup, not as a
    # misleading :generate 404.  LoRA composes with speculation since
    # v2 (base-weight draft, adapted verify), so no lora x draft guard.
    _spec = getattr(args, "spec_draft", None)
    _draft_dir = getattr(args, "draft_export_dir", None)
    if _spec == "model" and not _draft_dir:
        raise ValueError("--spec_draft model needs --draft_export_dir "
                         "(the draft LM to propose with); use "
                         "--spec_draft ngram for model-free speculation")
    if _spec == "ngram" and _draft_dir:
        raise ValueError("--spec_draft ngram is model-free — drop "
                         "--draft_export_dir (or pick --spec_draft model)")
    _model_draft = bool(_draft_dir) and _spec in (None, "model")
    if getattr(args, "generate_prefill_rows", 4) < 1:
        raise ValueError("--generate_prefill_rows must be >= 1 "
                         "(1 = sequential admission)")
    if getattr(args, "generate_prefill_budget", 0) < 0:
        raise ValueError("--generate_prefill_budget must be >= 0 "
                         "(0 = prefill_rows * prefill_chunk)")
    if getattr(args, "generate_engine", "async") not in ("async", "serial"):
        raise ValueError("--generate_engine must be 'async' or 'serial'")
    if getattr(args, "generate_pipeline_depth", 2) < 1:
        raise ValueError("--generate_pipeline_depth must be >= 1 "
                         "(flushed chunks in flight device->host)")
    if getattr(args, "role", "mixed") not in ("mixed", "prefill", "decode"):
        raise ValueError("--role must be 'mixed', 'prefill' or 'decode'")
    if getattr(args, "role", "mixed") != "mixed" and _model_draft:
        raise ValueError("--role prefill/decode does not compose with "
                         "--draft_export_dir (kv migration cannot ship "
                         "the draft model's cache); --spec_draft ngram "
                         "keeps no draft cache and composes")
    if getattr(args, "generate_priority_weight", 4) < 1:
        raise ValueError("--generate_priority_weight must be >= 1 "
                         "(interactive admissions per batch admission)")
    if getattr(args, "generate_preempt_ms", 0.0) < 0:
        raise ValueError("--generate_preempt_ms must be >= 0 "
                         "(0 disables the preemption controller)")
    if getattr(args, "generate_preempt_ms", 0.0) and _model_draft:
        raise ValueError("--generate_preempt_ms does not compose with "
                         "--draft_export_dir (freeze_session cannot cut "
                         "a row mid-round through the draft cache); "
                         "--spec_draft ngram composes")
    if getattr(args, "generate_park_capacity", 8) < 1:
        raise ValueError("--generate_park_capacity must be >= 1 "
                         "(the preemption controller's park pool bound)")
    service = ModelService(args)
    handler = type("BoundHandler", (_Handler,), {"service": service})

    class _Server(ThreadingHTTPServer):
        # server_close() tears the service down too (slot-batcher driver
        # thread, device caches) so `with`-style and finally-block
        # shutdowns release everything
        def server_close(self):
            super().server_close()
            service.close()

    server = _Server((args.host, args.port), handler)
    return server, service


def _register_with_fleet(args: Any, server: ThreadingHTTPServer,
                         service: "ModelService | None" = None):
    """Join the fleet gateway named by ``--fleet HOST:PORT``: REG this
    replica's advertised endpoint + capacity over the reservation plane
    and start the liveness heartbeat.  Returns the live registration
    (caller must ``deregister()`` at shutdown so the gateway drops the
    replica immediately instead of waiting out the heartbeat window)."""
    from . import fleet_client

    ghost, _, gport = args.fleet.rpartition(":")
    if not ghost or not gport.isdigit():
        raise ValueError(f"--fleet must be HOST:PORT, got {args.fleet!r}")
    features = {}
    if getattr(args, "generate_kv_page_size", 0):
        # the gateway sizes its :generate prefix-affinity hash off this,
        # aligning routing keys with the replica prefix-cache page unit
        features["kv_page_size"] = args.generate_kv_page_size
        features["kv_pages"] = args.generate_kv_pages
        features["paged_attn_impl"] = (
            getattr(args, "generate_paged_attn", None) or "kernel")
        features["paged_prefill_impl"] = (
            getattr(args, "generate_paged_prefill", None) or "kernel")
    if getattr(args, "generate_host_cache_mb", 0) and \
            getattr(args, "generate_kv_page_size", 0):
        # hierarchical kv cache: advertise the kv:prefix pull endpoint
        # so the gateway can point spilled requests at this replica's
        # host tier (REG features are static — force the PageServer
        # bind now).  A non-LM export just skips the feature
        features["host_cache_mb"] = args.generate_host_cache_mb
        try:
            eng = (service.migration_engine()
                   if service is not None else None)
        except Exception:
            logger.warning("kv:prefix endpoint unavailable",
                           exc_info=True)
            eng = None
        if eng is not None:
            features["kv_prefix_addr"] = eng.prefix_addr()
    if getattr(args, "generate_long_prompt_threshold", 0):
        # mega-prompt lane: the gateway routes prompts above this to
        # the lane-capable replica with the most kv headroom
        # (kv_pages * kv_page_size) instead of by prefix affinity
        features["long_prompt_threshold"] = (
            args.generate_long_prompt_threshold)
    # speculation: advertise the resolved draft mode (None defaults to
    # 'model' with a draft export, 'off' without — same resolution as
    # ContinuousBatcher) so dashboards can tell ngram replicas (zero
    # extra weight bytes) from model-draft ones
    _spec = getattr(args, "spec_draft", None)
    if _spec is None:
        _spec = ("model" if getattr(args, "draft_export_dir", None)
                 else "off")
    if _spec != "off":
        features["speculative"] = _spec
        features["draft_k"] = getattr(args, "draft_k", 4)
    if getattr(args, "generate_quantize", "none") != "none":
        features["quantize"] = args.generate_quantize
    if getattr(args, "generate_lora_rank", 0):
        features["lora_rank"] = args.generate_lora_rank
    # admission pipeline width: fleet dashboards read it next to slots
    features["prefill_rows"] = getattr(args, "generate_prefill_rows",
                                       4) or 4
    features["engine"] = getattr(args, "generate_engine", "async") or "async"
    # disaggregation: the gateway routes :generate admissions by role and
    # plants the migrate-to header for prefill replicas
    features["role"] = getattr(args, "role", "mixed") or "mixed"
    if getattr(args, "generate_preempt_ms", 0.0):
        features["preempt_ms"] = args.generate_preempt_ms
    return fleet_client.register_replica(
        (ghost, int(gport)),
        args.advertise_host or args.host,
        server.server_address[1],
        model_name=args.model_name,
        n_slots=getattr(args, "generate_slots", 8) or 8,
        features=features,
        heartbeat_interval_s=args.fleet_heartbeat_s)


def main(argv: Any = None) -> None:
    args = build_argparser().parse_args(argv)
    logging.basicConfig(
        level=logging.DEBUG if args.verbose else logging.INFO,
        format="%(asctime)s %(levelname)s %(message)s")
    server, service = make_server(args)
    host, port = server.server_address[:2]
    logger.info("serving %s (%s) on http://%s:%d", args.export_dir,
                service.desc, host, port)
    print(f"serving on http://{host}:{port} ({service.desc})", flush=True)
    registration = None
    if getattr(args, "fleet", None):
        registration = _register_with_fleet(args, server, service)
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        if registration is not None:
            registration.deregister()
        server.server_close()


if __name__ == "__main__":
    main()
