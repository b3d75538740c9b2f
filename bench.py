"""Benchmark entry point (run by the driver on real TPU hardware).

Prints ONE JSON line: {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}.

Metric (round 5+): **flagship-LM training MFU** on the RECOMMENDED
decoder config — `benchmarks.FLAGSHIP_LM_V2`: 0.87B params, d2048, 16
layers, GQA 16h/8kv (narrow k/v feed the GQA-native flash kernel
directly), d_ff 8192, S=1024, batch 8, bf16, RoPE, RMSNorm, adamw with
bf16 first moment — the framework's north-star workload class
(BASELINE.json: large-model training at >60% MFU).  MFU uses the
standard 6·N·T FLOP estimate over the chip's bf16 peak — conservative
(attention FLOPs excluded).

Metric history: rounds 1-2 used MNIST CNN images/sec (kept in aux);
rounds 3-4 used the same dims with LayerNorm (`FLAGSHIP_LM`, frozen for
comparability).  Round 5 re-baselined to RMSNorm (its `lm_mfu_
layernorm_v1` transition row has served its round and is retired).
Round 6 switches the flagship OPTIMIZER to the single-pass fused AdamW
kernel (`benchmarks.FLAGSHIP_OPTIMIZER = "adamw_fused"`,
ops/fused_optim.py) — same math and model config, fewer HBM passes; the
optax reference is measured in aux for THIS transition round
(`lm_mfu_adamw_unfused`), the same protocol as every metric change.

Round 6 also adds an `opt_ms` aux segment: the flagship step re-timed
with a zero-lr momentum-less SGD update ("sgd0" — the cheapest possible
optimizer) and `opt_ms = step_ms - step_ms_sgd0`, isolating what the
optimizer update costs per step so the fused kernel's win stays visible
in the trajectory.  `bench.py --segments` runs ONLY the segment
comparisons (SEGMENTS registry; one JSON line each, and exits 0 with a
"skipped" line per segment off-TPU, so CI can smoke the path).  Round 7
adds the `decode_ms` segment: the steady-state paged slot-decode step
(benchmarks.make_decode_step) timed with the flash-decode kernel vs the
einsum full-gather reference (TransformerConfig.paged_attn_impl).
Round 8 adds the `ttft_ms` segment: burst time-to-first-token through
the batched admission pipeline (benchmarks.make_prefill_burst,
prefill_rows=4) vs the sequential baseline (prefill_rows=1), plus
`--list-segments` so CI can discover the registry without a TPU.
Round 9 adds the `engine_tps` segment: sustained decode tokens/s
through the full continuous batcher (benchmarks.make_engine_burst) —
the async double-buffered engine vs the serialized single-thread loop,
with the device-idle fraction and pipeline-depth peak in aux.
The `prefill_ms` segment prices the paged S>1 chunk dispatch: the
Pallas in-place page-write prefill kernel vs the full-pool einsum
blend (benchmarks.make_prefill_chunk_step), with the analytic kv
write-traffic contrast in aux.
The `qmm_ms` segment prices the fused-dequant weight matmuls
(ops/quant_matmul.py): one decode-shaped flagship projection
(benchmarks.make_qmm_op) per weight store — int8 and nibble-packed
int4 Pallas kernels vs the dense bf16 baseline, with the analytic
weight-bytes contrast in aux; `decode_ms` and `engine_tps` each gain
an int8-quantized pass (aux) so the end-to-end decode win is priced
where operators feel it.

On a device whose bf16 peak is unknown (not in benchmarks.PEAK_BF16) the
metric falls back to tokens/sec — an MFU percent against a guessed peak
would be a fabricated number.

vs_baseline compares against the round-1 flagship-LM MFU figure (47%,
benchmarks.ROUND1_LM_MFU — taken on an earlier runtime, never re-measured
on this chip), since the reference publishes no numbers (BASELINE.json:
"published": {}).

Timing methodology (unchanged from round 1): host-readback barrier
(np.asarray of the scalar loss); device-resident batches; donated train
state; best-of-3 windows against dispatch-latency noise.  chip_smoke.py
times the same step by both barriers (block_until_ready and readback) so
the benchmark PR can settle which one to keep.
"""
import argparse
import json
import os
import queue
import sys
import time

from tensorflowonspark_tpu.benchmarks import (
    FLAGSHIP_BATCH, ROUND1_LM_MFU, bf16_peak, make_flagship_step)


def bench_flagship_lm(steps=10, windows=3, config="v2", optimizer=None):
    """Best-of-`windows` step time for the flagship LM; returns
    (mfu_pct_or_None, tokens_per_sec, step_ms, n_params).  ``optimizer``
    passes through to make_flagship_step (None = the headline default)."""
    import numpy as np

    import jax

    step, state, tokens, n_params = make_flagship_step(config=config,
                                                       optimizer=optimizer)
    B, S = tokens.shape[0], tokens.shape[1] - 1

    state, m = step(state, tokens, jax.random.key(1))
    np.asarray(m["loss"])                          # compile + sync
    best = float("inf")
    for _ in range(windows):
        t0 = time.perf_counter()
        for _ in range(steps):
            state, m = step(state, tokens, jax.random.key(1))
        np.asarray(m["loss"])                      # host readback barrier
        best = min(best, (time.perf_counter() - t0) / steps)

    peak = bf16_peak(jax.devices()[0].device_kind)
    mfu = (6 * n_params * B * S / best / peak * 100) if peak else None
    return mfu, B * S / best, best * 1000, n_params


def bench_mnist_cnn(batch_size=1024, steps=240, warmup=10):
    """Round-1/2 continuity metric: MNIST CNN images/sec, same harness
    (device-resident batches, donated state, readback-synced windows)."""
    import numpy as np

    import jax
    import jax.numpy as jnp
    import optax

    from tensorflowonspark_tpu.models.cnn import MnistCNN
    from tensorflowonspark_tpu.models.mlp import cross_entropy_loss
    from tensorflowonspark_tpu.parallel import train as train_mod

    model = MnistCNN()
    rng = jax.random.key(0)
    X = jax.device_put(
        np.random.RandomState(0).rand(batch_size, 28, 28, 1).astype("float32"))
    y = jax.device_put(
        np.random.RandomState(1).randint(0, 10, batch_size).astype("int32"))
    params = model.init(rng, jnp.zeros((1, 28, 28, 1)))["params"]

    def loss_fn(params, batch, rng):
        Xb, yb = batch
        logits = model.apply({"params": params}, Xb)
        return cross_entropy_loss(logits, yb)

    opt = optax.adam(1e-3)
    state = train_mod.TrainState(jnp.zeros((), jnp.int32), params,
                                 opt.init(params))
    step = train_mod.make_train_step(loss_fn, opt, donate=True)

    for _ in range(warmup):
        state, metrics = step(state, (X, y), rng)
    np.asarray(metrics["loss"])
    best = 0.0
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(steps):
            state, metrics = step(state, (X, y), rng)
        np.asarray(metrics["loss"])
        dt = time.perf_counter() - t0
        best = max(best, batch_size * steps / dt)
    return best


def bench_opt_segment(steps=10, windows=3):
    """The optimizer segment of the flagship step: full fused update vs
    the zero-lr momentum-less SGD floor.  Returns (full_ms, sgd0_ms,
    opt_ms) — opt_ms is what the optimizer update costs per step."""
    _, _, full_ms, _ = bench_flagship_lm(steps=steps, windows=windows)
    _, _, sgd0_ms, _ = bench_flagship_lm(steps=steps, windows=windows,
                                         optimizer="sgd0")
    return full_ms, sgd0_ms, full_ms - sgd0_ms


def bench_decode_segment(steps=32, windows=3):
    """The serving-decode segment: steady-state paged slot-decode step
    time on the flagship dims (benchmarks.make_decode_step /
    FLAGSHIP_DECODE — max_seq 4096, rows filled to 2000 tokens, the
    gather path's worst case), flash-decode kernel vs the einsum
    full-gather reference, plus a third pass with the weights int8-
    quantized through the fused-dequant quant_matmul path (weight-only
    W8A16 — the serving --generate_quantize int8 store).  Returns
    (kernel_ms, einsum_ms, int8_ms)."""
    import numpy as np

    from tensorflowonspark_tpu.benchmarks import make_decode_step

    def timed(impl, quantize=None):
        step, params, cache, (toks, temps, seeds, ords) = \
            make_decode_step(impl, quantize=quantize)
        toks, cache, ords = step(params, cache, toks, temps, seeds, ords)
        np.asarray(toks)                           # compile + sync
        best = float("inf")
        for _ in range(windows):
            t0 = time.perf_counter()
            for _ in range(steps):
                toks, cache, ords = step(params, cache, toks, temps,
                                         seeds, ords)
            np.asarray(toks)                       # host readback barrier
            best = min(best, (time.perf_counter() - t0) / steps)
        return best * 1000

    return timed("kernel"), timed("einsum"), timed("kernel", "int8")


def bench_qmm_segment(steps=64, windows=3):
    """The quantized-matmul segment: one flagship projection matmul
    (benchmarks.make_qmm_op / FLAGSHIP_QMM — 16 decode rows through the
    2048x8192 kernel) per weight store, the fused-dequant int8 and
    nibble-packed int4 Pallas kernels (ops.quant_matmul) vs the dense
    bf16 compute-width baseline.  Decode matmuls are weight-read-bound,
    so ms should track benchmarks.qmm_weight_bytes.  Returns
    {mode: ms} for modes bf16/int8/int4."""
    import numpy as np

    from tensorflowonspark_tpu.benchmarks import make_qmm_op

    out = {}
    for mode in ("bf16", "int8", "int4"):
        fn, x, w = make_qmm_op(mode)
        y = fn(x, w)
        np.asarray(y)                              # compile + sync
        best = float("inf")
        for _ in range(windows):
            t0 = time.perf_counter()
            for _ in range(steps):
                y = fn(x, w)
            np.asarray(y)                          # host readback barrier
            best = min(best, (time.perf_counter() - t0) / steps)
        out[mode] = best * 1000
    return out


def bench_prefill_segment(steps=16, windows=3):
    """The paged-prefill segment: steady-state batched multi-row prefill
    chunk dispatch on the flagship dims
    (benchmarks.make_prefill_chunk_step / FLAGSHIP_PREFILL_KERNEL — rows
    holding 2000 tokens of paged context, 256-token chunks), Pallas
    in-place page-write kernel vs the full-pool einsum blend reference.
    Returns (kernel_ms, blend_ms)."""
    import numpy as np

    from tensorflowonspark_tpu.benchmarks import make_prefill_chunk_step

    def timed(impl):
        prefill, params, cache, (chunks, rows, starts, n_valids, sink) = \
            make_prefill_chunk_step(impl)
        logits, cache = prefill(params, cache, chunks, rows, starts,
                                n_valids, sink)
        np.asarray(logits)                         # compile + sync
        best = float("inf")
        for _ in range(windows):
            t0 = time.perf_counter()
            for _ in range(steps):
                logits, cache = prefill(params, cache, chunks, rows,
                                        starts, n_valids, sink)
            np.asarray(logits)                     # host readback barrier
            best = min(best, (time.perf_counter() - t0) / steps)
        return best * 1000

    return timed("kernel"), timed("blend")


def bench_ttft_segment(reps=3, result_timeout=600):
    """The admission segment: steady-state time-to-first-token for a
    burst of queued prompts through the continuous batcher
    (benchmarks.make_prefill_burst / FLAGSHIP_PREFILL), batched
    multi-row prefill vs the sequential admission baseline
    (prefill_rows=1).  Per config: one warmup burst pays the compiles,
    then best mean-TTFT of the remaining bursts, read from the
    batcher's own ttft counters (stats() deltas — the same numbers
    operators see).  Returns (batched_ms, sequential_ms)."""
    from tensorflowonspark_tpu.benchmarks import (FLAGSHIP_PREFILL,
                                                  make_prefill_burst)

    def timed(rows):
        batcher, prompts, max_new = make_prefill_burst(prefill_rows=rows)
        try:
            best = float("inf")
            for rep in range(max(2, reps)):
                s0 = batcher.stats()
                handles = [batcher.submit(p, max_new) for p in prompts]
                for h in handles:
                    h.result(timeout=result_timeout)
                s1 = batcher.stats()
                n = max(1, s1["ttft_count"] - s0["ttft_count"])
                avg = (s1["ttft_ms_sum"] - s0["ttft_ms_sum"]) / n
                if rep:              # burst 0 is the compile warmup
                    best = min(best, avg)
        finally:
            batcher.stop()
        return best

    return timed(FLAGSHIP_PREFILL["prefill_rows"]), timed(1)


def bench_engine_segment(reps=3, result_timeout=600):
    """The engine segment: sustained decode tokens/s through the FULL
    ContinuousBatcher (benchmarks.make_engine_burst / FLAGSHIP_ENGINE)
    — admission, dispatch, readback, stream delivery — async
    double-buffered pipeline vs the serialized single-thread baseline.
    Per engine: one warmup burst pays the compiles, then best
    tokens/s of the remaining bursts from wall clock (generated tokens
    only).  A third pass re-runs the async engine with EVERY request
    traced (fresh trace id per submit) to price the observability
    layer: its span recording must be lost in the noise, and the
    ``trace_overhead`` aux keeps that claim regression-checked.  A
    fourth pass re-runs the async engine with the weights int8-
    quantized (the fused-dequant quant_matmul store serving uses for
    --generate_quantize int8) to price weight-only quantization at the
    full-batcher level.  Returns (async_tps, traced_tps, serial_tps,
    int8_tps, stats) where ``stats`` holds the async engine's
    device_idle_fraction and pipeline_depth_peak."""
    from tensorflowonspark_tpu import trace
    from tensorflowonspark_tpu.benchmarks import make_engine_burst

    def timed(engine, traced=False, quantize=None):
        batcher, prompts, max_new = make_engine_burst(engine=engine,
                                                      quantize=quantize)
        try:
            best = 0.0
            for rep in range(max(2, reps)):
                t0 = time.perf_counter()
                handles = [
                    batcher.submit(p, max_new,
                                   trace_id=(trace.new_id() if traced
                                             else None))
                    for p in prompts]
                total = sum(len(h.result(timeout=result_timeout)) - len(p)
                            for h, p in zip(handles, prompts))
                tps = total / (time.perf_counter() - t0)
                if rep:              # burst 0 is the compile warmup
                    best = max(best, tps)
            stats = batcher.stats()
        finally:
            batcher.stop()
        return best, stats

    async_tps, astats = timed("async")
    traced_tps, _ = timed("async", traced=True)
    serial_tps, _ = timed("serial")
    int8_tps, _ = timed("async", quantize="int8")
    return async_tps, traced_tps, serial_tps, int8_tps, astats


def bench_spec_segment(reps=3, result_timeout=600):
    """The spec segment: sustained greedy decode tokens/s through the
    ContinuousBatcher with speculation in each mode
    (benchmarks.make_spec_burst / FLAGSHIP_SPEC) — "ngram" model-free
    prompt-lookup drafting, "model" a scaled-down draft LM, "off" the
    plain-step baseline.  The burst's prompts are repetitive (tiled
    motifs), the workload prompt-lookup exists for; acceptance rate and
    adaptive mean draft length ride along from ``stats()``.  Per mode:
    burst 0 pays the compiles, then best tokens/s of the remaining
    bursts (generated tokens / wall clock).  Returns
    ``(ngram_tps, model_tps, off_tps, ngram_stats, model_stats)``."""
    from tensorflowonspark_tpu.benchmarks import make_spec_burst

    def timed(mode):
        batcher, prompts, max_new = make_spec_burst(mode=mode)
        try:
            best = 0.0
            for rep in range(max(2, reps)):
                t0 = time.perf_counter()
                handles = [batcher.submit(p, max_new) for p in prompts]
                total = sum(len(h.result(timeout=result_timeout)) - len(p)
                            for h, p in zip(handles, prompts))
                tps = total / (time.perf_counter() - t0)
                if rep:              # burst 0 is the compile warmup
                    best = max(best, tps)
            stats = batcher.stats()
        finally:
            batcher.stop()
        return best, stats

    ngram_tps, nstats = timed("ngram")
    model_tps, mstats = timed("model")
    off_tps, _ = timed("off")
    return ngram_tps, model_tps, off_tps, nstats, mstats


def bench_migrate_segment(reps=5, result_timeout=600):
    """The migrate segment: one live paged session moved mid-decode
    between two ContinuousBatchers through a real kvtransfer.PageServer
    socket (benchmarks.make_migrate_pair / FLAGSHIP_MIGRATE) — freeze
    gather, wire framing, page pull, resume splice, end to end.  Rep 0
    pays the freeze/scatter compiles and is discarded; the rest report
    medians.  Returns ``(migrate_ms, stall_ms, pages_per_s, n_pages,
    nbytes)`` where ``stall_ms`` is the client-visible token gap across
    the cut (last token streamed by the source to first token streamed
    by the destination)."""
    import statistics

    from tensorflowonspark_tpu import kvtransfer
    from tensorflowonspark_tpu.benchmarks import make_migrate_pair

    src, dst, prompt, max_new = make_migrate_pair()
    server = kvtransfer.PageServer()
    migrate_ms, stall_ms = [], []
    n_pages = nbytes = 0
    try:
        for _ in range(max(2, reps)):
            h = src.submit(prompt, max_new)
            h.tokens.get(timeout=result_timeout)   # mid-decode
            t_last = time.perf_counter()
            frozen = src.freeze_session(h, timeout_s=result_timeout)
            assert frozen is not None, "session finished before the cut"
            try:
                # tokens committed before the cut still drain to the
                # client
                while True:
                    try:
                        h.tokens.get(timeout=0.05)
                        t_last = time.perf_counter()
                    except queue.Empty:
                        break
                t0 = time.perf_counter()
                meta, blocks = kvtransfer.wire_snapshot(
                    frozen, "bench", page_size=src.kv_page_size)
                ticket = server.register(meta, blocks)
                try:
                    meta2, blocks2 = kvtransfer.pull_snapshot(
                        server.addr, ticket)
                    h2, installed = dst.submit_resume(meta2, blocks2)
                    assert installed.wait(result_timeout), \
                        "resume timed out"
                finally:
                    server.release(ticket)
                t1 = time.perf_counter()
                h2.tokens.get(timeout=result_timeout)  # live again
                t2 = time.perf_counter()
                src.complete_migration(frozen)
                frozen = None
            finally:
                if frozen is not None:
                    src.rollback_migration(frozen)
            h2.result(timeout=result_timeout)      # drain the session
            migrate_ms.append((t1 - t0) * 1e3)
            stall_ms.append((t2 - t_last) * 1e3)
            n_pages = int(meta["n_pages"])
            nbytes = sum(int(a.nbytes) for a in blocks.values())
    finally:
        server.close()
        src.stop()
        dst.stop()
    med = statistics.median(migrate_ms[1:])        # rep 0 = compile warmup
    med_stall = statistics.median(stall_ms[1:])
    return (med, med_stall, n_pages / (med / 1e3) if med else 0.0,
            n_pages, nbytes)


def bench_recover_segment(reps=5, result_timeout=600):
    """The recover segment: a mid-decode session LOST with its replica
    (no kv survives, unlike migrate_ms) and rebuilt on a second batcher
    from its token record alone via ``submit_replay`` — re-prefill over
    prompt+emitted, resume splice, decode live again.  This is the
    replica-crash recovery path the fleet gateway drives from its
    stream journal; the segment prices it end to end.  Rep 0 pays the
    prefill/splice compiles and is discarded; the rest report medians.
    Returns ``(recover_ms, gap_ms, n_replayed)`` where ``recover_ms``
    is submit_replay→splice-installed, ``gap_ms`` is the client-visible
    token gap across the crash (last token from the lost replica to
    first token from the recovered session), and ``n_replayed`` is the
    re-prefilled sequence length."""
    import statistics

    from tensorflowonspark_tpu.benchmarks import make_migrate_pair

    src, dst, prompt, max_new = make_migrate_pair()
    prompt = list(prompt)
    recover_ms, gap_ms = [], []
    n_replayed = 0
    try:
        for _ in range(max(2, reps)):
            h = src.submit(prompt, max_new)
            emitted = list(h.tokens.get(timeout=result_timeout))
            t_last = time.perf_counter()
            while True:                      # drain what the "crashed"
                try:                         # replica already committed
                    batch = h.tokens.get(timeout=0.05)
                except queue.Empty:
                    break
                if batch is None:
                    break
                emitted.extend(batch)
                t_last = time.perf_counter()
            assert 0 < len(emitted) < max_new, \
                "session finished before the kill"
            h.cancel()                       # the crash: source row gone,
            t0 = time.perf_counter()         # only the token record left
            h2, installed = dst.submit_replay(
                {"seq": prompt + emitted, "plen": len(prompt),
                 "max_new": max_new, "remaining": max_new - len(emitted),
                 "temp": 0.0, "seed": 0})
            assert installed.wait(result_timeout), "replay splice timed out"
            t1 = time.perf_counter()
            h2.tokens.get(timeout=result_timeout)  # live again
            t2 = time.perf_counter()
            out = h2.result(timeout=result_timeout)
            # byte parity over the recovered region: greedy, so the
            # continuation must re-commit exactly what was journaled
            assert out[:len(prompt) + len(emitted)] == prompt + emitted, \
                "recovered session diverged from its journal"
            recover_ms.append((t1 - t0) * 1e3)
            gap_ms.append((t2 - t_last) * 1e3)
            n_replayed = len(prompt) + len(emitted)
    finally:
        src.stop()
        dst.stop()
    return (statistics.median(recover_ms[1:]),   # rep 0 = compile warmup
            statistics.median(gap_ms[1:]), n_replayed)


def bench_sched_segment(result_timeout=600):
    """The sched segment: a paged batcher saturated by long batch-class
    sessions while short interactive requests land on top
    (benchmarks.make_sched_burst / FLAGSHIP_SCHED), run twice — with the
    freeze-based preemption controller armed and disarmed.  Reports the
    interactive p95 queueing delay for both runs plus the park traffic
    the armed run generated; the armed p95 being lower IS the segment's
    story (batch work absorbs the slack).  Returns ``(on_p95_ms,
    off_p95_ms, sessions_parked, sessions_unparked)``."""
    from tensorflowonspark_tpu.benchmarks import make_sched_burst

    out = {}
    for armed in (True, False):
        (batcher, batch_prompts, batch_max_new,
         inter_prompts, inter_max_new) = make_sched_burst(preempt=armed)
        try:
            hs = [batcher.submit(p, batch_max_new, priority="batch")
                  for p in batch_prompts]
            # batch sessions own every slot before interactive arrives
            for h in hs:
                h.tokens.get(timeout=result_timeout)
            ihs = []
            for p in inter_prompts:
                ihs.append(batcher.submit(p, inter_max_new,
                                          priority="interactive"))
                time.sleep(0.01)
            for h in ihs:
                h.result(timeout=result_timeout)
            for h in hs:
                h.result(timeout=result_timeout)
            st = batcher.stats()
            out[armed] = (st.get("qdelay_interactive_p95_ms", 0.0),
                          st.get("sessions_parked", 0),
                          st.get("sessions_unparked", 0))
            assert st.get("parked_sessions", 0) == 0, \
                "park pool did not drain back to zero"
        finally:
            batcher.stop()
    return (out[True][0], out[False][0], out[True][1], out[True][2])


def bench_job_segment(result_timeout=600):
    """The job_tps segment: a real :class:`jobs.JobManager` drains a
    jsonl record file through one paged batcher as batch-class work
    (benchmarks.make_job_burst / FLAGSHIP_JOB) while interactive probes
    ride on top — the offline data pump at full engine utilization.
    The dispatch callable drives the batcher directly (no model export
    / HTTP fleet bring-up on the bench box); everything above it —
    partition splits, checkpointing, idempotency keys, the output
    merge — is the production jobs path.  Returns ``(records_per_s,
    inter_p95_loaded_ms, inter_p95_idle_ms)``."""
    import shutil
    import tempfile

    from tensorflowonspark_tpu import jobs as jobs_mod
    from tensorflowonspark_tpu.benchmarks import (FLAGSHIP_JOB,
                                                  make_job_burst)

    (batcher, record_prompts, record_max_new,
     inter_prompts, inter_max_new) = make_job_burst()
    d = FLAGSHIP_JOB
    work = tempfile.mkdtemp(prefix="bench_job_")
    try:
        # compile warmup: one prefill+decode at each population's shape
        batcher.submit(record_prompts[0], record_max_new,
                       priority="batch").result(timeout=result_timeout)
        batcher.submit(inter_prompts[0], inter_max_new,
                       priority="interactive").result(
                           timeout=result_timeout)

        def probe_p95():
            lats = []
            for p in inter_prompts:
                t0 = time.perf_counter()
                batcher.submit(p, inter_max_new,
                               priority="interactive").result(
                                   timeout=result_timeout)
                lats.append((time.perf_counter() - t0) * 1e3)
            lats.sort()
            return lats[int(0.95 * (len(lats) - 1))]

        idle_p95 = probe_p95()

        input_path = os.path.join(work, "records.jsonl")
        with open(input_path, "w", encoding="utf-8") as f:
            for p in record_prompts:
                f.write(json.dumps(p) + "\n")

        def dispatch(body, key):
            hs = [batcher.submit(p, int(body.get("max_new_tokens",
                                                 record_max_new)),
                                 priority=body.get("priority", "batch"))
                  for p in body["inputs"]]
            return {"outputs": [h.result(timeout=result_timeout)
                                for h in hs]}

        mgr = jobs_mod.JobManager(os.path.join(work, "jobs"),
                                  dispatch=dispatch,
                                  default_workers=d["workers"],
                                  checkpoint_every=d["checkpoint_every"])
        try:
            t0 = time.perf_counter()
            st = mgr.submit({"input": input_path,
                             "partitions": d["partitions"],
                             "request": {"max_new_tokens":
                                         record_max_new}})
            loaded_p95 = probe_p95()     # probes ride on the live job
            deadline = time.monotonic() + result_timeout
            while (mgr.status(st["id"])["state"] == "running"
                   and time.monotonic() < deadline):
                time.sleep(0.02)
            elapsed = time.perf_counter() - t0
            final = mgr.status(st["id"])
            assert final["state"] == "completed", final
            assert final["records_done"] == len(record_prompts), final
            with open(final["output"], encoding="utf-8") as f:
                assert sum(1 for _ in f) == len(record_prompts)
        finally:
            mgr.stop()
        return len(record_prompts) / elapsed, loaded_p95, idle_p95
    finally:
        batcher.stop()
        shutil.rmtree(work, ignore_errors=True)


def bench_warm_segment(result_timeout=600):
    """The warm-turn segment: 8 returning conversations through a paged
    batcher with the host-DRAM page tier armed (benchmarks.
    make_warm_burst / FLAGSHIP_WARM).  A throwaway burst pays the
    compiles, then the cold pass prefills every prompt from scratch;
    the tier is flushed and the DEVICE prefix cache dropped (each
    full-prefix page demotes to host DRAM), so the warm pass re-running
    the SAME prompts can only be served by host->device promotion —
    the cross-turn prefill skip.  TTFT comes from the batcher's own
    counters (stats() deltas, same numbers operators see).  Returns
    ``(warm_ms, cold_ms, host_hits, tokens_skipped)``."""
    from tensorflowonspark_tpu.benchmarks import make_warm_burst

    batcher, prompts, max_new = make_warm_burst()
    try:
        def burst():
            s0 = batcher.stats()
            handles = [batcher.submit(p, max_new) for p in prompts]
            outs = [h.result(timeout=result_timeout) for h in handles]
            s1 = batcher.stats()
            n = max(1, s1["ttft_count"] - s0["ttft_count"])
            return ((s1["ttft_ms_sum"] - s0["ttft_ms_sum"]) / n, outs,
                    s1["host_hits"] - s0["host_hits"],
                    s1["prefill_tokens_shared"]
                    - s0["prefill_tokens_shared"])

        burst()                          # compile warmup
        batcher._host_tier.flush()
        batcher.drop_prefix_cache()      # forget warmup conversations
        batcher._host_tier.clear()
        cold_ms, cold_outs, _, _ = burst()
        batcher._host_tier.flush()       # retirement demotes land
        batcher.drop_prefix_cache()      # device cache -> host tier only
        batcher._host_tier.flush()
        warm_ms, warm_outs, host_hits, skipped = burst()
        assert warm_outs == cold_outs, \
            "warm pass diverged from cold pass"
        assert host_hits > 0, "warm pass never hit the host tier"
        return warm_ms, cold_ms, host_hits, skipped
    finally:
        batcher.stop()


def bench_long_segment(result_timeout=600):
    """The long_ttft_ms segment: one 32k-token mega-prompt streamed
    through a paged batcher while a short interactive burst rides on
    top (benchmarks.make_long_burst / FLAGSHIP_LONG), run twice — with
    the long-context admission lane armed and disarmed.  Armed, the
    prompt admits immediately and prefills chunk-by-chunk under the
    lane quota, the page table growing from its seed width and cold
    prefix pages demoting through the overflow valve; disarmed, it is
    a monolithic admission hogging the prefill budget.  Reports the
    mega-prompt TTFT/TPOT and the interactive p95 queueing delay both
    ways plus the armed run's growth/demotion counts — the interactive
    p95 holding while the monster streams IS the segment's story.
    Returns ``(on, off)`` tuples of ``(ttft_ms, tpot_ms,
    inter_p95_ms, table_grows, pages_demoted)``."""
    from tensorflowonspark_tpu.benchmarks import make_long_burst

    out = {}
    for armed in (True, False):
        (batcher, long_prompt, long_max_new,
         inter_prompts, inter_max_new) = make_long_burst(armed=armed)
        try:
            # compile warmup at the interactive shape only — the mega
            # prompt's own chunks reuse the same prefill buckets
            batcher.submit(inter_prompts[0], inter_max_new,
                           priority="interactive").result(
                               timeout=result_timeout)
            s0 = batcher.stats()
            t0 = time.perf_counter()
            lh = batcher.submit(long_prompt, long_max_new,
                                priority="batch")
            ihs = []
            for p in inter_prompts:
                ihs.append(batcher.submit(p, inter_max_new,
                                          priority="interactive"))
                time.sleep(0.01)
            lh.tokens.get(timeout=result_timeout)
            ttft = (time.perf_counter() - t0) * 1e3
            for h in ihs:
                h.result(timeout=result_timeout)
            lh.result(timeout=result_timeout)
            total = (time.perf_counter() - t0) * 1e3
            st = batcher.stats()
            out[armed] = (
                ttft,
                (total - ttft) / max(1, long_max_new - 1),
                st.get("qdelay_interactive_p95_ms", 0.0),
                st.get("kv_table_grows", 0)
                - s0.get("kv_table_grows", 0),
                st.get("kv_pages_demoted_overflow", 0)
                - s0.get("kv_pages_demoted_overflow", 0))
        finally:
            batcher.stop()
    return out[True], out[False]


def _warm_segment_setup():
    from tensorflowonspark_tpu import kvtier, serve
    from tensorflowonspark_tpu.benchmarks import (FLAGSHIP_WARM,
                                                  make_warm_burst)

    assert callable(make_warm_burst)
    assert callable(kvtier.HostPageTier)
    assert callable(serve.ContinuousBatcher.drop_prefix_cache)
    d = FLAGSHIP_WARM
    assert d["prompt_len"] + d["max_new"] <= d["max_seq"]
    assert d["max_seq"] % d["kv_page_size"] == 0
    # every conversation's full-prefix pages must fit the host tier at
    # once, or the warm pass silently re-prefills the evicted tail
    assert d["prompt_len"] // d["kv_page_size"] >= 2
    assert d["host_cache_mb"] > 0 and d["conversations"] > 0
    return {"config": dict(d)}


def _warm_segment_result():
    warm_ms, cold_ms, host_hits, skipped = bench_warm_segment()
    return {"metric": "warm_ttft_ms", "value": round(warm_ms, 1),
            "unit": "ms/request",
            "aux": {"cold_ttft_ms": round(cold_ms, 1),
                    "speedup_vs_cold": round(
                        cold_ms / warm_ms, 2) if warm_ms else None,
                    "host_hits": host_hits,
                    "prefill_tokens_skipped": skipped}}


def _long_segment_setup():
    from tensorflowonspark_tpu import serve
    from tensorflowonspark_tpu.benchmarks import (FLAGSHIP_LONG,
                                                  make_long_burst)

    assert callable(make_long_burst)
    assert callable(serve.max_table_pages)
    d = FLAGSHIP_LONG
    assert d["long_prompt_len"] + d["long_max_new"] <= d["max_seq"]
    assert d["inter_prompt_len"] + d["inter_max_new"] <= d["max_seq"]
    assert d["max_seq"] % d["kv_page_size"] == 0
    # the mega-prompt routes through the lane; the interactive burst
    # stays below the threshold and never does
    assert d["inter_prompt_len"] <= d["long_prompt_threshold"]
    assert d["long_prompt_threshold"] < d["long_prompt_len"]
    # the table must grow from its seed width to cover the mega-prompt
    assert (serve.max_table_pages(d["max_seq"], d["kv_page_size"])
            > serve._INIT_TABLE_PAGES)
    # pool covers the mega-prompt's own page run, but NOT that run plus
    # every interactive session's retired prefix pages — the overflow
    # valve must fire for the stream to finish
    need = -(-(d["long_prompt_len"] + d["long_max_new"])
             // d["kv_page_size"])
    inter_pages = -(-(d["inter_prompt_len"] + d["inter_max_new"])
                    // d["kv_page_size"])
    assert need < d["kv_pages"]
    assert need + d["inter_sessions"] * inter_pages > d["kv_pages"]
    assert d["host_cache_mb"] > 0
    return {"config": dict(d)}


def _long_segment_result():
    on, off = bench_long_segment()
    return {"metric": "long_ttft_ms", "value": round(on[0], 1),
            "unit": "ms mega-prompt time-to-first-token",
            "aux": {"long_ttft_ms_unlaned": round(off[0], 1),
                    "long_tpot_ms": round(on[1], 2),
                    "interactive_p95_ms": round(on[2], 1),
                    "interactive_p95_unlaned_ms": round(off[2], 1),
                    "kv_table_grows": on[3],
                    "kv_pages_demoted_overflow": on[4]}}


def _job_segment_setup():
    from tensorflowonspark_tpu import jobs
    from tensorflowonspark_tpu.benchmarks import (FLAGSHIP_JOB,
                                                  make_job_burst)

    assert callable(make_job_burst)
    assert callable(jobs.JobManager) and callable(jobs.split_file)
    d = FLAGSHIP_JOB
    assert d["record_prompt_len"] + d["record_max_new"] <= d["max_seq"]
    assert d["inter_prompt_len"] + d["inter_max_new"] <= d["max_seq"]
    assert d["max_seq"] % d["kv_page_size"] == 0
    assert 1 <= d["partitions"] <= d["records"]
    assert d["workers"] >= 1 and d["checkpoint_every"] >= 1
    assert d["preempt_ms"] > 0 and d["inter_probes"] >= 2
    return {"config": dict(d)}


def _job_segment_result():
    tps, loaded_p95, idle_p95 = bench_job_segment()
    return {"metric": "job_tps", "value": round(tps, 1),
            "unit": "records/s",
            "aux": {"interactive_p95_ms": round(loaded_p95, 1),
                    "interactive_p95_idle_ms": round(idle_p95, 1),
                    "interactive_p95_delta_ms": round(
                        loaded_p95 - idle_p95, 1)}}


def _sched_segment_setup():
    from tensorflowonspark_tpu import serve
    from tensorflowonspark_tpu.benchmarks import (FLAGSHIP_SCHED,
                                                  make_sched_burst)

    assert callable(make_sched_burst)
    assert serve.PRIORITY_CLASSES == ("interactive", "batch")
    d = FLAGSHIP_SCHED
    assert d["batch_prompt_len"] + d["batch_max_new"] <= d["max_seq"]
    assert d["inter_prompt_len"] + d["inter_max_new"] <= d["max_seq"]
    assert d["max_seq"] % d["kv_page_size"] == 0
    # every batch session can be parked at once, and the pool still
    # holds pages for the interactive burst riding on top
    assert d["kv_pages"] * d["kv_page_size"] >= 2 * d["max_seq"]
    assert d["preempt_ms"] > 0
    return {"config": dict(d)}


def _sched_segment_result():
    on_p95, off_p95, parked, unparked = bench_sched_segment()
    return {"metric": "sched_ms", "value": round(on_p95, 1),
            "unit": "ms p95 interactive queue delay",
            "aux": {"sched_ms_no_preempt": round(off_p95, 1),
                    "speedup_vs_no_preempt": round(
                        off_p95 / on_p95, 2) if on_p95 else None,
                    "sessions_parked": parked,
                    "sessions_unparked": unparked}}


def _opt_segment_setup():
    """Cheap, CPU-safe registry smoke: the segment's builders and frozen
    config resolve without building the 0.87B model or touching a
    device (tests/test_bench_segments.py dry-runs every setup)."""
    from tensorflowonspark_tpu.benchmarks import (FLAGSHIP_LM_V2,
                                                  FLAGSHIP_OPTIMIZER,
                                                  make_flagship_step)

    assert callable(make_flagship_step)
    assert FLAGSHIP_LM_V2["d_model"] > 0
    return {"config": dict(FLAGSHIP_LM_V2),
            "optimizer": FLAGSHIP_OPTIMIZER}


def _opt_segment_result():
    full_ms, sgd0_ms, opt_ms = bench_opt_segment()
    return {"metric": "opt_ms", "value": round(opt_ms, 1),
            "unit": "ms/step",
            "aux": {"lm_step_ms": round(full_ms, 1),
                    "lm_step_ms_sgd0": round(sgd0_ms, 1)}}


def _decode_segment_setup():
    from tensorflowonspark_tpu.benchmarks import (FLAGSHIP_DECODE,
                                                  make_decode_step)

    assert callable(make_decode_step)
    d = FLAGSHIP_DECODE
    assert d["fill"] <= d["max_seq"] and d["max_seq"] % d["page_size"] == 0
    return {"config": dict(d)}


def _decode_segment_result():
    kernel_ms, einsum_ms, int8_ms = bench_decode_segment()
    return {"metric": "decode_ms", "value": round(kernel_ms, 2),
            "unit": "ms/step",
            "aux": {"decode_ms_einsum": round(einsum_ms, 2),
                    "speedup_vs_einsum": round(einsum_ms / kernel_ms, 2),
                    # same step with the weights int8-quantized through
                    # the fused-dequant matmul path (W8A16 serving store)
                    "decode_ms_int8": round(int8_ms, 2),
                    "speedup_int8_vs_bf16": round(kernel_ms / int8_ms, 2)}}


def _qmm_segment_setup():
    from tensorflowonspark_tpu.benchmarks import (FLAGSHIP_QMM,
                                                  make_qmm_op,
                                                  qmm_weight_bytes)
    assert callable(make_qmm_op)
    d = FLAGSHIP_QMM
    assert d["rows"] > 0 and d["group_size"] % 2 == 0
    # whole groups: the analytic bytes and the packed layout agree
    assert d["in_dim"] % d["group_size"] == 0
    # the weight-read contrast the segment exists to price
    assert (qmm_weight_bytes("int4") < qmm_weight_bytes("int8")
            < qmm_weight_bytes("bf16"))
    return {"config": dict(d)}


def _qmm_segment_result():
    from tensorflowonspark_tpu.benchmarks import qmm_weight_bytes

    ms = bench_qmm_segment()
    return {"metric": "qmm_ms", "value": round(ms["int8"], 3),
            "unit": "ms/matmul",
            "aux": {"qmm_ms_bf16": round(ms["bf16"], 3),
                    "qmm_ms_int4": round(ms["int4"], 3),
                    "speedup_int8_vs_bf16": round(
                        ms["bf16"] / ms["int8"], 2),
                    "speedup_int4_vs_bf16": round(
                        ms["bf16"] / ms["int4"], 2),
                    # analytic per-step weight read (the bound the
                    # kernels chase on a weight-bound decode matmul)
                    "weight_mb_bf16": round(
                        qmm_weight_bytes("bf16") / 1e6, 2),
                    "weight_mb_int8": round(
                        qmm_weight_bytes("int8") / 1e6, 2),
                    "weight_mb_int4": round(
                        qmm_weight_bytes("int4") / 1e6, 2)}}


def _prefill_segment_setup():
    from tensorflowonspark_tpu.benchmarks import (
        FLAGSHIP_PREFILL_KERNEL, make_prefill_chunk_step,
        prefill_chunk_write_bytes)

    assert callable(make_prefill_chunk_step)
    d = FLAGSHIP_PREFILL_KERNEL
    assert d["fill"] + d["chunk"] <= d["max_seq"]
    assert d["max_seq"] % d["page_size"] == 0
    # the in-place write claim the segment exists to price: kernel
    # traffic scales with the chunk, blend traffic with the whole pool
    assert (prefill_chunk_write_bytes("kernel")
            < prefill_chunk_write_bytes("blend"))
    return {"config": dict(d)}


def _prefill_segment_result():
    from tensorflowonspark_tpu.benchmarks import prefill_chunk_write_bytes

    kernel_ms, blend_ms = bench_prefill_segment()
    kb = prefill_chunk_write_bytes("kernel")
    bb = prefill_chunk_write_bytes("blend")
    return {"metric": "prefill_ms", "value": round(kernel_ms, 2),
            "unit": "ms/chunk",
            "aux": {"prefill_ms_blend": round(blend_ms, 2),
                    "speedup_vs_blend": round(blend_ms / kernel_ms, 2),
                    "kv_write_mb_kernel": round(kb / 1e6, 2),
                    "kv_write_mb_blend": round(bb / 1e6, 2),
                    "kv_write_ratio": round(bb / kb, 1)}}


def _ttft_segment_setup():
    from tensorflowonspark_tpu.benchmarks import (FLAGSHIP_PREFILL,
                                                  make_prefill_burst)

    assert callable(make_prefill_burst)
    d = FLAGSHIP_PREFILL
    assert d["prompt_len"] + d["max_new"] <= d["max_seq"]
    assert d["prefill_rows"] >= 1 and d["prompts"] >= d["prefill_rows"]
    return {"config": dict(d)}


def _ttft_segment_result():
    batched_ms, sequential_ms = bench_ttft_segment()
    return {"metric": "ttft_ms", "value": round(batched_ms, 1),
            "unit": "ms/request",
            "aux": {"ttft_ms_sequential": round(sequential_ms, 1),
                    "speedup_vs_sequential": round(
                        sequential_ms / batched_ms, 2)}}


def _engine_segment_setup():
    from tensorflowonspark_tpu.benchmarks import (FLAGSHIP_ENGINE,
                                                  make_engine_burst)

    assert callable(make_engine_burst)
    d = FLAGSHIP_ENGINE
    assert d["prompt_len"] + d["max_new"] <= d["max_seq"]
    assert d["max_new"] > d["prompt_len"]  # decode-dominated by design
    return {"config": dict(d)}


def _engine_segment_result():
    (async_tps, traced_tps, serial_tps, int8_tps,
     astats) = bench_engine_segment()
    return {"metric": "engine_tps", "value": round(async_tps, 1),
            "unit": "tokens/s",
            "aux": {"engine_tps_serial": round(serial_tps, 1),
                    "speedup_vs_serial": round(async_tps / serial_tps, 2),
                    # the async engine with int8-quantized weights
                    # (fused-dequant matmul path, W8A16 serving store)
                    "engine_tps_int8": round(int8_tps, 1),
                    # fractional tokens/s lost with every request
                    # traced (negative = noise); keeps "tracing is
                    # free on the hot path" an actual regression check
                    "engine_tps_traced": round(traced_tps, 1),
                    "trace_overhead":
                        round(1.0 - traced_tps / async_tps, 4),
                    "device_idle_fraction":
                        astats.get("device_idle_fraction", 0.0),
                    "pipeline_depth_peak":
                        astats.get("pipeline_depth_peak", 0)}}


def _spec_segment_setup():
    from tensorflowonspark_tpu.benchmarks import (FLAGSHIP_LM_V2,
                                                  FLAGSHIP_SPEC,
                                                  make_spec_burst)

    assert callable(make_spec_burst)
    d = FLAGSHIP_SPEC
    # spec-eligible requests reserve draft_k verify-overshoot headroom
    assert d["prompt_len"] + d["max_new"] + d["draft_k"] <= d["max_seq"]
    assert d["motif_len"] < d["prompt_len"]   # prompts actually repeat
    assert d["draft_layers"] < FLAGSHIP_LM_V2["n_layers"]
    return {"config": dict(d)}


def _spec_segment_result():
    ngram_tps, model_tps, off_tps, nstats, mstats = bench_spec_segment()
    return {"metric": "spec_tps", "value": round(ngram_tps, 1),
            "unit": "tokens/s",
            "aux": {"spec_tps_model": round(model_tps, 1),
                    "spec_tps_off": round(off_tps, 1),
                    # the headline claim: prompt-lookup drafting beats
                    # plain decode on repetitive prompts with zero
                    # extra weight bytes
                    "speedup_vs_off": round(ngram_tps / off_tps, 2),
                    "accept_rate_ngram":
                        nstats.get("spec_accept_rate", 0.0),
                    "accept_rate_model":
                        mstats.get("spec_accept_rate", 0.0),
                    "mean_k_ngram": nstats.get("spec_k_mean", 0.0),
                    "mean_k_model": mstats.get("spec_k_mean", 0.0)}}


def _migrate_segment_setup():
    from tensorflowonspark_tpu import kvtransfer
    from tensorflowonspark_tpu.benchmarks import (FLAGSHIP_MIGRATE,
                                                  make_migrate_pair)

    assert callable(make_migrate_pair)
    assert kvtransfer.WIRE_VERSION >= 1
    d = FLAGSHIP_MIGRATE
    assert d["prompt_len"] + d["max_new"] <= d["max_seq"]
    assert d["max_seq"] % d["kv_page_size"] == 0
    # the snapshot must fit both pools with room for the decode tail
    assert d["kv_pages"] * d["kv_page_size"] >= 2 * d["max_seq"]
    return {"config": dict(d)}


def _migrate_segment_result():
    migrate_ms, stall_ms, pages_per_s, n_pages, nbytes = \
        bench_migrate_segment()
    return {"metric": "migrate_ms", "value": round(migrate_ms, 1),
            "unit": "ms/migration",
            "aux": {"stream_stall_ms": round(stall_ms, 1),
                    "kv_pages_per_s": round(pages_per_s, 1),
                    "kv_pages": n_pages,
                    "kv_bytes": nbytes}}


def _recover_segment_setup():
    from tensorflowonspark_tpu import serve
    from tensorflowonspark_tpu.benchmarks import (FLAGSHIP_MIGRATE,
                                                  make_migrate_pair)

    assert callable(make_migrate_pair)
    assert callable(serve.ContinuousBatcher.submit_replay)
    d = FLAGSHIP_MIGRATE
    assert d["prompt_len"] + d["max_new"] <= d["max_seq"]
    # the replay re-prefills prompt+emitted on the destination alone
    assert d["kv_pages"] * d["kv_page_size"] >= d["max_seq"]
    return {"config": dict(d)}


def _recover_segment_result():
    recover_ms, gap_ms, n_replayed = bench_recover_segment()
    return {"metric": "recover_ms", "value": round(recover_ms, 1),
            "unit": "ms/recovery",
            "aux": {"stream_gap_ms": round(gap_ms, 1),
                    "replayed_tokens": n_replayed}}


# segment registry: every entry shares the off-TPU skip + one-JSON-line-
# per-segment protocol, so growing a segment is one row (the old
# hardcoded opt_ms plumbing could not be reused).  Each entry carries:
#   run   — the TPU measurement, returns the segment's JSON dict
#   setup — cheap CPU-safe resolution of the segment's builders/config
#           (dry-run by the tier-1 smoke test, so a broken import or
#           frozen-config drift is caught off-TPU, not on the bench box)
#   help  — one line for --list-segments
SEGMENTS = {
    "opt_ms": {
        "run": _opt_segment_result,
        "setup": _opt_segment_setup,
        "help": "optimizer-update cost per flagship train step "
                "(fused adamw vs zero-lr sgd floor)"},
    "decode_ms": {
        "run": _decode_segment_result,
        "setup": _decode_segment_setup,
        "help": "steady-state paged slot-decode step "
                "(flash-decode kernel vs einsum full-gather)"},
    "qmm_ms": {
        "run": _qmm_segment_result,
        "setup": _qmm_segment_setup,
        "help": "fused-dequant weight matmul on the flagship projection "
                "(int8 / nibble-packed int4 Pallas kernels vs the dense "
                "bf16 store, with the analytic weight-bytes contrast)"},
    "prefill_ms": {
        "run": _prefill_segment_result,
        "setup": _prefill_segment_setup,
        "help": "steady-state paged prefill chunk dispatch (in-place "
                "page-write kernel vs full-pool einsum blend, with the "
                "analytic kv write-traffic contrast)"},
    "ttft_ms": {
        "run": _ttft_segment_result,
        "setup": _ttft_segment_setup,
        "help": "burst time-to-first-token through the admission "
                "pipeline (batched multi-row prefill vs sequential)"},
    "engine_tps": {
        "run": _engine_segment_result,
        "setup": _engine_segment_setup,
        "help": "sustained decode tokens/s through the full continuous "
                "batcher (async double-buffered engine vs serialized loop)"},
    "spec_tps": {
        "run": _spec_segment_result,
        "setup": _spec_segment_setup,
        "help": "speculative decode tokens/s on repetitive prompts "
                "(model-free n-gram drafting vs draft-model vs off, "
                "with acceptance rate and adaptive mean-k aux)"},
    "migrate_ms": {
        "run": _migrate_segment_result,
        "setup": _migrate_segment_setup,
        "help": "mid-decode kv migration between two batchers over a "
                "page-server socket (freeze to resume splice, plus the "
                "client-visible stream stall)"},
    "recover_ms": {
        "run": _recover_segment_result,
        "setup": _recover_segment_setup,
        "help": "crash recovery of a lost session from its token record "
                "alone (submit_replay re-prefill to resume splice, plus "
                "the client-visible stream gap)"},
    "sched_ms": {
        "run": _sched_segment_result,
        "setup": _sched_segment_setup,
        "help": "interactive p95 queueing delay under mixed-priority "
                "load (freeze-based preemption parking batch sessions "
                "vs FIFO sharing)"},
    "warm_ttft_ms": {
        "run": _warm_segment_result,
        "setup": _warm_segment_setup,
        "help": "returning-conversation time-to-first-token with prefix "
                "pages promoted from the host-DRAM kv tier vs a cold "
                "full prefill"},
    "job_tps": {
        "run": _job_segment_result,
        "setup": _job_segment_setup,
        "help": "offline bulk-inference job drain rate (records/s "
                "through the jobs spool/checkpoint path at full engine "
                "utilization, with the interactive p95 it costs)"},
    "long_ttft_ms": {
        "run": _long_segment_result,
        "setup": _long_segment_setup,
        "help": "mega-prompt time-to-first-token through the "
                "long-context admission lane (chunk-streamed growable "
                "page table + host-tier overflow vs an unlaned "
                "monolithic admission), with the interactive p95 it "
                "protects"},
}


def list_segments_main():
    """`bench.py --list-segments`: one JSON line per registry entry —
    no jax import, runnable anywhere (CI discovers the segment set
    without an accelerator runtime)."""
    for name, entry in SEGMENTS.items():
        print(json.dumps({"segment": name, "help": entry["help"]}))
    return 0


def segments_main():
    """`bench.py --segments`: the segment comparisons alone (SEGMENTS
    registry — one JSON line each).  Off-TPU it exits 0 with a skipped
    line PER SEGMENT before building any 0.87B model — the CI smoke path
    (scripts/run_tests.sh boxes have no accelerator)."""
    import jax

    if jax.default_backend() != "tpu":
        for name in SEGMENTS:
            print(json.dumps({"metric": name, "skipped":
                              "segment bench needs TPU (backend is "
                              f"{jax.default_backend()})"}))
        return 0
    for entry in SEGMENTS.values():
        print(json.dumps(entry["run"]()))
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--segments", action="store_true",
                    help="run only the segment comparisons (one JSON "
                         "line each; exits 0 with skipped lines "
                         "off-TPU)")
    ap.add_argument("--list-segments", action="store_true",
                    help="print the segment registry (one JSON line per "
                         "segment: name + help) and exit; needs no "
                         "accelerator")
    args = ap.parse_args(argv)
    if args.list_segments:
        return list_segments_main()
    from tensorflowonspark_tpu import util

    util.enable_compile_cache()
    if args.segments:
        return segments_main()

    mfu, tps, step_ms, n_params = bench_flagship_lm()
    # transition-round continuity: the optax adamw step (the round-5
    # headline's optimizer), measured in the SAME session so the fused
    # switch stays comparable in the records
    uf_mfu, _, uf_step_ms, _ = bench_flagship_lm(optimizer="adamw")
    # optimizer segment: the same step with the cheapest possible update
    _, _, sgd0_step_ms, _ = bench_flagship_lm(optimizer="sgd0")
    mnist = bench_mnist_cnn()
    aux = {
        "lm_tokens_per_sec": round(tps, 0),
        "lm_step_ms": round(step_ms, 1),
        "lm_params": n_params,
        "lm_batch": FLAGSHIP_BATCH,
        "opt_ms": round(step_ms - sgd0_step_ms, 1),
        "lm_step_ms_sgd0": round(sgd0_step_ms, 1),
        "lm_mfu_adamw_unfused": round(uf_mfu, 1) if uf_mfu else None,
        "lm_step_ms_adamw_unfused": round(uf_step_ms, 1),
        "mnist_cnn_images_per_sec": round(mnist, 0),
    }
    if mfu is not None:
        out = {"metric": "flagship_lm_train_mfu", "value": round(mfu, 1),
               "unit": "percent_of_bf16_peak",
               "vs_baseline": round(mfu / ROUND1_LM_MFU, 3), "aux": aux}
    else:  # unknown chip peak: report throughput, never a guessed MFU
        # (vs_baseline 1.0: no prior tokens/sec record exists for THIS
        # config on an unknown chip — the run establishes its own baseline)
        out = {"metric": "flagship_lm_tokens_per_sec", "value": round(tps, 0),
               "unit": "tokens/sec", "vs_baseline": 1.0, "aux": aux}
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
