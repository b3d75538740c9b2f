"""Continuous batching under concurrent load: throughput + tail latency.

Round-3 verdict item 6 (throughput): N concurrent mixed-length clients
against the slot batcher vs the same requests decoded one-at-a-time
behind a lock (what the pre-round-5 grouped path degenerated to under
concurrency).  Criterion: >= 2x.

Round-4 verdict item 4 (latency): the admission prefill used to run
inline in the device loop, stalling every in-flight stream for the whole
prompt; round 5 chunks it (serve.ContinuousBatcher prefill_chunk).  This
bench drives short streams under Poisson arrivals while LONG prompts
keep being admitted, and reports per-stream inter-token p50/p95 with
inline-equivalent (prefill_chunk >= prompt) vs chunked admission.

    python scripts/bench_continuous.py                # the chip
    python scripts/bench_continuous.py --smoke        # CI shape (cpu)
"""
import argparse
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def build_argparser():
    p = argparse.ArgumentParser()
    p.add_argument("--d_model", type=int, default=1024)
    p.add_argument("--n_layers", type=int, default=8)
    p.add_argument("--n_heads", type=int, default=8)
    p.add_argument("--n_kv_heads", type=int, default=4)
    p.add_argument("--d_ff", type=int, default=4096)
    p.add_argument("--vocab_size", type=int, default=32000)
    p.add_argument("--max_seq_len", type=int, default=512)
    p.add_argument("--clients", type=int, default=6)
    p.add_argument("--slots", type=int, default=8)
    p.add_argument("--max_new", type=int, default=48)
    p.add_argument("--long_prompt", type=int, default=256,
                   help="admission prompt length for the latency section")
    p.add_argument("--prefill_chunk", type=int, default=64,
                   help="chunked-admission chunk for the latency section")
    p.add_argument("--skip_latency", action="store_true")
    p.add_argument("--skip_throughput", action="store_true")
    p.add_argument("--smoke", action="store_true")
    return p


def _build(args):
    import jax
    import jax.numpy as jnp

    from tensorflowonspark_tpu.models.transformer import (
        Transformer, TransformerConfig)

    cfg = TransformerConfig(
        vocab_size=args.vocab_size, d_model=args.d_model,
        n_heads=args.n_heads, n_kv_heads=args.n_kv_heads,
        n_layers=args.n_layers, d_ff=args.d_ff,
        max_seq_len=args.max_seq_len, dtype="bfloat16", rope=True,
        norm_type="rmsnorm", attention_impl="dense")
    model = Transformer(cfg)
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    params = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16)
        if jnp.issubdtype(x.dtype, jnp.floating) else x, params)
    return model, params


def bench_throughput(args, model, params):
    import concurrent.futures as cf

    import numpy as np

    from tensorflowonspark_tpu import serve
    from tensorflowonspark_tpu.models import decode

    import jax.numpy as jnp

    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, args.vocab_size,
                           size=rng.choice([4, 7, 12, 21])).tolist()
               for _ in range(args.clients)]
    total_tokens = args.clients * args.max_new

    # ---- serial baseline: one decode.generate at a time under a lock ----
    lock = threading.Lock()

    def serial_one(p):
        with lock:
            out = decode.generate(model, params,
                                  jnp.asarray([p], jnp.int32),
                                  max_new_tokens=args.max_new)
            return np.asarray(out)[0].tolist()

    for L in sorted({len(p) for p in prompts}):   # compile outside timing
        serial_one(prompts[[len(p) for p in prompts].index(L)])
    t0 = time.perf_counter()
    with cf.ThreadPoolExecutor(args.clients) as ex:
        serial_out = list(ex.map(serial_one, prompts))
    serial_dt = time.perf_counter() - t0

    # ---- continuous path: slot batcher over the same params -------------
    batcher = serve.ContinuousBatcher(model, params, n_slots=args.slots)
    for p in prompts:      # warm every prefill bucket outside timing
        batcher.submit(p, 2).result(timeout=600)
    t0 = time.perf_counter()
    handles = [batcher.submit(p, args.max_new) for p in prompts]
    slot_out = [h.result(timeout=600) for h in handles]
    slot_dt = time.perf_counter() - t0
    batcher.stop()

    # bf16 caveat: serial and slot decode are DIFFERENT compiled programs;
    # near-tied logits can round to different argmaxes (f32 parity is
    # exact, tests/test_slots.py) — report agreement, don't assert it.
    agree = sum(a == b for a, b in zip(serial_out, slot_out))
    return {
        "clients": args.clients, "max_new": args.max_new,
        "prompt_lens": [len(p) for p in prompts],
        "serial_tok_s": total_tokens / serial_dt,
        "continuous_tok_s": total_tokens / slot_dt,
        "speedup": serial_dt / slot_dt,
        "greedy_agreement": f"{agree}/{len(prompts)}",
    }


def _drive_latency(args, model, params, prefill_chunk, n_short=None,
                   read_chunk=2):
    """Short streams decode while long prompts keep being admitted
    (Poisson arrivals); returns per-stream inter-token gap stats of the
    short streams."""
    import numpy as np

    from tensorflowonspark_tpu import serve

    n_short = n_short or max(2, args.slots // 2 - 1)
    batcher = serve.ContinuousBatcher(model, params, n_slots=args.slots,
                                      read_chunk=read_chunk,
                                      prefill_chunk=prefill_chunk)
    rng = np.random.RandomState(1)
    long_prompts = [rng.randint(1, args.vocab_size,
                                size=args.long_prompt).tolist()
                    for _ in range(4)]
    short_prompts = [rng.randint(1, args.vocab_size, size=6).tolist()
                     for _ in range(n_short)]
    # warm all compile variants outside timing
    batcher.submit(long_prompts[0], 2).result(timeout=900)
    batcher.submit(short_prompts[0], 2).result(timeout=900)

    stop = threading.Event()
    gaps = []

    def short_stream(p):
        h = batcher.submit(p, args.max_new)
        last = time.perf_counter()
        while True:
            tok = h.tokens.get()
            now = time.perf_counter()
            if tok is None:
                break
            gaps.append(now - last)
            last = now
        h.result(timeout=900)

    def long_admitter():
        # Poisson arrivals of long prompts, mean one per ~6 short tokens
        i = 0
        lam = 0.15
        r = np.random.RandomState(2)
        while not stop.is_set():
            time.sleep(r.exponential(1.0 / lam) * 0.1)
            try:
                batcher.submit(long_prompts[i % len(long_prompts)], 4)
            except Exception:
                return
            i += 1

    adm = threading.Thread(target=long_admitter, daemon=True)
    adm.start()
    threads = [threading.Thread(target=short_stream, args=(p,))
               for p in short_prompts]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=900)
    dt = time.perf_counter() - t0
    stop.set()
    adm.join(timeout=30)
    batcher.stop()
    gaps_ms = sorted(g * 1e3 for g in gaps)
    if not gaps_ms:
        raise RuntimeError(
            "no inter-token gaps collected — every short stream failed "
            f"before its first token (batcher dead? {batcher._dead!r})")

    def pct(q):
        return gaps_ms[min(len(gaps_ms) - 1, int(q * len(gaps_ms)))]

    return {
        "prefill_chunk": prefill_chunk,
        "short_streams": n_short, "tokens": len(gaps_ms),
        "inter_token_p50_ms": round(pct(0.50), 1),
        "inter_token_p95_ms": round(pct(0.95), 1),
        "inter_token_max_ms": round(gaps_ms[-1], 1),
        "short_tok_s": round(len(gaps_ms) / dt, 1),
    }


def main(argv=None):
    args = build_argparser().parse_args(argv)
    if args.smoke:
        args.d_model, args.n_layers, args.d_ff = 64, 2, 128
        args.vocab_size, args.max_seq_len = 128, 512
        args.max_new, args.clients = 12, 4
        args.long_prompt, args.prefill_chunk = 96, 16

    import jax

    from tensorflowonspark_tpu import util

    util.enable_compile_cache()

    model, params = _build(args)
    result = {"platform": jax.devices()[0].platform,
              "params_m": round(sum(x.size for x in
                                    jax.tree_util.tree_leaves(params))
                                / 1e6)}
    ok = True
    if not args.skip_throughput:
        result.update(bench_throughput(args, model, params))
        ok = result["speedup"] >= 2.0
    if not args.skip_latency:
        # inline-equivalent arm: one chunk covers the whole long prompt
        inline = _drive_latency(args, model, params,
                                prefill_chunk=args.max_seq_len)
        chunked = _drive_latency(args, model, params,
                                 prefill_chunk=args.prefill_chunk)
        result["latency_inline_prefill"] = inline
        result["latency_chunked_prefill"] = chunked
        result["p95_improvement"] = round(
            inline["inter_token_p95_ms"]
            / max(chunked["inter_token_p95_ms"], 1e-9), 2)
    print(json.dumps(result, indent=2))
    if not args.skip_throughput:
        print(f"continuous >= 2x serial: {ok}")
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
