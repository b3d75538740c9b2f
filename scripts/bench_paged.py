"""Serving-engine feature benches: paged kv, speculative slots, prefix cache.

Not driver-run; no figure from it has been taken on this chip:

    python scripts/bench_paged.py                 # all three sections
    python scripts/bench_paged.py --only paged    # dense vs paged pool
    python scripts/bench_paged.py --only spec     # self-draft ceiling
    python scripts/bench_paged.py --only prefix   # repeated-prompt TTFT
    python scripts/bench_paged.py --smoke         # CI shape

Sections:
- paged: same 8 concurrent short requests against the dense per-row
  cache vs a pool 1/4 its size (the per-step blend write shrinks with
  the pool, so right-sizing is a SPEED win too, not just capacity).
- spec: fused speculative rounds with a SELF-draft (acceptance ~1 —
  the mechanical ceiling, and the worst case for round cost).
- prefix: cold vs cached admission of a repeated long prompt; where
  the dispatch round trip dominates the win vanishes; the section
  reports prefill_tokens_shared either way.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def build_argparser():
    p = argparse.ArgumentParser()
    p.add_argument("--only", default=None,
                   choices=[None, "paged", "spec", "prefix"])
    p.add_argument("--d_model", type=int, default=1024)
    p.add_argument("--n_layers", type=int, default=8)
    p.add_argument("--vocab_size", type=int, default=32000)
    p.add_argument("--max_seq_len", type=int, default=2048)
    p.add_argument("--max_new", type=int, default=48)
    p.add_argument("--smoke", action="store_true")
    return p


def _build(args):
    import jax
    import jax.numpy as jnp

    from tensorflowonspark_tpu.models.transformer import (
        Transformer, TransformerConfig)

    cfg = TransformerConfig(
        vocab_size=args.vocab_size, d_model=args.d_model,
        n_heads=max(2, args.d_model // 128),
        n_kv_heads=max(1, args.d_model // 256),
        n_layers=args.n_layers, d_ff=4 * args.d_model,
        max_seq_len=args.max_seq_len, dtype="bfloat16", rope=True,
        norm_type="rmsnorm", attention_impl="dense")
    model = Transformer(cfg)
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    params = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16)
        if jnp.issubdtype(x.dtype, jnp.floating) else x, params)
    return model, params


def bench_paged(args, model, params):
    import numpy as np

    from tensorflowonspark_tpu import serve

    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, args.vocab_size,
                           size=rng.choice([6, 10, 16])).tolist()
               for _ in range(8)]

    def run(**kw):
        b = serve.ContinuousBatcher(model, params, n_slots=8,
                                    read_chunk=8, **kw)
        try:
            b.submit(prompts[0], 2).result(timeout=900)
            t0 = time.perf_counter()
            hs = [b.submit(p, args.max_new) for p in prompts]
            outs = [h.result(timeout=900) for h in hs]
            return outs, 8 * args.max_new / (time.perf_counter() - t0)
        finally:
            b.stop()

    page = max(8, args.max_seq_len // 8)
    pool = (8 * args.max_seq_len) // (4 * page)   # 1/4 the dense resident
    dense_out, dense_tps = run()
    paged_out, paged_tps = run(kv_page_size=page, kv_pages=pool)
    return {
        "dense_tok_s": round(dense_tps, 1),
        "paged_tok_s": round(paged_tps, 1),
        "speedup": round(paged_tps / dense_tps, 2),
        "agreement": f"{sum(a == b for a, b in zip(dense_out, paged_out))}/8",
        "dense_kv_tokens": 8 * args.max_seq_len,
        "paged_pool_tokens": pool * page,
    }


def bench_spec(args, model, params):
    import numpy as np

    from tensorflowonspark_tpu import serve

    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, args.vocab_size, size=12).tolist()
               for _ in range(2)]

    def run(draft):
        kw = (dict(draft_model=model, draft_params=params, draft_k=4)
              if draft else {})
        b = serve.ContinuousBatcher(model, params, n_slots=2,
                                    read_chunk=8, **kw)
        try:
            b.submit(prompts[0], 2).result(timeout=900)
            t0 = time.perf_counter()
            hs = [b.submit(p, args.max_new) for p in prompts]
            outs = [h.result(timeout=900) for h in hs]
            dt = time.perf_counter() - t0
            return outs, 2 * args.max_new / dt, b._spec_rounds, b._steps
        finally:
            b.stop()

    plain_out, plain_tps, _, steps = run(False)
    spec_out, spec_tps, rounds, _ = run(True)
    return {
        "plain_tok_s": round(plain_tps, 1),
        "spec_tok_s": round(spec_tps, 1),
        "speedup": round(spec_tps / plain_tps, 2),
        "spec_rounds": rounds, "plain_steps": steps,
        "agreement": f"{sum(a == b for a, b in zip(plain_out, spec_out))}/2",
    }


def bench_prefix(args, model, params):
    import numpy as np

    from tensorflowonspark_tpu import serve

    rng = np.random.RandomState(0)
    n = min(args.max_seq_len - args.max_new - 8, 3 * args.max_seq_len // 4)
    prompt = rng.randint(1, args.vocab_size, size=n).tolist()
    page = max(8, args.max_seq_len // 8)

    b = serve.ContinuousBatcher(model, params, n_slots=4, read_chunk=2,
                                kv_page_size=page,
                                kv_pages=6 * args.max_seq_len // page,
                                prefill_chunk=max(64, page))
    try:
        b.submit(rng.randint(1, args.vocab_size, size=n).tolist(),
                 2).result(timeout=900)                    # warm compiles

        def ttft(p):
            h = b.submit(p, 2)
            t0 = time.perf_counter()
            h.tokens.get()
            dt = time.perf_counter() - t0
            h.result(timeout=900)
            return dt

        cold = ttft(prompt)
        ttft(prompt)          # first hit compiles the tail bucket
        cached = ttft(prompt)
        s = b.stats()
        return {
            "prompt_tokens": n,
            "cold_ttft_ms": round(cold * 1e3, 1),
            "cached_ttft_ms": round(cached * 1e3, 1),
            "prefill_tokens_shared": s["prefill_tokens_shared"],
            "prefix_pages_cached": s["prefix_pages_cached"],
        }
    finally:
        b.stop()


def main(argv=None):
    args = build_argparser().parse_args(argv)
    if args.smoke:
        args.d_model, args.n_layers = 64, 2
        args.vocab_size, args.max_seq_len, args.max_new = 128, 256, 8

    import jax

    from tensorflowonspark_tpu import util

    util.enable_compile_cache()

    model, params = _build(args)
    out = {"platform": jax.devices()[0].platform}
    if args.only in (None, "paged"):
        out["paged"] = bench_paged(args, model, params)
    if args.only in (None, "spec"):
        out["spec"] = bench_spec(args, model, params)
    if args.only in (None, "prefix"):
        out["prefix"] = bench_prefix(args, model, params)
    print(json.dumps(out, indent=2))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
