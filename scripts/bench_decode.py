"""KV-cache decode throughput harness (not driver-run; bench.py stays the
single driver metric).

Measures autoregressive generation on the flagship-LM config — the
serving-side complement of the training MFU metric:

    python scripts/bench_decode.py                  # flagship dims
    python scripts/bench_decode.py --batch_size 32  # batched serving shape

Reports prefill time, per-token decode latency, and decode tokens/sec.
Timing barrier is a host readback of the final token.
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__), "..")))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--batch_size", type=int, default=8)
    p.add_argument("--prompt_len", type=int, default=128)
    p.add_argument("--new_tokens", type=int, default=128)
    p.add_argument("--windows", type=int, default=3)
    p.add_argument("--norm_type", default="rmsnorm",
                   choices=["layernorm", "rmsnorm"])
    p.add_argument("--loop", default="auto",
                   choices=["auto", "scan", "host"],
                   help="token-loop driver (host = one async dispatch per "
                        "token; 10x on high-dispatch-overhead runtimes)")
    p.add_argument("--param_dtype", default="bfloat16",
                   help="serving weight width (bfloat16 = what serve's "
                        ":generate uses; float32 = training masters)")
    p.add_argument("--quantize", default="none", choices=["none", "int8"],
                   help="int8 = weight-only quantized decode (W8A16, "
                        "inline dequant per step — serve's "
                        "--generate_quantize int8)")
    p.add_argument("--d_model", type=int, default=2048)
    p.add_argument("--n_layers", type=int, default=16)
    p.add_argument("--n_heads", type=int, default=16)
    p.add_argument("--n_kv_heads", type=int, default=8)
    p.add_argument("--d_ff", type=int, default=8192)
    p.add_argument("--vocab_size", type=int, default=32000)
    args = p.parse_args()

    import numpy as np

    import jax
    import jax.numpy as jnp

    from tensorflowonspark_tpu.models import decode
    from tensorflowonspark_tpu.models.transformer import (
        Transformer, TransformerConfig)

    S = args.prompt_len + args.new_tokens
    cfg = TransformerConfig(
        vocab_size=args.vocab_size, d_model=args.d_model,
        n_heads=args.n_heads, n_kv_heads=args.n_kv_heads,
        n_layers=args.n_layers, d_ff=args.d_ff, max_seq_len=S,
        dtype="bfloat16", rope=True, norm_type=args.norm_type)
    model = Transformer(cfg)
    B = args.batch_size
    prompt = jnp.asarray(
        np.random.RandomState(0).randint(0, cfg.vocab_size,
                                         (B, args.prompt_len)), jnp.int32)
    params = model.init(jax.random.key(0), prompt)["params"]
    if args.quantize == "int8":
        # mirror serve._load_lm: quantize from the f32 masters, then cast
        # the unquantized remainder to the serving width
        from tensorflowonspark_tpu import quantize as quantize_mod
        params = quantize_mod.quantize_tree(params)
        if args.param_dtype != "float32":
            params = quantize_mod.cast_float_leaves(
                params, jnp.dtype(args.param_dtype))
        qb, fb = quantize_mod.quantized_bytes(params)
        print(f"int8 weights: {qb / 1e6:.0f} MB quantized "
              f"(f32-equivalent {fb / 1e6:.0f} MB)")
    elif args.param_dtype != "float32":
        from tensorflowonspark_tpu import quantize as quantize_mod
        params = quantize_mod.cast_float_leaves(
            params, jnp.dtype(args.param_dtype))
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))

    def run():
        out = decode.generate(model, params, prompt,
                              max_new_tokens=args.new_tokens,
                              temperature=0.0, loop=args.loop)
        np.asarray(out[:, -1])            # host readback barrier
        return out

    run()                                 # compile (prefill + scan)
    best = float("inf")
    for _ in range(args.windows):
        t0 = time.perf_counter()
        run()
        best = min(best, time.perf_counter() - t0)

    # prefill-only timing: generate 1 token (scan body compiles separately
    # but its single step is negligible next to the prompt pass).  The
    # warmup must SYNC before the timer starts or its queued execution
    # lands inside the timed window.
    np.asarray(decode.generate(model, params, prompt, max_new_tokens=1,
                               temperature=0.0, loop=args.loop)[:, -1])
    t0 = time.perf_counter()
    out = decode.generate(model, params, prompt, max_new_tokens=1,
                          temperature=0.0, loop=args.loop)
    np.asarray(out[:, -1])
    prefill = time.perf_counter() - t0

    dec = best - prefill
    per_tok = dec / max(args.new_tokens - 1, 1)
    kind = jax.devices()[0].device_kind
    print(f"device={kind} params={n_params / 1e6:.0f}M B={B} "
          f"prompt={args.prompt_len} new={args.new_tokens} "
          f"norm={args.norm_type} loop={args.loop}")
    print(f"end-to-end={best * 1000:.0f} ms  prefill~{prefill * 1000:.0f} ms  "
          f"decode={per_tok * 1000:.2f} ms/tok  "
          f"throughput={B / per_tok:,.0f} tok/s")


if __name__ == "__main__":
    main()
