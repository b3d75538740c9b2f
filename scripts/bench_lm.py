"""LM training throughput harness (not driver-run; bench.py stays the
single driver metric):

    python scripts/bench_lm.py                 # 56M params, B16 S1024 bf16
    python scripts/bench_lm.py --attention dense   # XLA-dense comparison
    python scripts/bench_lm.py --preset flagship   # the bench.py metric config

Prints step time, tokens/sec, and a 6·N·T-FLOP MFU estimate against the
chip's bf16 peak (from `tensorflowonspark_tpu.benchmarks.PEAK_BF16`, the
same table bench.py uses).
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__), "..")))


def main():
    from tensorflowonspark_tpu import benchmarks

    p = argparse.ArgumentParser()
    p.add_argument("--preset", default=None,
                   choices=[None, "flagship", "flagship_v1"],
                   help="flagship = benchmarks.FLAGSHIP_LM_V2 (rmsnorm), "
                        "exactly the bench.py round-5 driver-metric "
                        "config; flagship_v1 = the round-3/4 LayerNorm "
                        "config (FLAGSHIP_LM)")
    p.add_argument("--batch_size", type=int, default=16)
    p.add_argument("--seq_len", type=int, default=1024)
    p.add_argument("--d_model", type=int, default=512)
    p.add_argument("--n_layers", type=int, default=8)
    p.add_argument("--n_heads", type=int, default=8)
    p.add_argument("--n_kv_heads", type=int, default=4)
    p.add_argument("--d_ff", type=int, default=2048)
    p.add_argument("--vocab_size", type=int, default=32000)
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--mu_dtype", default=None,
                   help="optimizer first-moment dtype, e.g. bfloat16")
    p.add_argument("--attention", default="auto",
                   choices=["auto", "flash", "dense"])
    p.add_argument("--norm_type", default="layernorm",
                   choices=["layernorm", "rmsnorm"],
                   help="rmsnorm = LLaMA-style scale-only norm (one "
                        "statistics reduce instead of two)")
    args = p.parse_args()

    import numpy as np

    import jax

    if args.preset in ("flagship", "flagship_v1"):
        # the EXACT driver-metric step — no reassembled look-alike
        config = "v2" if args.preset == "flagship" else "v1"
        step, state, tokens, n_params = benchmarks.make_flagship_step(
            config=config)
        B, S = tokens.shape[0], tokens.shape[1] - 1
        cfg_dict = (benchmarks.FLAGSHIP_LM_V2 if config == "v2"
                    else benchmarks.FLAGSHIP_LM)
        attention = cfg_dict["attention_impl"]
    else:
        import jax.numpy as jnp

        from tensorflowonspark_tpu.models.transformer import (
            Transformer, TransformerConfig, lm_loss)
        from tensorflowonspark_tpu.optim import make_optimizer
        from tensorflowonspark_tpu.parallel import train as train_mod

        cfg = TransformerConfig(
            vocab_size=args.vocab_size, d_model=args.d_model,
            n_heads=args.n_heads, n_kv_heads=args.n_kv_heads,
            n_layers=args.n_layers, d_ff=args.d_ff,
            max_seq_len=args.seq_len, dtype="bfloat16", rope=True,
            attention_impl=args.attention, norm_type=args.norm_type)
        model = Transformer(cfg)
        B, S = args.batch_size, args.seq_len
        attention = args.attention
        tokens = jnp.asarray(
            np.random.RandomState(0).randint(0, cfg.vocab_size, (B, S + 1)),
            jnp.int32)
        params = model.init(jax.random.key(0), tokens[:, :S])["params"]
        n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))

        def loss_fn(p, batch, rng):
            return lm_loss(model.apply({"params": p}, batch[:, :-1]),
                           batch[:, 1:])

        opt, _ = make_optimizer("adamw", learning_rate=3e-4,
                                mu_dtype=args.mu_dtype)
        state = train_mod.create_train_state(params, opt)
        step = train_mod.make_train_step(loss_fn, opt, donate=True)

    state, m = step(state, tokens, jax.random.key(1))
    _ = np.asarray(m["loss"])                       # warm + sync
    t0 = time.perf_counter()
    for _ in range(args.steps):
        state, m = step(state, tokens, jax.random.key(1))
    _ = np.asarray(m["loss"])
    dt = (time.perf_counter() - t0) / args.steps

    kind = jax.devices()[0].device_kind
    peak = benchmarks.bf16_peak(kind)
    mfu = (6 * n_params * B * S / dt / peak * 100) if peak else float("nan")
    print(f"device={kind} params={n_params / 1e6:.1f}M attention={attention}")
    print(f"step={dt * 1000:.1f} ms  tokens/sec={B * S / dt:,.0f}  "
          f"MFU~{mfu:.1f}%")


if __name__ == "__main__":
    main()
