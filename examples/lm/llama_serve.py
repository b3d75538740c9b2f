"""End-to-end LLaMA serving: import -> export -> HTTP generation.

The deployment half of the LM story (gpt2_finetune.py covers tuning):

1. `convert.from_hf_llama` imports a LLaMA-family checkpoint (a local
   `--model_path`, or a small randomly-initialized LLaMA when absent so
   the example runs fully offline) — RMSNorm, SwiGLU, GQA, RoPE map
   onto the flagship decoder with exact logit parity;
2. `export.export_saved_model` writes the rebuildable artifact with the
   `build_transformer` builder spec;
3. `serve.make_server` hosts it, and `POST /v1/models/default:generate`
   returns kv-cache greedy/sampled continuations (the server casts the
   f32 masters to the model's compute width — half the weight bytes per
   token).  Requests decode through the continuous-batching slot engine
   (round 5); `--kv_page_size/--kv_pages` switch its cache to the PAGED
   pool (resident kv proportional to actual need; speed on this chip:
   not measured).

Run:
    python examples/lm/llama_serve.py --new_tokens 16
    python examples/lm/llama_serve.py --model_path /ckpts/llama --serve_only
    python examples/lm/llama_serve.py --kv_page_size 256 --kv_pages 16
"""
import argparse
import dataclasses
import json
import os
import sys
import threading
import urllib.request

sys.path.insert(0, os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..")))


def build_argparser():
    p = argparse.ArgumentParser()
    p.add_argument("--model_path", default=None,
                   help="local HF LLaMA dir; default: tiny random LLaMA")
    p.add_argument("--out_dir", default=None,
                   help="export dir (default: a temp dir)")
    p.add_argument("--port", type=int, default=0,
                   help="0 = ephemeral")
    p.add_argument("--new_tokens", type=int, default=16)
    p.add_argument("--temperature", type=float, default=0.0)
    p.add_argument("--serve_only", action="store_true",
                   help="serve forever instead of one demo round trip")
    p.add_argument("--platform", default=None,
                   help="pin jax platform (e.g. cpu)")
    p.add_argument("--slots", type=int, default=8,
                   help="continuous-batching decode slots")
    p.add_argument("--kv_page_size", type=int, default=0,
                   help=">0: paged kv cache (tokens per pool page)")
    p.add_argument("--kv_pages", type=int, default=0,
                   help="pool size (pages) for --kv_page_size")
    p.add_argument("--quantize", choices=["none", "int8"], default="none",
                   help="int8 = weight-only quantized serving (W8A16: "
                        "~4x less weight HBM, inline dequant per step)")
    p.add_argument("--lora_rank", type=int, default=0,
                   help=">0: multi-adapter LoRA bank on the slots; a "
                        "demo adapter registers as 'demo' and the round "
                        "trip generates with and without it")
    p.add_argument("--kv_dtype", choices=["auto", "int8"], default="auto",
                   help="int8 = quantized kv cache (~2x less resident kv)")
    return p


def _tiny_llama():
    import torch
    import transformers

    cfg = transformers.LlamaConfig(
        vocab_size=512, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128, tie_word_embeddings=False)
    torch.manual_seed(0)
    return transformers.LlamaForCausalLM(cfg).eval()


def main(argv=None):
    args = build_argparser().parse_args(argv)
    if args.platform:
        from tensorflowonspark_tpu import util
        util.pin_platform(args.platform)

    from tensorflowonspark_tpu import convert, export, serve

    # 1. import --------------------------------------------------------
    src = args.model_path if args.model_path else _tiny_llama()
    cfg, params = convert.from_hf_llama(src)
    print(f"imported LLaMA: d{cfg.d_model} L{cfg.n_layers} "
          f"heads {cfg.n_heads}/{cfg.n_kv_heads} vocab {cfg.vocab_size}")

    # 2. export --------------------------------------------------------
    out_dir = args.out_dir
    if out_dir is None:
        import tempfile
        out_dir = os.path.join(tempfile.mkdtemp(), "llama_export")
    export.export_saved_model(
        out_dir, params,
        builder="tensorflowonspark_tpu.models.transformer:build_transformer",
        builder_kwargs=dataclasses.asdict(cfg))
    print(f"exported to {out_dir}")

    # 3. serve + generate ---------------------------------------------
    serve_argv = ["--export_dir", out_dir, "--port", str(args.port),
                  "--generate_slots", str(args.slots)]
    if args.kv_page_size:
        serve_argv += ["--generate_kv_page_size", str(args.kv_page_size),
                       "--generate_kv_pages", str(args.kv_pages)]
    if args.quantize != "none":
        serve_argv += ["--generate_quantize", args.quantize]
    if args.kv_dtype != "auto":
        serve_argv += ["--generate_kv_dtype", args.kv_dtype]
    if args.lora_rank:
        # write a demo adapter next to the export and register it as
        # 'demo': the round trip below generates with and without it
        import jax

        from tensorflowonspark_tpu import lora
        adapters = lora.init(jax.random.key(1), params,
                             rank=args.lora_rank)
        for i, p in enumerate(sorted(adapters)):
            adapters[p]["b"] = jax.random.normal(
                jax.random.fold_in(jax.random.key(2), i),
                adapters[p]["b"].shape)
        lora_path = os.path.join(os.path.dirname(out_dir) or ".",
                                 "demo_adapter.msgpack")
        lora.save_adapters(lora_path, adapters, scale=1.0)
        serve_argv += ["--generate_lora_rank", str(args.lora_rank),
                       "--generate_lora", f"demo={lora_path}"]
    serve_args = serve.build_argparser().parse_args(serve_argv)
    server, service = serve.make_server(serve_args)
    host, port = server.server_address[:2]
    print(f"serving on http://{host}:{port}")
    if args.serve_only:
        server.serve_forever()
        return

    t = threading.Thread(target=server.serve_forever, daemon=True)
    t.start()
    try:
        prompts = [[1, 5, 9, 13], [2, 4, 6, 8]]
        body = {"inputs": prompts, "max_new_tokens": args.new_tokens,
                "temperature": args.temperature}
        if args.temperature > 0:
            body["seed"] = 0
        req = urllib.request.Request(
            f"http://{host}:{port}/v1/models/default:generate",
            data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=600) as r:
            outs = json.loads(r.read())["outputs"]
        for prompt, seq in zip(prompts, outs):
            print(f"prompt {prompt} -> continuation {seq[len(prompt):]}")
        if args.lora_rank:
            body["adapter"] = "demo"
            req = urllib.request.Request(
                f"http://{host}:{port}/v1/models/default:generate",
                data=json.dumps(body).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=600) as r:
                aouts = json.loads(r.read())["outputs"]
            for prompt, seq in zip(prompts, aouts):
                print(f"prompt {prompt} -> adapter 'demo' continuation "
                      f"{seq[len(prompt):]}")
        print("llama serving round trip complete")
    finally:
        server.shutdown()
        server.server_close()


if __name__ == "__main__":
    main()
