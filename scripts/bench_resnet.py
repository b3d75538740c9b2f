"""ResNet-50 training throughput harness (the BASELINE north-star
workload on one chip; not driver-run — bench.py is the single driver
metric and imports `bench_step` from here).

    python scripts/bench_resnet.py                   # GroupNorm (round-1)
    python scripts/bench_resnet.py --norm none       # normalizer-free
    python scripts/bench_resnet.py --norm none --batch_size 512

Round-1 methodology: 224px bf16 images, sgd+momentum, donated state,
device-resident batch, readback-synced timing windows.
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__), "..")))

# 3 * fwd FLOPs/img at 224px; fwd ResNet-50 is ~4.1 GFLOP
FLOP_PER_IMAGE = 3 * 4.1e9
PEAK_BF16 = {"TPU v5 lite": 197e12, "TPU v4": 275e12, "TPU v5p": 459e12}


def build_step(norm="group", batch_size=256, image_size=224,
               num_classes=1000, stem="conv"):
    """Returns (step, state, batch, labels); step is donated + jitted."""
    import numpy as np

    import jax
    import jax.numpy as jnp
    import optax

    from tensorflowonspark_tpu.models.resnet import ResNet50
    from tensorflowonspark_tpu.parallel import train as train_mod

    model = ResNet50(norm=norm, stem=stem)
    rng = np.random.RandomState(0)
    images = jnp.asarray(
        rng.rand(batch_size, image_size, image_size, 3), jnp.bfloat16)
    labels = jnp.asarray(rng.randint(0, num_classes, (batch_size,)), jnp.int32)
    params = model.init(jax.random.key(0), images[:1])["params"]

    def loss_fn(p, batch, _rng):
        imgs, labs = batch
        logits = model.apply({"params": p}, imgs)
        onehot = jax.nn.one_hot(labs, num_classes, dtype=jnp.float32)
        return -jnp.mean(jnp.sum(
            jax.nn.log_softmax(logits.astype(jnp.float32)) * onehot, axis=-1))

    opt = optax.sgd(0.1, momentum=0.9)
    state = train_mod.create_train_state(params, opt)
    step = train_mod.make_train_step(loss_fn, opt, donate=True)
    return step, state, (images, labels), params


def bench_step(norm="group", batch_size=256, steps=30, windows=3,
               stem="conv"):
    """Best-of-`windows` images/sec over `steps`-step readback-synced runs."""
    import numpy as np

    import jax

    step, state, batch, _ = build_step(norm=norm, batch_size=batch_size,
                                       stem=stem)
    state, m = step(state, batch, jax.random.key(1))
    _ = np.asarray(m["loss"])                       # compile + sync
    best = float("inf")
    for _ in range(windows):
        t0 = time.perf_counter()
        for _ in range(steps):
            state, m = step(state, batch, jax.random.key(1))
        _ = np.asarray(m["loss"])                   # host readback barrier
        best = min(best, (time.perf_counter() - t0) / steps)
    return batch_size / best, best


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--norm", default="group",
                   choices=["group", "none", "batch"])
    p.add_argument("--stem", default="conv", choices=["conv", "s2d"])
    p.add_argument("--batch_size", type=int, default=256)
    p.add_argument("--steps", type=int, default=30)
    p.add_argument("--windows", type=int, default=3)
    p.add_argument("--trace", action="store_true",
                   help="capture a 3-step profiler trace and print the "
                        "device time / bytes / actual-HLO-FLOPs breakdown "
                        "by hlo_category (the roofline evidence)")
    args = p.parse_args()

    import jax

    from tensorflowonspark_tpu import util

    util.enable_compile_cache()

    ips, dt = bench_step(norm=args.norm, batch_size=args.batch_size,
                         steps=args.steps, windows=args.windows,
                         stem=args.stem)
    kind = jax.devices()[0].device_kind
    peak = next((v for k, v in PEAK_BF16.items() if k in kind), None)
    mfu = (ips * FLOP_PER_IMAGE / peak * 100) if peak else float("nan")
    print(f"device={kind} norm={args.norm} stem={args.stem} "
          f"batch={args.batch_size}")
    print(f"step={dt * 1000:.1f} ms  images/sec={ips:,.0f}  MFU~{mfu:.1f}%")
    if args.trace:
        profile_step(norm=args.norm, batch_size=args.batch_size,
                     stem=args.stem, peak=peak)


def profile_step(norm="none", batch_size=256, stem="conv", peak=None,
                 trace_dir="/tmp/resnet_trace", built=None):
    """3-step trace -> per-hlo_category device time / bytes / FLOPs table.

    The question it answers: with norm='none', do the convolution
    fusions (elementwise already fused into their epilogues) carry the
    device time, i.e. is the naive 3*4.1GF/img MFU bounded by conv HBM
    traffic and not by an unfused elementwise tail?  On this chip: not
    measured.
    """
    import collections
    import glob
    import gzip
    import json

    import numpy as np

    import jax

    if built is None:       # standalone call; main() could pass bench's
        built = build_step(norm=norm, batch_size=batch_size, stem=stem)[:3]
    step, state, batch = built
    state, m = step(state, batch, jax.random.key(1))
    _ = np.asarray(m["loss"])                       # compile + sync
    jax.profiler.start_trace(trace_dir)
    for _ in range(3):
        state, m = step(state, batch, jax.random.key(1))
    _ = np.asarray(m["loss"])
    jax.profiler.stop_trace()
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins/profile/*/*.trace.json.gz")))
    with gzip.open(paths[-1]) as f:
        trace = json.load(f)
    pids = {e["pid"] for e in trace["traceEvents"]
            if e.get("ph") == "M" and e.get("name") == "process_name"
            and "TPU" in e["args"].get("name", "")}
    cat = collections.Counter()
    byt = collections.Counter()
    flops = collections.Counter()
    for e in trace["traceEvents"]:
        a = e.get("args") or {}
        if (e.get("ph") == "X" and e.get("pid") in pids
                and "hlo_category" in a):
            c = a["hlo_category"]
            cat[c] += e["dur"]
            byt[c] += int(a.get("raw_bytes_accessed", 0))
            flops[c] += int(a.get("model_flops", 0) or 0)
    tot = sum(cat.values())
    print(f"\ndevice op time {tot / 3e3:.1f} ms/step "
          f"({sum(flops.values()) / 3e9:,.0f} actual GFLOP/step):")
    for c, us in cat.most_common():
        ms = us / 3e3
        gib = byt[c] / 3 / (1 << 30)
        gf = flops[c] / 3e9
        line = f"  {ms:7.2f} ms  {gib:7.2f} GiB  {gf:8.1f} GF  {c}"
        if us:
            line += f"  ({byt[c] / (us * 1e-6) / 1e9:,.0f} GB/s)"
        print(line)
    if peak:
        print(f"actual-HLO MXU utilization: "
              f"{sum(flops.values()) / 3 / (tot / 3e6) / peak * 100:.0f}%")


if __name__ == "__main__":
    main()
