#!/usr/bin/env python3
"""Chip smoke: cluster-fed flagship training on the TPU, end to end.

The quickest proof that the system still starts on the chip.  A JAX-free
driver (this process) launches a one-executor cluster through the normal
entry points — `cluster.run(LocalBackend(1), map_fun, ...,
InputMode.SPARK)`, `c.train(partitions)`, `c.shutdown(timeout=...)`; the
background node process owns the chip, builds the flagship LM
(FLAGSHIP_LM_V2: 0.87B params, d2048, 16 layers, GQA 16/8, d_ff 8192,
S=1024, batch 8, bf16, rmsnorm, adamw_fused), pulls
`[8, 1025]` int32 token batches off the shm ring with
`DataFeed.next_numpy_batch`, keeps transfers in flight with
`feed.device_prefetch`, and takes a few donated steps.

    python chip_smoke.py             # one chip (what the driver runs)
    python chip_smoke.py --chips 4   # dp=4 mesh vs one device, same batch

Every output line is one JSON object; the last is
`{"ok": true, "device": {...}}` with the device as the NODE process saw
it.  Exit is non-zero, with the reason on stderr and no ok line, when the
node was not on a TPU, the compiled step holds no Pallas kernel, a loss is
not finite or does not fall, the node failed, or records went missing.
This is a smoke, not a benchmark: its timings are single samples.
"""
import argparse
import json
import os
import shutil
import signal
import sys
import tempfile
import time

RESULT_FILE = "chip_smoke.json"
WARM_STEPS = 2        # untimed: first dispatches after compile
TIMED_STEPS = 5       # per barrier (block_until_ready, then readback)
COMPARE_STEPS = 3     # --chips 4: steps compared against one device
# bf16 activations: the dp=4 run reduces the batch mean and the gradient
# all-reduce in another order than one device does.  1% of a loss is
# ~2.5 bf16 ulps (2**-8 each).
LOSS_RTOL = 1e-2
BUDGET_S = 1100       # whole script, compile included (driver limit 1200)

# What the smoke trains: a private 0.87B LLaMA-shaped config at the width
# the earlier rounds' records were taken at.  Plain dicts and scalars:
# the driver process imports no JAX.
FLAGSHIP_LM_V2 = dict(
    vocab_size=32000, d_model=2048, n_heads=16, n_kv_heads=8,
    n_layers=16, d_ff=8192, max_seq_len=1024, dtype="bfloat16",
    rope=True, attention_impl="auto", norm_type="rmsnorm")
FLAGSHIP_BATCH = 8
FLAGSHIP_OPTIMIZER = "adamw_fused"    # ops/fused_optim.py, one HBM pass
FLAGSHIP_MU_DTYPE = "bfloat16"


def smoke_args(chips=1, seed=0, platform="tpu", model=None, batch=None):
    """The run's description, shipped to the node as `tf_args`.  The
    defaults ARE the smoke (flagship width on a TPU); the CPU rehearsal in
    tests/test_chip_smoke.py passes a toy `model` and `platform="cpu"`."""
    return argparse.Namespace(
        chips=chips, seed=seed, platform=platform,
        model=dict(model or FLAGSHIP_LM_V2),
        batch=batch or FLAGSHIP_BATCH,
        steps=WARM_STEPS + 2 * TIMED_STEPS)


# --------------------------------------------------------------- node ----

def _build(args, mesh):
    """Model, state and the donated train step over `mesh` (None: one
    device)."""
    import jax
    import jax.numpy as jnp

    from tensorflowonspark_tpu.models.transformer import (
        Transformer, TransformerConfig, lm_loss)
    from tensorflowonspark_tpu.optim import make_optimizer
    from tensorflowonspark_tpu.parallel import train as train_mod

    cfg = TransformerConfig(**args.model)
    model = Transformer(cfg)
    tokens = jnp.zeros((args.batch, cfg.max_seq_len), jnp.int32)

    def loss_fn(p, batch, rng):
        return lm_loss(model.apply({"params": p}, batch[:, :-1]),
                       batch[:, 1:])

    opt, _ = make_optimizer(FLAGSHIP_OPTIMIZER, learning_rate=3e-4,
                            mu_dtype=FLAGSHIP_MU_DTYPE)
    init = jax.jit(lambda key: model.init(key, tokens)["params"])

    def make_state(params):
        return train_mod.create_train_state(params, opt, mesh=mesh)

    step = train_mod.make_train_step(loss_fn, opt, mesh=mesh, donate=True)
    return init, make_state, step


def _compile_run(args, mesh, events):
    """Compile init and step (timed, cache hits counted); returns
    `(state, compiled_step, batch_sharding, info)`."""
    import contextlib

    import jax
    import numpy as np

    from tensorflowonspark_tpu.parallel import mesh as mesh_mod

    init, make_state, step = _build(args, mesh)
    key = jax.random.key(args.seed)
    hits0 = events["hits"]
    t0 = time.perf_counter()
    init_c = init.lower(key).compile()
    init_s = time.perf_counter() - t0
    state = make_state(init_c(key))
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(state.params))
    S = args.model["max_seq_len"]
    sharding = mesh_mod.batch_sharding(mesh) if mesh is not None else None
    batch0 = jax.device_put(np.zeros((args.batch, S + 1), np.int32),
                            sharding)
    # the flash dispatch shard_maps the kernel over the AMBIENT mesh
    with jax.set_mesh(mesh) if mesh is not None else \
            contextlib.nullcontext():
        t0 = time.perf_counter()
        compiled = step.lower(state, batch0, jax.random.key(1)).compile()
        step_s = time.perf_counter() - t0
    text = compiled.as_text()
    # what the step needs on a device: memory_stats()'s peak counts live
    # buffers, not the program's temporaries
    mem = compiled.memory_analysis()
    info = {
        "n_params": int(n_params),
        "init_compile_s": init_s, "step_compile_s": step_s,
        "persistent_cache_hits": events["hits"] - hits0,
        "tpu_custom_calls": text.count("tpu_custom_call"),
        "all_reduces": text.count("all-reduce"),
        "step_argument_bytes": getattr(mem, "argument_size_in_bytes", None),
        "step_temp_bytes": getattr(mem, "temp_size_in_bytes", None),
    }
    return state, compiled, sharding, info


def map_fun(args, ctx):
    """The training node.  Owns the chip; everything JAX happens here."""
    import numpy as np

    from tensorflowonspark_tpu import feed as feed_mod
    from tensorflowonspark_tpu import util

    cache_dir = util.enable_compile_cache()
    import jax

    events = {"hits": 0}

    def _on_event(name, **_kw):
        if name == "/jax/compilation_cache/cache_hits":
            events["hits"] += 1

    jax.monitoring.register_event_listener(_on_event)

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if device["platform"] != args.platform:
        raise RuntimeError(
            f"node sees platform {device['platform']!r}, not "
            f"{args.platform!r}: {devs}")
    if len(devs) < args.chips:
        raise RuntimeError(f"--chips {args.chips} but node sees {devs}")

    from tensorflowonspark_tpu.parallel import mesh as mesh_mod

    mesh = None
    if args.chips > 1:
        mesh = mesh_mod.build_mesh(mesh_mod.MeshSpec(dp=args.chips),
                                   devices=devs[:args.chips])
    state, step, sharding, info = _compile_run(args, mesh, events)
    lines = [dict(info, phase="compile", chips=args.chips,
                  compile_cache_dir=cache_dir)]

    B, S = args.batch, args.model["max_seq_len"]
    df = ctx.get_data_feed(train_mode=True)
    seen = {"records": 0, "feed_wait_s": 0.0, "first": None}

    def host_batches():
        while not df.should_stop():
            t0 = time.perf_counter()
            toks = df.next_numpy_batch(B, dtype=np.int32, timeout=300)
            if toks is None or len(toks) == 0:
                continue        # end of feed: not a wait for data
            seen["feed_wait_s"] += time.perf_counter() - t0
            seen["records"] += len(toks)
            if len(toks) != B:
                raise RuntimeError(f"ragged batch of {len(toks)} records")
            if seen["first"] is None:
                seen["first"] = toks
            yield toks

    rng = jax.random.key(1)
    losses, sync_ms, readback_ms, shard_devices = [], [], [], None
    t_window = t_end = None
    for i, batch in enumerate(feed_mod.device_prefetch(
            host_batches(), sharding=sharding, depth=2)):
        if shard_devices is None:
            shard_devices = sorted(
                {str(s.device) for s in batch.addressable_shards})
        if i == WARM_STEPS:
            t_window = time.perf_counter()
        t0 = time.perf_counter()
        state, metrics = step(state, batch, rng)
        if WARM_STEPS <= i < WARM_STEPS + TIMED_STEPS:
            jax.block_until_ready((state, metrics))
            sync_ms.append((time.perf_counter() - t0) * 1e3)
        else:
            np.asarray(metrics["loss"])             # readback barrier
            if i >= WARM_STEPS + TIMED_STEPS:
                readback_ms.append((time.perf_counter() - t0) * 1e3)
        losses.append(float(metrics["loss"]))
        t_end = time.perf_counter()
    window_s = t_end - t_window if t_window else None
    stats = devs[0].memory_stats() or {}
    n_timed = len(sync_ms) + len(readback_ms)
    lines.append({
        "phase": "train", "chips": args.chips, "batch": B, "seq_len": S,
        "steps": len(losses), "losses": losses,
        "step_ms_block_until_ready": sync_ms,
        "step_ms_readback": readback_ms,
        "tokens_per_s": (B * S * len(sync_ms) / (sum(sync_ms) / 1e3)
                         if sync_ms else None),
        "window_tokens_per_s": (B * S * n_timed / window_s
                                if n_timed else None),
        "feed_wait_s": seen["feed_wait_s"],
        "records_consumed": seen["records"],
        "shard_devices": shard_devices,
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        "bytes_limit": stats.get("bytes_limit"),
    })

    if mesh is not None:
        # what the mesh run is compared with: the same seed batch on ONE
        # device of this host, the sharded state freed first
        del state, step, batch
        one_state, one_step, _, one_info = _compile_run(args, None, events)
        first = jax.device_put(seen["first"])
        one_losses = []
        for _ in range(COMPARE_STEPS):
            one_state, metrics = one_step(one_state, first, rng)
            one_losses.append(float(metrics["loss"]))
        lines.append(dict(one_info, phase="one_device_reference",
                          losses=one_losses, rtol=LOSS_RTOL))

    out = {"device": device, "lines": lines}
    tmp = os.path.join(ctx.working_dir, RESULT_FILE + ".tmp")
    with open(tmp, "w") as f:
        json.dump(out, f)
    os.rename(tmp, os.path.join(ctx.working_dir, RESULT_FILE))


# ------------------------------------------------------------- driver ----

def seed_partitions(args):
    """`steps` copies of ONE seeded `[batch, S+1]` token batch, as two
    partitions of records (one int32 row per record): on a repeated batch
    the loss must fall, which is what makes a silent no-op step visible."""
    import numpy as np

    S = args.model["max_seq_len"]
    toks = np.random.RandomState(args.seed).randint(
        0, args.model["vocab_size"], (args.batch, S + 1)).astype(np.int32)
    half = args.steps // 2
    return [list(toks) * n for n in (half, args.steps - half)]


def check(args, result, fed):
    """Every way the run could look fine while the chip did nothing.
    Returns the list of failures (empty = pass)."""
    import math

    bad = []
    lines = {ln["phase"]: ln for ln in result["lines"]}
    comp, train = lines["compile"], lines["train"]
    if result["device"]["platform"] != args.platform:
        bad.append(f"node platform {result['device']['platform']!r}")
    if args.platform == "tpu" and comp["tpu_custom_calls"] <= 0:
        bad.append("compiled step holds no tpu_custom_call: the Pallas "
                   "kernels fell back to dense/interpret")
    losses = train["losses"]
    if not losses or not all(math.isfinite(x) for x in losses):
        bad.append(f"non-finite loss: {losses}")
    elif not losses[-1] < losses[0]:
        bad.append(f"loss did not fall on the repeated batch: {losses}")
    if train["records_consumed"] != fed:
        bad.append(f"fed {fed} records, node consumed "
                   f"{train['records_consumed']}")
    if len(train["step_ms_block_until_ready"]) < TIMED_STEPS or \
            len(train["step_ms_readback"]) < TIMED_STEPS:
        bad.append("fewer timed steps than planned")
    if args.chips > 1:
        ref = lines["one_device_reference"]
        if len(train["shard_devices"]) != args.chips:
            bad.append(f"batch shards sit on {train['shard_devices']}, "
                       f"not {args.chips} distinct devices")
        if comp["all_reduces"] <= 0:
            bad.append("no all-reduce in the compiled mesh step")
        if args.platform == "tpu" and ref["tpu_custom_calls"] <= 0:
            bad.append("one-device reference step holds no kernel")
        for a, b in zip(losses[:COMPARE_STEPS], ref["losses"]):
            if not abs(a - b) <= LOSS_RTOL * max(abs(a), abs(b)):
                bad.append(f"mesh losses {losses[:COMPARE_STEPS]} vs "
                           f"one device {ref['losses']} beyond rtol "
                           f"{LOSS_RTOL}")
                break
    return bad


def drive(args, map_fn=map_fun, start_method="fork", timeout=900):
    """Run the smoke through the cluster API; returns the node's result
    dict (device + lines).  Raises when the node failed or its result
    does not pass `check`.  `start_method="spawn"` is for callers whose
    own process is already JAX-threaded (the pytest rehearsal)."""
    from tensorflowonspark_tpu import backend, cluster

    # single host: loopback rendezvous (the routable-IP default is for
    # real clusters; a sealed machine has no route to probe with)
    os.environ.setdefault("TFOS_TPU_SERVER_HOST", "127.0.0.1")
    workdir = tempfile.mkdtemp(prefix="chip-smoke-")
    be = backend.LocalBackend(1, workdir=workdir, start_method=start_method)
    try:
        # num_chips=0: the one node process takes the whole host's chips
        c = cluster.run(be, map_fn, args, num_executors=1,
                        input_mode=cluster.InputMode.SPARK, num_chips=0,
                        reservation_timeout=120)
        try:
            parts = seed_partitions(args)
            fed = sum(len(p) for p in parts)
            c.train(parts, feed_timeout=timeout)
            c.shutdown(timeout=timeout)
        except BaseException:
            c.abort()
            raise
        # shutdown grants the node 60 s; the --chips 4 reference run
        # (a second compile) may need longer — wait for the node's exit
        be.join(timeout=timeout)
        err = be.check_bootstrap_errors()
        if err:
            raise RuntimeError(f"node failed during run:\n{err}")
        path = os.path.join(be.executor_dirs[0], RESULT_FILE)
        if not os.path.exists(path):
            raise RuntimeError("node exited without writing its result")
        with open(path) as f:
            result = json.load(f)
    finally:
        be.terminate()
        shutil.rmtree(workdir, ignore_errors=True)
    bad = check(args, result, fed)
    if bad:
        raise RuntimeError(
            "chip smoke failed:\n  " + "\n  ".join(bad) + "\nnode lines:\n"
            + "\n".join(json.dumps(ln) for ln in result["lines"]))
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: the dp=4 mesh run and its one-device "
                         "reference, nothing else")
    ap.add_argument("--seed", type=int, default=0)
    ns = ap.parse_args(argv)

    def _out_of_time(_signum, _frame):
        raise TimeoutError(f"chip smoke exceeded its {BUDGET_S}s budget")

    signal.signal(signal.SIGALRM, _out_of_time)
    signal.alarm(BUDGET_S)
    args = smoke_args(chips=ns.chips, seed=ns.seed)
    if "jax" in sys.modules:
        raise RuntimeError("the driver process imported jax before "
                           "cluster.run: it would hold the chip")
    result = drive(args)
    if "jax" in sys.modules:
        raise RuntimeError("the driver process imported jax")
    for line in result["lines"]:
        print(json.dumps(line), flush=True)
    print(json.dumps({"ok": True, "device": result["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
