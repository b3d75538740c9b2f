"""One run of one cell: a JAX-free driver and the node that owns the chips.

`drive(spec)` (the run's own process, which never imports JAX) starts a
one-executor cluster through the program's normal entry points
(`cluster.run(LocalBackend(1), node_main, ..., InputMode.SPARK)`,
`c.train(partitions)`, `c.shutdown()`) and feeds it seeded records.
`node_main` (the node process) builds the step with
`parallel/train.make_train_step`, pulls batches with
`DataFeed.next_numpy_batch` through `feed.device_prefetch`, and:

1. set-up: compiles, takes the first `check_steps` steps from the feed and
   keeps what the comparison needs of them, warms up;
2. window: `--seconds` of steps dispatched without a barrier, the loss of
   step i-1 read back while step i runs; ends on `block_until_ready`;
3. `--trace 1` only: a few more steps under the profiler;
4. after the window: frees the program's state and follows the same first
   steps with the family's plain float32 reference.

What belongs to a family, a configuration, a traffic mix or a metric is in
a file of its own (`families/`, `configs/`, `traffic/`, `workloads/`,
`metrics/`); this file knows none of them by name.
"""
import argparse
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if HERE not in sys.path:
    sys.path.insert(0, HERE)
import traffic as traffic_mod  # noqa: E402  (numpy only)
import weights  # noqa: E402  (imports JAX inside its functions)

RESULT_FILE = "benchmark_node.json"
CLOSED_FILE = "benchmark_window_closed"
SPANS = ("bench.next_batch", "bench.dispatch", "bench.readback")


def load_module(kind, name):
    """`benchmark/<kind>/<name>.py`, found by name."""
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_spec(workload, seed, seconds, trace, platform="tpu"):
    """Everything one run needs, as plain data (it is shipped to the node)."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next((w for w in bench["workloads"] if w["name"] == workload),
                None)
    if cell is None:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
    wl = traffic_mod.load("workloads", workload)
    cfg_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, cfg_entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f)["devices"]
    return make_spec(
        workload, seed, seconds, trace, cell=wl, config=config,
        traffic=traffic_mod.load("traffic", cell["traffic"]),
        chips=cell["chips"], peaks=peaks, platform=platform,
        end_to_end=[m for m in bench["end_to_end"]
                    if workload in m.get("workloads", [workload])],
        per_layer=[m for m in bench["per_layer"]
                   if workload in m.get("workloads", [workload])])


def make_spec(workload, seed, seconds, trace, cell, config, traffic, chips,
              peaks, platform="tpu", end_to_end=(), per_layer=(), fault=None,
              keep_trace=None):
    """The run's description.  `fault` and `keep_trace` are for the tests
    and the builder's scripts under `benchmark/tests/`: no command-line
    switch sets them."""
    return argparse.Namespace(
        workload=workload, seed=int(seed), seconds=float(seconds),
        trace=int(trace), platform=platform, chips=chips, cell=cell,
        config=config, traffic=traffic, peaks=peaks,
        end_to_end=list(end_to_end), per_layer=list(per_layer),
        t_start=time.time(), fault=fault, keep_trace=keep_trace)


# --------------------------------------------------------------- node ----

def first_grad_norms(opt_cfg, opt_state):
    """`{path: norm}` of the first gradient as the optimizer got it, from
    its state after one step: Adam's first moment is (1-b1) g, SGD's
    momentum trace is g."""
    name = opt_cfg["name"]
    if name in ("adamw_fused", "adamw", "adam"):
        mu = opt_state.mu if hasattr(opt_state, "mu") else opt_state[0].mu
        k = 1.0 / (1.0 - opt_cfg.get("b1", 0.9))
        return {p: v * k for p, v in _leaf_norms(mu).items()}
    if name == "sgd":
        return _leaf_norms(opt_state[0].trace)
    raise ValueError(f"no first-gradient rule for optimizer {name!r}")


def first_grads(opt_cfg, opt_state):
    """`{path: array}` of the first gradient itself, on the host, where the
    optimizer's state after one step holds it exactly (SGD's momentum
    trace)."""
    import numpy as np

    if opt_cfg["name"] != "sgd":
        raise ValueError("the first gradient is kept for sgd only, not "
                         f"{opt_cfg['name']!r}")
    return {k: np.asarray(v) for k, v in
            weights.flatten(opt_state[0].trace).items()}


def first_steps(compiled, state, next_batch, rng, cfg, traffic):
    """Drive the step object through its first `check_steps` steps and keep
    what the comparison needs of them.  Returns the new state and
    `{"losses", "grad_norms"[, "first_grads"]}`; the parameters' change is
    taken by the caller, who knows the weights' seed."""
    opt_cfg = cfg["program"]["optimizer"]
    prog = {"losses": []}
    for i in range(traffic["check_steps"]):
        state, metrics = compiled(state, next_batch(), rng)
        prog["losses"].append(float(metrics["loss"]))
        if i == 0:
            prog["grad_norms"] = first_grad_norms(opt_cfg, state.opt_state)
            if traffic.get("keep_first_grads"):
                prog["first_grads"] = first_grads(opt_cfg, state.opt_state)
    return state, prog


def _leaf_norms(tree, other=None):
    """`{path: norm}` of a tree of dicts (of `tree - other`), on the host."""
    import jax
    import jax.numpy as jnp

    def f(a, b):
        return jax.tree_util.tree_map(
            lambda x, y: jnp.sqrt(jnp.sum(jnp.square(
                x.astype(jnp.float32) - y))), a, b)

    if other is None:
        other = jax.tree_util.tree_map(lambda x: jnp.float32(0), tree)
    return {k: float(v) for k, v in
            weights.flatten(jax.jit(f)(tree, other)).items()}


def grad_diff(got, want, want_norms):
    """`{path: |got - want| / norm}` by leaf: the norm of the first
    gradient's DIFFERENCE (random rounding moves it in first order, where a
    gap of norms sees it in second), over the reference's norm of that leaf
    or of the median leaf, whichever is larger."""
    import statistics

    import numpy as np

    med = statistics.median(want_norms.values())
    return {k: float(np.linalg.norm(
        np.asarray(got[k], np.float32).ravel()
        - np.asarray(want[k], np.float32).ravel())) / max(want_norms[k], med)
        for k in want}


def compare(prog, ref, limits):
    """The numbers that decide `correct`, each beside its limit (a number
    with no limit in the cell's file is worked out and printed, not judged).

    - `feed_rows_wrong`: rows of the first batches that are not the rows the
      seed makes (exact);
    - `loss_gap`: widest |program - reference| / reference over the steps;
      `loss1_gap`: the same of the first step alone;
    - `grad_norm_gap`, `update_norm_gap`: by the worst leaf, the gap
      between the program's norm and the reference's over the reference's
      norm of that leaf or of the median leaf, whichever is larger;
      `grad_norm_gap_median`, `update_norm_gap_median`: the median leaf's.
      Leaves whose reference gradient is under a thousandth of the median
      leaf's (a key's bias under softmax) move by round-off alone under Adam
      and are left out of the update's comparison;
    - `grad_diff`, `grad_diff_median`, `grad_diff_least`: where the cell
      keeps the first gradient itself (`keep_first_grads` of its traffic
      file): the norm of its difference from the reference's, by the worst,
      the median and the least leaf.  The least leaf is the one nearest the
      loss (the classifier's bias): no rectifier's mask lies between it and
      the forward pass, so it reads the forward pass's precision in first
      order, where the leaves behind rectifiers read its square root.
    """
    import statistics

    def gaps_of(key, skip=()):
        r, p = ref[key], prog[key]
        if set(r) != set(p):
            return {"leaf sets differ": float("inf")}
        med = statistics.median(r.values())
        return {k: abs(p[k] - r[k]) / max(r[k], med)
                for k in r if k not in skip}

    g_med = statistics.median(ref["grad_norms"].values())
    idle = {k for k, v in ref["grad_norms"].items() if v < 1e-3 * g_med}
    rel = [abs(a - b) / abs(b) for a, b in zip(prog["losses"], ref["losses"])]
    if len(prog["losses"]) != len(ref["losses"]) or not all(
            math.isfinite(x) for x in prog["losses"]):
        rel = [float("inf")]
    grad, upd = gaps_of("grad_norms"), gaps_of("update_norms", skip=idle)
    grad_leaf, upd_leaf = max(grad, key=grad.get), max(upd, key=upd.get)
    values = {"feed_rows_wrong": prog["feed_rows_wrong"],
              "loss_gap": max(rel), "loss1_gap": rel[0],
              "grad_norm_gap": grad[grad_leaf],
              "grad_norm_gap_median": statistics.median(grad.values()),
              "update_norm_gap": upd[upd_leaf],
              "update_norm_gap_median": statistics.median(upd.values())}
    if "first_grads" in prog and "first_grads" in ref:
        diff = grad_diff(prog["first_grads"], ref["first_grads"],
                         ref["grad_norms"])
        values["grad_diff"] = max(diff.values())
        values["grad_diff_median"] = statistics.median(diff.values())
        values["grad_diff_least"] = min(diff.values())
    numbers = {k: {"value": values[k], "limit": limits[k]} for k in limits}
    correct = all(v["value"] <= v["limit"] for v in numbers.values())
    return correct, numbers, {"grad_norm_leaf": grad_leaf,
                              "update_norm_leaf": upd_leaf,
                              "leaves_left_out": sorted(idle),
                              "all": values}


def build_program(family, cfg, tr, mesh_spec, devs):
    """The step object a window drives, as the node builds it: mesh,
    `create_train_state` over weights made from a seed, the donated step of
    `make_train_step` compiled for the traffic's batch.  (`tests/control.py`
    builds the same object to read many seeds in one process.)"""
    import contextlib

    import jax
    import numpy as np

    from tensorflowonspark_tpu.parallel import mesh as mesh_mod
    from tensorflowonspark_tpu.parallel import train as train_mod

    mesh = sharding = repl = None
    if mesh_spec:
        mesh = mesh_mod.build_mesh(mesh_mod.MeshSpec(**mesh_spec),
                                   devices=devs)
        sharding = mesh_mod.batch_sharding(mesh)
        repl = mesh_mod.replicated_sharding(mesh)
    shapes = family.param_shapes(cfg)
    loss_fn, opt = family.build(cfg)
    step = train_mod.make_train_step(loss_fn, opt, mesh=mesh, donate=True)

    def fresh_state(seed):
        params = weights.nest(weights.make(seed, shapes, repl))
        return train_mod.create_train_state(params, opt, mesh=mesh)

    def compile_step(state):
        b = tr["batch"]
        zeros = tuple(np.zeros((b,) + shape, dtype)
                      for dtype, shape, _, _ in traffic_mod.fields(tr, cfg))
        batch0 = jax.device_put(zeros[0] if len(zeros) == 1 else zeros,
                                sharding)
        # the flash dispatch shard_maps the kernel over the AMBIENT mesh
        with jax.set_mesh(mesh) if mesh is not None else \
                contextlib.nullcontext():
            return step.lower(state, batch0, rng).compile()

    rng = jax.random.key(1)
    return argparse.Namespace(
        mesh=mesh, sharding=sharding, repl=repl, shapes=shapes,
        loss_fn=loss_fn, opt=opt, rng=rng, fresh_state=fresh_state,
        compile=compile_step)


def node_main(args, ctx):
    """The training node.  Owns the chips; everything JAX happens here."""
    t_entered = time.time()
    import numpy as np

    from tensorflowonspark_tpu import feed as feed_mod
    from tensorflowonspark_tpu import util

    cache_dir = util.enable_compile_cache()
    import jax

    # every program of a run goes to the persistent cache, small ones too
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    events = {"hits": 0, "misses": 0, "compiles": 0}

    def _on_event(name, **_kw):
        if name == "/jax/compilation_cache/cache_hits":
            events["hits"] += 1
        elif name == "/jax/compilation_cache/cache_misses":
            events["misses"] += 1

    def _on_duration(name, _secs, **_kw):
        if name == "/jax/core/compile/backend_compile_duration":
            events["compiles"] += 1

    jax.monitoring.register_event_listener(_on_event)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if device["platform"] != args.platform:
        raise RuntimeError(f"node sees platform {device['platform']!r}, "
                           f"not {args.platform!r}: {devs}")
    if len(devs) < args.chips:
        raise RuntimeError(f"the cell asks for {args.chips} chips, the "
                           f"node sees {devs}")
    if args.platform == "tpu" and device["kind"] not in args.peaks:
        raise RuntimeError(f"device kind {device['kind']!r} is not in "
                           "benchmark/peaks.json")
    devs = devs[:args.chips]

    from tensorflowonspark_tpu.parallel import train as train_mod

    cfg, tr = args.config, args.traffic
    family = load_module("families", cfg["family"])
    fault = args.fault            # tests only: the timed path broken

    built = build_program(family, cfg, tr, args.cell.get("mesh"), devs)
    mesh, sharding, repl = built.mesh, built.sharding, built.repl
    shapes, loss_fn, opt = built.shapes, built.loss_fn, built.opt
    t0 = time.perf_counter()
    state = built.fresh_state(args.seed)
    jax.block_until_ready(state)
    init_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    compiled = built.compile(state)
    step_compile_s = time.perf_counter() - t0
    b = tr["batch"]
    rng = built.rng
    text = compiled.as_text()
    mem = compiled.memory_analysis()
    program_bytes = int(
        mem.argument_size_in_bytes + mem.output_size_in_bytes
        + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    info = {
        "n_params": int(sum(math.prod(s) for s, _ in shapes.values())),
        "init_s": init_s, "step_compile_s": step_compile_s,
        "tpu_custom_calls": text.count("tpu_custom_call"),
        "all_reduces": text.count("all-reduce"),
        "step_program_bytes": program_bytes,
        "step_argument_bytes": int(mem.argument_size_in_bytes),
        "step_temp_bytes": int(mem.temp_size_in_bytes),
        "compile_cache_dir": cache_dir,
    }
    del text
    if args.platform == "tpu" and cfg["program"].get("expect_kernels") \
            and info["tpu_custom_calls"] <= 0:
        raise RuntimeError("the compiled step holds no tpu_custom_call: the "
                           "Pallas kernels fell back to dense or interpret")
    if mesh is not None and info["all_reduces"] <= 0:
        raise RuntimeError("no all-reduce in the compiled mesh step")

    if fault == "state_unchanged":
        real = compiled

        def compiled(state, batch, rng):          # noqa: F811
            keep = jax.tree_util.tree_map(lambda x: x + 0, state)
            return keep, real(state, batch, rng)[1]
    elif fault in ("half_batch", "no_exchange"):
        # half of the batch left out, the mean taken over the rest; with
        # the exchange between chips left out a chip sees its own rows only
        rows = b // 2 if fault == "half_batch" else b // args.chips
        compiled = train_mod.make_train_step(
            lambda p, batch, rng: loss_fn(p, jax.tree_util.tree_map(
                lambda x: x[:rows], batch), rng),
            opt, mesh=mesh, donate=True)

    # ---- the feed --------------------------------------------------------
    df = ctx.get_data_feed(train_mode=True)
    seen = {"records": 0, "wait_s": 0.0}
    first = []
    n_check = tr["check_steps"]

    def host_batches():
        while not df.should_stop():
            with jax.profiler.TraceAnnotation(SPANS[0]):
                t0 = time.perf_counter()
                got = df.next_numpy_batch(b, timeout=300)
                if got is None or len(got) == 0:
                    continue            # end of feed: not a wait for data
                seen["wait_s"] += time.perf_counter() - t0
            n = len(got[0]) if isinstance(got, tuple) else len(got)
            if n != b:
                raise RuntimeError(f"ragged batch of {n} records")
            seen["records"] += n
            if len(first) < n_check:
                if fault == "feed_altered" and not first:
                    jax.tree_util.tree_leaves(got)[0].flat[0] ^= 1
                first.append(jax.tree_util.tree_map(np.copy, got))
            yield got

    batches = feed_mod.device_prefetch(host_batches(), sharding=sharding,
                                       depth=2)
    wait = {"s": 0.0}

    def next_batch():
        """The consuming loop's wait for the next device batch."""
        t0 = time.perf_counter()
        batch = next(batches)
        wait["s"] += time.perf_counter() - t0
        return batch

    # ---- set-up: the first steps, kept for the comparison ---------------
    shard_devices = set()

    def checked_batch():
        batch = next_batch()
        leaf = jax.tree_util.tree_leaves(batch)[0]
        shard_devices.update(str(s.device) for s in leaf.addressable_shards)
        return batch

    state, prog = first_steps(compiled, state, checked_batch, rng, cfg, tr)
    shard_devices = sorted(shard_devices)
    start = weights.nest(weights.make(args.seed, shapes, repl))
    prog["update_norms"] = _leaf_norms(state.params, start)
    del start
    if mesh is not None and len(shard_devices) != args.chips:
        raise RuntimeError(f"batch shards sit on {shard_devices}, not "
                           f"{args.chips} devices")

    # ---- warm-up, then the window ---------------------------------------
    def one_step(state, prev):
        """Dispatch a step; read the PREVIOUS step's loss back while it
        runs.  Returns the new state, this step's metrics, and the time the
        previous step was seen to be complete."""
        batch = next_batch()
        with jax.profiler.TraceAnnotation(SPANS[1]):
            state, metrics = compiled(state, batch, rng)
        done = None
        if prev is not None:
            with jax.profiler.TraceAnnotation(SPANS[2]):
                np.asarray(prev["loss"])
            done = time.perf_counter()
        return state, metrics, done

    prev = None
    for _ in range(tr["warm_steps"]):
        state, prev, _ = one_step(state, prev)
    jax.block_until_ready((state, prev))
    prev = None
    cache_before = dict(events)
    wait["s"], seen["wait_s"] = 0.0, 0.0
    records0 = seen["records"]
    t_window_wall = time.time()
    t_start = time.perf_counter()
    done_at, steps = [], 0
    while time.perf_counter() - t_start < args.seconds:
        state, prev, done = one_step(state, prev)
        steps += 1
        if done is not None:
            done_at.append(done)
    jax.block_until_ready((state, prev))
    t_end = time.perf_counter()
    done_at.append(t_end)
    window = {
        "seconds": t_end - t_start, "steps": steps,
        "records": steps * b, "records_pulled": seen["records"] - records0,
        "wait_s": wait["s"], "feed_block_s": seen["wait_s"],
        "intervals_ms": [(y - x) * 1e3 for x, y in
                         zip([t_start] + done_at[:-1], done_at)],
        "compiles_in_window": events["compiles"] - cache_before["compiles"],
        "last_loss": float(prev["loss"]),
    }

    # ---- the traced steps (their own few steps, after the window) -------
    trace_dir = None
    if args.trace:
        trace_dir = os.path.join(ctx.working_dir, "trace")
        prev = None
        # of the host, only what is at the level of the benchmark's own
        # spans: at the default level the runtime logs every chunk it
        # transposes for a host-to-device copy (8.5 million events in ten
        # ResNet steps) and `stop_trace` takes minutes
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        t0 = time.perf_counter()
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        for _ in range(tr["trace_steps"]):
            state, prev, _ = one_step(state, prev)
        jax.block_until_ready((state, prev))
        jax.profiler.stop_trace()
        window["trace_s"] = time.perf_counter() - t0

    stats = [d.memory_stats() or {} for d in devs]
    live_peak = max((s.get("peak_bytes_in_use", 0) for s in stats), default=0)
    memory = {"live_peak_bytes": int(live_peak),
              "step_program_bytes": program_bytes,
              "bytes_limit": int(stats[0].get("bytes_limit", 0)),
              "memory_peak_bytes": int(max(live_peak, program_bytes))}

    out = {"device": device, "info": info, "window": window,
           "memory": memory, "trace_dir": trace_dir,
           "cache": dict(events), "shard_devices": shard_devices,
           "t_entered": t_entered, "t_window": t_window_wall}

    # ---- after the window: free the program, follow the reference -------
    # tell the driver to offer no further partition, then drain the one
    # in flight
    with open(os.path.join(ctx.working_dir, CLOSED_FILE), "w"):
        pass
    df.terminate()
    batches.close()
    state = prev = metrics = batch = compiled = step = None
    jax.clear_caches()
    t0 = time.perf_counter()
    want = traffic_mod.first_batches(tr, cfg, args.seed, n_check)
    prog["feed_rows_wrong"] = int(sum(
        np.sum(np.any((g != w).reshape(len(w), -1), axis=1))
        for got, exp in zip(first, want)
        for g, w in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(exp))))
    ref = family.reference(
        cfg, lambda: weights.make(args.seed, shapes), want,
        devices=devs, row_block=tr["reference_row_block"],
        keep_grads=bool(tr.get("keep_first_grads")))
    correct, numbers, detail = compare(prog, ref, args.cell["limits"])
    out.update(correct=correct, numbers=numbers, detail=detail,
               program={"losses": prog["losses"]},
               reference={"losses": ref["losses"]},
               reference_s=time.perf_counter() - t0)
    if trace_dir:
        import tracered

        t0 = time.perf_counter()
        out["trace"] = tracered.reduce_dir(trace_dir, SPANS)
        out["trace_read_s"] = time.perf_counter() - t0
        if args.keep_trace:
            shutil.copytree(trace_dir, args.keep_trace, dirs_exist_ok=True)
    tmp = os.path.join(ctx.working_dir, RESULT_FILE + ".tmp")
    with open(tmp, "w") as f:
        json.dump(out, f)
    os.rename(tmp, os.path.join(ctx.working_dir, RESULT_FILE))


# ------------------------------------------------------------- driver ----

def drive(args, start_method="fork", timeout=900):
    """Run one cell through the cluster API; returns the node's result with
    the driver's own clock readings added.  Raises when the node failed."""
    from tensorflowonspark_tpu import backend, cluster

    # single host: loopback rendezvous (a sealed machine has no route)
    os.environ.setdefault("TFOS_TPU_SERVER_HOST", "127.0.0.1")
    workdir = tempfile.mkdtemp(prefix="benchmark-run-")
    be = backend.LocalBackend(1, workdir=workdir, start_method=start_method)
    try:
        t_run = time.time()
        # num_chips=0: the one node process takes the whole host's chips
        c = cluster.run(be, node_main, args, num_executors=1,
                        input_mode=cluster.InputMode.SPARK, num_chips=0,
                        reservation_timeout=120)
        try:
            parts = traffic_mod.partitions(args.traffic, args.config,
                                           args.seed, args.seconds)
            # one partition a call, as a streaming driver feeds, and none
            # once the node has closed its window.  A partition offered
            # after `df.terminate()` makes its feeder send STOP, which shuts
            # the reservation server down: every later partition then waits
            # 60 s on it, and so does the node's BYE when it exits.
            closed = os.path.join(be.executor_dirs[0], CLOSED_FILE)
            path = os.path.join(be.executor_dirs[0], RESULT_FILE)
            fed = 0
            for part in parts:
                if os.path.exists(closed) or c.stop_requested():
                    break
                c.train([part], feed_timeout=timeout)
                fed += len(part)
            # the node follows the reference now; the reservation server has
            # to outlive it (the node says BYE to it), so shut down after
            deadline = time.time() + timeout
            while not os.path.exists(path) and time.time() < deadline:
                err = be.check_bootstrap_errors()
                if err:
                    raise RuntimeError(f"node failed during run:\n{err}")
                time.sleep(0.2)
            t_result = time.time()
            c.shutdown(timeout=timeout)
        except BaseException:
            c.abort()
            raise
        be.join(timeout=timeout)
        err = be.check_bootstrap_errors()
        if err:
            raise RuntimeError(f"node failed during run:\n{err}")
        if not os.path.exists(path):
            raise RuntimeError("node exited without writing its result")
        with open(path) as f:
            result = json.load(f)
        result["fed_records"] = fed
        result["exit_s"] = time.time() - t_result
        result["launch_s"] = result["t_entered"] - t_run
        result["setup_s"] = result["t_window"] - args.t_start
        return result
    finally:
        be.terminate()
        shutil.rmtree(workdir, ignore_errors=True)
