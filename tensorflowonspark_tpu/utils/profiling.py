"""Profiling/observability: the TPU-native replacement for the reference's
TensorBoard subprocess (SURVEY.md §5 "Tracing/profiling"; reference:
TFSparkNode.py:282-319 launched `tensorboard` on chief and surfaced the URL).

Here the chief starts the JAX profiler server (connectable from TensorBoard's
profile plugin or `jax.profiler.trace`) and, when the tensorboard binary is
on PATH, optionally a TensorBoard subprocess over the log dir.
"""
import contextlib
import logging
import os
import shutil
import subprocess

logger = logging.getLogger(__name__)

_profiler_started = False


def start_profiler_server(port=9012):
    """Start the JAX profiler gRPC server (idempotent)."""
    global _profiler_started
    if _profiler_started:
        return port
    import jax
    jax.profiler.start_server(port)
    _profiler_started = True
    logger.info("jax profiler server listening on %d", port)
    return port


@contextlib.contextmanager
def trace(log_dir):
    """Capture a profiler trace viewable in TensorBoard/Perfetto."""
    import jax
    with jax.profiler.trace(log_dir):
        yield
    logger.info("profiler trace written to %s", log_dir)


def parse_perfetto_trace(path_or_events, device_only=True, group=True):
    """Aggregate a perfetto trace (`jax.profiler` with
    ``create_perfetto_trace=True``) into per-op device time.

    Returns [(name, total_dur_us, count)] sorted by time desc.  `group`
    collapses versioned XLA op names ("fusion.123" -> "fusion"); set
    False for the per-instance view.  Accepts a path to
    ``perfetto_trace.json.gz``/.json, a trace dict, or an event list.
    """
    import collections
    import gzip
    import json

    if isinstance(path_or_events, str):
        opener = (gzip.open if path_or_events.endswith(".gz") else open)
        with opener(path_or_events, "rt") as f:
            trace = json.load(f)
        events = trace.get("traceEvents", [])
    elif isinstance(path_or_events, dict):
        events = path_or_events.get("traceEvents", [])
    else:
        events = path_or_events

    pids = {ev.get("pid"): ev.get("args", {}).get("name", "")
            for ev in events
            if ev.get("ph") == "M" and ev.get("name") == "process_name"}
    dur = collections.Counter()
    cnt = collections.Counter()
    for ev in events:
        if ev.get("ph") != "X" or "dur" not in ev:
            continue
        track = pids.get(ev.get("pid"), "")
        if device_only and not ("TPU" in track or "GPU" in track
                                or "/device:" in track):
            continue
        name = ev.get("name", "?")
        if group:
            name = name.split(".")[0]
        dur[name] += ev["dur"]
        cnt[name] += 1
    return [(name, d, cnt[name]) for name, d in dur.most_common()]


def op_breakdown(fn, *args, steps=3, log_dir=None, top=20):
    """Run `fn(*args)` under the profiler and return the per-op device-time
    breakdown — the 'where does my step go' question in one call.

    `fn` should be the jitted step (warmed up by this helper); the
    result's scale is `steps` executions.  Returns
    [(op_name, total_us, count)]; also logs the top entries.
    """
    import glob
    import tempfile

    import jax
    import numpy as np

    def _sync(out):
        # host readback of every leaf: a barrier that holds on any runtime
        for leaf in jax.tree_util.tree_leaves(out):
            np.asarray(leaf)

    _sync(fn(*args))                      # warmup/compile
    log_dir = log_dir or tempfile.mkdtemp(prefix="tfos_profile_")
    jax.profiler.start_trace(log_dir, create_perfetto_trace=True)
    out = None
    for _ in range(steps):
        out = fn(*args)
    _sync(out)
    jax.profiler.stop_trace()
    traces = glob.glob(os.path.join(log_dir, "**", "perfetto_trace.json.gz"),
                       recursive=True)
    if not traces:
        raise RuntimeError(f"no perfetto trace produced under {log_dir}")
    rows = parse_perfetto_trace(sorted(traces)[-1])
    for name, us, n in rows[:top]:
        logger.info("%10.3f ms/step x%-5d %s", us / 1e3 / steps, n // steps,
                    name)
    return rows


def start_tensorboard(log_dir, port=None):
    """Launch a TensorBoard subprocess if the binary is available.

    Returns (pid, port, url) or None.  Mirrors the reference's PATH search +
    TENSORBOARD_PORT/ephemeral port behavior (TFSparkNode.py:288-311).
    """
    binary = shutil.which("tensorboard")
    if binary is None:
        logger.warning("tensorboard not found on PATH; skipping")
        return None
    from .. import util
    port = port or int(os.environ.get("TENSORBOARD_PORT", 0)) or \
        util.get_free_port()
    proc = subprocess.Popen(
        [binary, "--logdir", log_dir, "--port", str(port), "--bind_all"],
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    url = f"http://{util.get_ip_address()}:{port}"
    logger.info("tensorboard pid=%d at %s", proc.pid, url)
    return proc.pid, port, url


def stop_tensorboard(pid):
    """Kill the TensorBoard subprocess (reference: TFSparkNode.py:599-605)."""
    import signal
    try:
        os.kill(pid, signal.SIGTERM)
    except ProcessLookupError:
        pass
