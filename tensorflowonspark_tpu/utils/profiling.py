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
