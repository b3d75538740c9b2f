"""Test harness configuration.

Forces JAX onto a virtual 8-device CPU platform BEFORE jax is imported
anywhere, so multi-chip sharding (dp/tp/sp meshes, collectives) is exercised
without TPU hardware — the TPU analog of the reference's trick of testing on
a local 2-worker Spark standalone cluster (reference: tests/README.md:10,
tox.ini:29-34).
"""
import os

# Force (not setdefault): the surrounding environment may pin JAX_PLATFORMS
# to the real accelerator; tests must run on the virtual CPU mesh.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")

import faulthandler
import multiprocessing as mp
import signal
import sys
import threading

import pytest

# Import jax here so every test module sees the 8-device CPU platform; the
# config API pins it even where jax was preloaded before this file ran.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


@pytest.fixture(scope="session")
def mp_ctx():
    # 'fork' keeps worker startup cheap on the 1-core CI box; the runtime
    # itself supports spawn (each executor re-execs its bootstrap closure).
    return mp.get_context("fork")


# Test tiering (round-1 VERDICT item 8): the full suite is jit-compile
# bound (>20 min on a 1-core box), so the core-runtime tier must stay
# runnable in one sitting.  Inclusion rule: a file is slow if it measured
# >=20 s standalone (timing sweep recorded 2026-07-31) OR is non-core
# (models/parallelism/optimizer features, peripheral utils) and the fast
# tier would otherwise exceed its budget (~100 s as of round 5 on an
# idle 1-core box) — that covers the sub-20 s
# entries (hybrid_mesh 11 s, optim8bit 14 s, summary 9 s).  Everything
# else forms the fast tier:
#     pytest -m "not slow"        (also: scripts/run_tests.sh --fast)
SLOW_FILES = {
    "test_aot.py",              # 70 s — native lib + mock PJRT round trips
    "test_bert.py",             # 45 s
    "test_chaos.py",            # ~60 s — kill/recover soak over real engines
    "test_cluster.py",          # 86 s — multi-process integration
    "test_convert.py",          # 31 s — HF checkpoint parity
    "test_decode.py",           # 62 s — KV-cache generation compiles
    "test_deeplab.py",          # 53 s — dilated-conv compiles
    "test_elastic.py",          # ~80 s — SIGKILL + relaunch integration (LocalBackend + minispark paths)
    "test_examples.py",         # >10 min — example subprocesses
    "test_hybrid_mesh.py",      # 11 s — multi-slice mesh compiles
    "test_kv_int8.py",          # ~60 s — quantized-cache engines compile
    "test_lora.py",             # 25 s
    "test_lora_serving.py",     # ~60 s — multi-adapter slot engines
    "test_optim8bit.py",        # 14 s (round 5 grew it: layout parity)
    "test_paged.py",            # 55 s — paged-kv batcher compiles
    "test_metrics_vit.py",      # 82 s
    "test_minispark.py",        # 60 s — spawn-started executor pools
    "test_models.py",           # 88 s
    "test_ops.py",              # 47 s — pallas kernels (interpret mode)
    "test_pipeline.py",         # 45 s
    "test_pipelined_lm.py",     # 25 s
    "test_preemption.py",       # ~90 s — mixed-priority load over a real
    # Gateway + preemption-controller engines (decode compiles, sleeps
    # on queueing-delay windows)
    "test_quantize.py",         # 9 s — non-core (serving-width weights);
    # moved round 5 to keep the fast tier under its 90 s budget as the
    # round's layout/sampling tests accreted onto fast files
    "test_ring_attention.py",   # 31 s
    "test_sampling_controls.py",  # ~60 s — slot engines + decode compiles
    "test_serve.py",            # 68 s — HTTP servers + decode compiles
    "test_slots.py",            # 31 s — slot-decode parity compiles
    # (both grew past the fast budget with the round-4 continuous-
    # batching work; the fast tier keeps the cluster data-plane smoke)
    "test_spark_integration.py",  # 110 s — end-to-end Spark surface
    "test_spark_real.py",       # same bodies over real pyspark (skips
    # in seconds when pyspark is absent, but runs minutes when present)
    "test_streaming.py",        # 41 s
    "test_summary.py",          # 9 s — non-core (tfevents writer), keeps
    # the tier under its 90 s budget as fast files accrete
    "test_transformer.py",      # 47 s
    "test_ulysses.py",          # 35 s
    "test_xent.py",             # 20 s
}


# A hung test must fail BY NAME, not sit until the driver's whole-run
# timeout cuts the suite (rc 124 costs a PR its check).  pytest-timeout
# is not installed.  Two limits on every phase (setup / call / teardown):
#
# * soft — SIGALRM in the worker's main thread.  The handler raises
#   inside whatever interruptible wait the test is stuck in (lock, join,
#   queue.get, socket); that phase fails with this message and the worker
#   lives on.
# * hard — faulthandler's watchdog, a C thread.  A Python signal handler
#   cannot run while the main thread sits in native code (an XLA
#   rendezvous, a compile), so past the soft limit the watchdog writes
#   every thread's stack to the real stderr and _exits the worker; xdist
#   fails the test as the one that took its worker down
#   (`pytest_handlecrashitem` below lets the run go on from there).
#
# Five hard limits and a 220 s run still end inside the driver's 1470 s.
SOFT_LIMIT_S = 200
HARD_LIMIT_S = 240

_real_stderr = None


def pytest_configure(config):
    # capture is suspended while plugins configure: fd 2 is the real one
    # here, and a test's own fd 2 is a capture file
    global _real_stderr
    _real_stderr = os.dup(2)


def _limited(item):
    faulthandler.dump_traceback_later(HARD_LIMIT_S, exit=True,
                                      file=_real_stderr or sys.__stderr__)
    soft = (hasattr(signal, "SIGALRM")
            and threading.current_thread() is threading.main_thread())

    def _on_alarm(_signum, _frame):
        raise TimeoutError(
            f"{item.nodeid} exceeded the {SOFT_LIMIT_S}s per-test limit")

    if soft:
        previous = signal.signal(signal.SIGALRM, _on_alarm)
        signal.alarm(SOFT_LIMIT_S)
    try:
        yield
    finally:
        if soft:
            signal.alarm(0)
            signal.signal(signal.SIGALRM, previous)
        faulthandler.cancel_dump_traceback_later()


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_setup(item):
    yield from _limited(item)


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    yield from _limited(item)


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_teardown(item):
    yield from _limited(item)


@pytest.hookimpl(optionalhook=True)
def pytest_handlecrashitem(crashitem, report, sched):
    """Let the run go on after a worker died (an abort in native code, the
    hard limit above, the OOM killer).

    xdist 3.8's loadfile scheduler puts a dead worker's WHOLE workload
    back in the queue: the files it had already finished, and the crashed
    test itself, still pending.  The replacement worker is handed a
    finished file first, has nothing to run, reports nothing, and is
    never scheduled again — the run sits silent until the driver's limit.
    (That is what took this PR's first submission to rc 124, and by its
    symptoms PR 22's: an XLA CPU-collective abort 150 s in, then 22
    silent minutes.)  And a
    crashed test that is handed out again takes the next worker down too.
    xdist has already failed the crashed test by name, so here it counts
    as run, and finished files leave the queue."""
    queue = getattr(sched, "workqueue", None)
    if queue is None:               # not the loadscope/loadfile scheduler
        return
    for scope, unit in list(queue.items()):
        if crashitem in unit:
            unit[crashitem] = True
        if all(unit.values()):
            del queue[scope]


def pytest_collection_modifyitems(config, items):
    for item in items:
        if os.path.basename(str(item.fspath)) in SLOW_FILES:
            item.add_marker(pytest.mark.slow)
