"""Host/environment utilities (maps reference util.py:1-94).

Pure-Python helpers with no JAX dependency so the coordination layer can be
imported and unit-tested without paying accelerator-runtime startup.
"""
import errno
import logging
import os
import random
import re
import socket
import time

logger = logging.getLogger(__name__)

EXECUTOR_ID_FILE = "executor_id"


class RetryPolicy:
    """ONE retry/backoff discipline for every network loop in the package.

    Three loops grew three divergent retry shapes (reservation.Client's
    capped-exponential connect retries, the fleet gateway's hedged
    predict retry, kvtransfer.MigrationEngine's deadline-bounded attempt
    loop); this class is the shared schedule they all thread their
    existing knobs through.  ``attempts`` is the TOTAL number of tries
    (not extra retries), ``delay(i)`` the capped exponential backoff
    before try ``i+1`` — base, 2*base, 4*base, ... never exceeding
    ``cap_delay`` — plus up to ``jitter``-fraction uniform noise so a
    fleet of clients retrying the same dead endpoint doesn't
    synchronize.  ``deadline_s`` bounds the loop's total wall time
    (sleeps are clipped to it, and no try starts past it).

    ``sleeps()`` is the iteration helper::

        for attempt in policy.sleeps():
            try:
                return dial()
            except OSError as e:
                last = e
        raise ConnectionError(last)

    It yields attempt indices and sleeps the backoff BETWEEN tries
    (never after the last — the no-pointless-post-final-sleep rule every
    hand-rolled loop had to re-derive).
    """

    def __init__(self, attempts=3, base_delay=2.0, cap_delay=15.0,
                 jitter=0.0, deadline_s=None):
        if attempts < 1:
            raise ValueError(f"attempts={attempts} must be >= 1")
        if base_delay < 0 or cap_delay < 0:
            raise ValueError("delays must be >= 0")
        if not 0.0 <= jitter <= 1.0:
            raise ValueError(f"jitter={jitter} must be in [0, 1]")
        self.attempts = int(attempts)
        self.base_delay = float(base_delay)
        self.cap_delay = float(cap_delay)
        self.jitter = float(jitter)
        self.deadline_s = None if deadline_s is None else float(deadline_s)

    def delay(self, attempt):
        """Backoff before retry `attempt` (0-based: the sleep after the
        first failed try is ``delay(0)``)."""
        d = min(self.cap_delay, self.base_delay * (2.0 ** attempt))
        if self.jitter:
            d += random.uniform(0.0, self.jitter * d)
        return d

    def sleeps(self, stop=None):
        """Yield attempt indices ``0..attempts-1``, sleeping the backoff
        between them and ending early at the deadline.  ``stop`` is an
        optional ``threading.Event``-like object: the inter-try sleep
        waits on it instead of ``time.sleep`` so a shutdown can end the
        loop mid-backoff."""
        start = time.monotonic()
        for attempt in range(self.attempts):
            if (attempt and self.deadline_s is not None
                    and time.monotonic() - start >= self.deadline_s):
                return
            yield attempt
            if attempt < self.attempts - 1:
                d = self.delay(attempt)
                if self.deadline_s is not None:
                    d = min(d, max(0.0, self.deadline_s
                                   - (time.monotonic() - start)))
                if stop is not None:
                    if stop.wait(d):
                        return
                elif d > 0:
                    time.sleep(d)


def get_ip_address():
    """Best-effort routable IP of this host.

    Uses the UDP-connect trick (reference: util.py:52-65): no packets are
    sent; the kernel just picks the interface that would route to the target.
    Falls back to loopback when the host is offline.
    """
    s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    try:
        s.connect(("8.8.8.8", 80))
        return s.getsockname()[0]
    except OSError:
        return "127.0.0.1"
    finally:
        s.close()


def get_free_port(host=""):
    """Reserve an ephemeral TCP port and return it (racy but adequate)."""
    s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    try:
        s.bind((host, 0))
        return s.getsockname()[1]
    finally:
        s.close()


def parse_port_spec(spec):
    """Parse a port env var: '8080' -> [8080]; '8000-8010' -> [8000..8010].

    Mirrors the reference's TFOS_SERVER_PORT range support
    (reference: reservation.py:190-206).
    """
    spec = str(spec).strip()
    if "-" in spec:
        lo, hi = spec.split("-", 1)
        lo, hi = int(lo), int(hi)
        if hi < lo:
            raise ValueError(f"invalid port range: {spec}")
        return list(range(lo, hi + 1))
    return [int(spec)]


def bind_socket(host, ports=None):
    """Bind a listening TCP socket on `host`.

    `ports` is None (ephemeral) or a list of candidate ports tried in order
    (reference: reservation.py:190-206).  Returns the bound, listening socket.
    """
    sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    try:
        sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        if not ports:
            sock.bind((host, 0))
        else:
            last_err = None
            for port in ports:
                try:
                    sock.bind((host, port))
                    last_err = None
                    break
                except OSError as e:
                    if e.errno != errno.EADDRINUSE:
                        raise
                    last_err = e
            if last_err is not None:
                raise last_err
        sock.listen(64)
    except BaseException:
        sock.close()
        raise
    return sock


def find_in_path(path, file_name):
    """Find `file_name` in a ':'-separated search path (reference: util.py:68-76)."""
    for p in path.split(os.pathsep):
        candidate = os.path.join(p, file_name)
        if os.path.exists(candidate) and os.path.isfile(candidate):
            return candidate
    return False


def write_executor_id(num, cwd=None):
    """Persist this executor's id in a CWD file.

    Later feeder tasks scheduled on the same executor read it to locate the
    node's queue manager (reference: util.py:77-82).
    """
    path = os.path.join(cwd or os.getcwd(), EXECUTOR_ID_FILE)
    with open(path, "w") as f:
        f.write(str(num))


def read_executor_id(cwd=None):
    """Read the executor id written by `write_executor_id` (reference: util.py:85-94)."""
    path = os.path.join(cwd or os.getcwd(), EXECUTOR_ID_FILE)
    with open(path) as f:
        return int(f.read())


def single_node_env(num_cpu_devices=None):
    """Configure the environment for a single-node JAX run.

    Maps reference util.py:21-49 (which expanded the Hadoop CLASSPATH and set
    CUDA_VISIBLE_DEVICES).  On the TPU build the analog is: make sure child
    processes inherit a sane JAX platform selection, and optionally force a
    virtual multi-device CPU platform for testing.
    """
    if num_cpu_devices:
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        token = f"--xla_force_host_platform_device_count={num_cpu_devices}"
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (flags + " " + token).strip()
    # Keep TF (used only for TFRecord interop tests) off the accelerator.
    os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "2")


def pin_platform(platform):
    """Pin THIS process (and everything forked from it) to a JAX platform.

    Env alone is not enough: the surrounding environment may both preload
    jax and pin JAX_PLATFORMS to the real accelerator, so the config API
    must win; the env var is still set so spawn-started children (which do
    not inherit config state) agree. Local multi-process demos must pin
    "cpu" — several processes sharing one real TPU deadlock on the device.
    """
    os.environ["JAX_PLATFORMS"] = platform
    import jax

    jax.config.update("jax_platforms", platform)


def enable_compile_cache():
    """Turn on JAX's persistent compilation cache; returns its directory.

    ONE rule, for every node bootstrap (`chip_smoke.py`, the cells of
    `benchmark/`): where ``JAX_COMPILATION_CACHE_DIR`` is set,
    JAX already honours it and no directory is set in code; where it is
    not, the cache lives in ``.jax_cache/`` at the root of this checkout
    (git-ignored).  The path is part of the cache key, so it is fixed and
    absolute — never under a tempdir, a pid or the clock — and never
    relative: executors chdir into per-run scratch directories.  Call
    before the first compile of the process.

    In both cases the names in the program are part of what the cache keys
    on (``jax_compilation_cache_include_metadata_in_key``).  JAX's default
    strips every location before it hashes a module, and a
    `jax.named_scope` lives only in locations: two programs that differ in
    a scope's name alone then share one entry, and the second is handed the
    first's executable with the first's ``op_name``s, which are what the
    device trace is split by (`benchmark/tracered.region_of`).  Locations
    hold file names and line numbers too, so an edit that moves a traced
    line compiles once more; the checkout's own root is cut from the file
    names (``jax_hlo_source_file_canonicalization_regex``), so the same
    tree at another path still hits.

    It also makes the process's set-up visible from inside: every
    duration of a millisecond or more that JAX reports under
    ``/jax/core/compile/`` (tracing to a jaxpr, lowering to MLIR, the
    backend's compile) and ``/jax/compilation_cache/`` (a cache entry's
    retrieval) becomes a `trace` span named after the event's last path
    component.
    """
    import jax

    _trace_compile_events()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      "^" + re.escape(root + os.sep))
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


_compile_events_traced = False
_MIN_COMPILE_EVENT_SECS = 1e-3


def _trace_compile_events():
    """One `jax.monitoring` duration listener a process, however often
    `enable_compile_cache` is called."""
    global _compile_events_traced
    if _compile_events_traced:
        return
    _compile_events_traced = True
    import jax

    from . import trace

    def on_duration(event, seconds, **kw):
        # `compile_time_saved_sec` is an estimate of time NOT spent: no
        # interval to put on a timeline
        if not event.startswith(("/jax/core/compile/",
                                 "/jax/compilation_cache/")) \
                or event.endswith("_saved_sec"):
            return
        if seconds < _MIN_COMPILE_EVENT_SECS:
            # JAX reports a trace for every inner `jit` it passes through
            # (thousands in one step's tracing, microseconds each, all
            # inside the outer one's span, which covers their time): not
            # recorded, or they would push the feed's spans out of the ring
            return
        trace.span_ended(event.rsplit("/", 1)[1], seconds,
                         **{k: str(v) for k, v in kw.items()})

    jax.monitoring.register_event_duration_secs_listener(on_duration)


def absolutize_args(args, keys=("data_dir", "model_dir", "export_dir",
                                "output", "tfrecord_dir", "log_dir")):
    """Resolve path-valued args on the DRIVER: executor processes run in
    their own per-executor workdirs, so relative paths would land there
    (the reference routes paths through ctx.absolute_path/hdfs_path for the
    same reason, TFNode.py:29-64)."""
    for k in keys:
        v = getattr(args, k, None)
        if v and "://" not in v:
            setattr(args, k, os.path.abspath(v))
    return args
