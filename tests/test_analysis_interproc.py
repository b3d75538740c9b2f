"""graftcheck v2 tests: call graph, dataflow summaries, thread-role race
analyzer, jit-recompile lint, marker-free hostsync, and the new CLI
plumbing (SARIF, --changed-only, shrink-only baseline guard).

Stdlib only — no JAX import.  The serve.py tests run the REAL rules over
the real package so the three PR 6 roles (device, host-drain, HTTP
callers) are verified against the actual engine, not a fixture.
"""
import json
import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from tensorflowonspark_tpu.analysis import core  # noqa: E402
from tensorflowonspark_tpu.analysis import (  # noqa: E402,F401  (registers)
    callgraph, dataflow, hostsync, locks, pallas_tiles, recompile,
    shardlint, style, threads, tracer)


def run(src, rules, path="tensorflowonspark_tpu/mod.py"):
    findings = core.analyze_source(textwrap.dedent(src), path=path,
                                   rules=rules)
    return [(f.rule, f.line) for f in findings], findings


def _project(sources):
    """Project out of {path: src} in-memory files."""
    project = core.Project()
    for path, src in sources.items():
        project.files.append(core.FileContext.from_source(
            textwrap.dedent(src), path=path, project=project))
    return project


# ------------------------------------------------------------ callgraph ----

def test_callgraph_resolves_methods_imports_and_closures():
    project = _project({
        "tensorflowonspark_tpu/util.py": """
            def helper(v):
                return v

            class Base:
                def shared(self):
                    return 1
        """,
        "tensorflowonspark_tpu/mod.py": """
            from tensorflowonspark_tpu.util import helper
            from tensorflowonspark_tpu import util

            class C(util.Base):
                def m(self):
                    return self.shared() + helper(2) + util.helper(3)

            def outer():
                def inner(v):
                    return v
                def caller():
                    return inner(1)
                return caller
        """,
    })
    cg = callgraph.for_project(project)
    mod = cg.modules["tensorflowonspark_tpu.mod"]
    c = mod.classes["C"]
    m = c.methods["m"]

    import ast
    calls = [n for n in ast.walk(m.node) if isinstance(n, ast.Call)]
    resolved = {cg.resolve_call(n.func, m).qualname
                for n in calls if cg.resolve_call(n.func, m) is not None}
    # self.shared through the project-resolvable base class, plus both
    # import styles of the helper
    assert "util.Base.shared" in resolved
    assert "util.helper" in resolved

    caller = mod.functions["outer"].nested["caller"]
    inner_call = [n for n in ast.walk(caller.node)
                  if isinstance(n, ast.Call)][0]
    fi = cg.resolve_call(inner_call.func, caller)
    assert fi is not None and fi.name == "inner"   # sibling closure


def test_callgraph_caches_on_project():
    project = _project({"tensorflowonspark_tpu/a.py": "X = 1\n"})
    assert callgraph.for_project(project) is callgraph.for_project(project)


# ------------------------------------------------- dataflow summaries ------

def test_tracer_taint_survives_one_helper_level():
    hits, fs = run("""
        import jax

        def _to_host(v):
            return float(v)

        @jax.jit
        def f(x):
            return _to_host(x)
    """, ["tracer-host-cast"])
    assert [r for r, _ in hits] == ["tracer-host-cast"]
    assert "helper '_to_host'" in fs[0].message


def test_tracer_helper_launders_and_concrete_actual_passes():
    hits, _ = run("""
        import jax

        def _to_host(v):
            return float(v)

        def _shape_of(v):
            return v.shape

        @jax.jit
        def f(x):
            a = _shape_of(x)       # summary returns no origins: laundered
            b = _to_host(3.5)      # concrete actual: hazard dead here
            return x * a[0] + b
    """, ["tracer-host-cast"])
    assert hits == []


def test_dataflow_depth_bound_cutoff():
    src = """
        import jax

        def h3(w):
            return float(w)

        def h2(v):
            return h3(v)

        def h1(u):
            return h2(u)

        @jax.jit
        def f(x):
            return h1(x)
    """
    # default depth (2): f -> h1 -> h2 is summarized, h3 is past the
    # bound and goes opaque, so the cast three frames down is missed...
    hits, _ = run(src, ["tracer-host-cast"])
    assert hits == []
    # ...while the same cast two frames down reports
    hits, fs = run(src.replace("return h1(x)", "return h2(x)"),
                   ["tracer-host-cast"])
    assert [r for r, _ in hits] == ["tracer-host-cast"]
    assert "helper 'h2'" in fs[0].message


def test_dataflow_recursion_cycle_terminates():
    hits, _ = run("""
        import jax

        def even(n):
            return odd(n - 1)

        def odd(n):
            return even(n - 1)

        @jax.jit
        def f(x):
            return even(x)
    """, ["tracer-host-cast"])
    assert hits == []   # opaque at the cycle, and it terminates


def test_tracer_staged_closure_resolves_sibling_helper():
    hits, fs = run("""
        import jax

        def make(cfg):
            def helper(v):
                return float(v)

            @jax.jit
            def step(x):
                return helper(x)
            return step
    """, ["tracer-host-cast"])
    assert [r for r, _ in hits] == ["tracer-host-cast"]
    assert "helper 'helper'" in fs[0].message


def test_tracer_side_effect_in_helper_is_unconditional():
    hits, _ = run("""
        import jax

        def log(v):
            print(v)

        @jax.jit
        def f(x):
            log(1)
            return x
    """, ["tracer-side-effect"])
    assert [r for r, _ in hits] == ["tracer-side-effect"]


# ------------------------------------------------------- thread roles ------

BATCHER = """
    import queue
    import threading

    class Batcher:
        def __init__(self):
            self._thread = threading.Thread(target=self._loop)
            self._host_thread = threading.Thread(target=self._host_loop)
            self._ready = queue.Queue(2)
            self._retire_q = queue.Queue()
            self.n_done = 0
            self._items = {}

        def _loop(self):
            self._dispatch()

        def _dispatch(self):
            self._items["k"] = 1
            x = make_step()
            x.copy_to_host_async()
            self._ready.put(x)

        def _host_loop(self):
            x = self._ready.get()
            self._process(x)

        def _process(self, x):
            self.n_done += 1

        def _free(self):
            self.n_done += 1

        def retire(self):
            if threading.current_thread() is self._thread:
                self._free()
                return
            self._retire_q.put(1)

        def stats(self):
            return len(self._items)
"""


def test_thread_roles_inferred_from_entry_points():
    project = _project({"tensorflowonspark_tpu/b.py": BATCHER})
    cg = callgraph.for_project(project)
    ci = cg.modules["tensorflowonspark_tpu.b"].classes["Batcher"]
    model = threads.build_class_model(ci)
    assert set(model.roles) == {"thread:_loop", "thread:_host_loop",
                                "external"}
    assert model.roles["thread:_loop"].device          # copy_to_host_async
    assert not model.roles["thread:_host_loop"].device
    assert "retire" in model.roles["external"].methods
    # pinned call edge: _free reaches ONLY the device role
    assert "_free" in model.roles["thread:_loop"].methods
    assert "_free" not in model.roles["external"].methods


def test_thread_race_container_cross_role():
    hits, fs = run(BATCHER, ["thread-race"],
                   path="tensorflowonspark_tpu/b.py")
    # _items: content-written on the device thread, len()'d from stats
    # (external), no common lock.  n_done: _process RMW (host) + _free
    # RMW (device, via the pinned call edge) => cross-role lost update.
    assert [r for r, _ in hits] == ["thread-race", "thread-race"]
    msgs = " | ".join(f.message for f in fs)
    assert "_items" in msgs and "container content-written" in msgs
    assert "n_done" in msgs and "read-modify-write" in msgs


def test_thread_race_common_lock_and_queue_are_safe():
    hits, _ = run("""
        import queue
        import threading

        class C:
            def __init__(self):
                self._thread = threading.Thread(target=self._loop)
                self._lock = threading.Lock()
                self._q = queue.Queue()
                self._items = {}
                self.n = 0

            def _loop(self):
                with self._lock:
                    self._items["k"] = 1
                    self.n += 1
                self._q.put(1)

            def read(self):
                with self._lock:
                    self.n += 1
                    return len(self._items)

            def poke(self):
                self._q.put(2)
    """, ["thread-race"], path="tensorflowonspark_tpu/c.py")
    assert hits == []


def test_thread_race_atomic_rebind_publication_is_safe():
    hits, _ = run("""
        import threading

        class C:
            def __init__(self):
                self._thread = threading.Thread(target=self._loop)
                self._snapshot = None

            def _loop(self):
                self._snapshot = {"a": 1}    # fresh object, atomic rebind

            def read(self):
                return self._snapshot
    """, ["thread-race"], path="tensorflowonspark_tpu/c.py")
    assert hits == []


def test_thread_race_pin_guard_vs_unpinned():
    unpinned = BATCHER.replace(
        """if threading.current_thread() is self._thread:
                self._free()
                return
            self._retire_q.put(1)""",
        "self._free()")
    _, fs = run(unpinned, ["thread-race"],
                path="tensorflowonspark_tpu/b.py")
    msgs = " | ".join(f.message for f in fs)
    # without the identity pin, _free's RMW lands in the external role too
    assert "external" in msgs and "n_done" in msgs


def test_lock_order_cycle():
    hits, fs = run("""
        import threading

        class C:
            def __init__(self):
                self._thread = threading.Thread(target=self._work)
                self._l1 = threading.Lock()
                self._l2 = threading.Lock()

            def _work(self):
                with self._l1:
                    with self._l2:
                        pass

            def flip(self):
                with self._l2:
                    with self._l1:
                        pass
    """, ["lock-order"], path="tensorflowonspark_tpu/c.py")
    assert [r for r, _ in hits] == ["lock-order"]
    assert "lock-order inversion" in fs[0].message


def test_serve_three_roles_identified_with_zero_annotations():
    """Acceptance: device / host-drain / HTTP-caller roles fall out of
    serve.py's entry points with no markers anywhere in the file."""
    path = os.path.join(REPO, "tensorflowonspark_tpu", "serve.py")
    with open(path) as f:
        src = f.read()
    assert "# graftcheck: hotpath" not in src   # markers are GONE
    project = core.load_project([os.path.join(REPO,
                                              "tensorflowonspark_tpu")])
    cg = callgraph.for_project(project)
    ci = cg.modules["tensorflowonspark_tpu.serve"].classes[
        "ContinuousBatcher"]
    model = threads.build_class_model(ci)
    assert "thread:_loop" in model.roles           # device
    assert "thread:_host_loop" in model.roles      # host drain
    assert "external" in model.roles               # HTTP handler threads
    assert model.roles["thread:_loop"].device
    assert not model.roles["thread:_host_loop"].device
    # the public API the HTTP plane calls
    ext = model.roles["external"].methods
    assert "submit" in ext and "stats" in ext
    # shared host-side code is NOT device-exclusive
    device = set(model.roles["thread:_loop"].methods)
    others = set(model.roles["thread:_host_loop"].methods) | set(ext)
    assert "_dispatch" in device - others
    assert "_process_batch" in others


def test_metrics_counters_are_role_safe():
    """The fleet-aggregated stats path: Counters bumped on worker threads
    and read from stats() must NOT flag — Counters carries its own lock
    internally and the batcher only ever calls methods on it."""
    hits, _ = run("""
        import threading
        from tensorflowonspark_tpu.metrics import Counters, Gauge

        class C:
            def __init__(self):
                self._thread = threading.Thread(target=self._loop)
                self.counters = Counters()
                self._depth = Gauge()

            def _loop(self):
                self.counters.inc("copy_to_host_fallbacks")
                self._depth.add(1)

            def stats(self):
                return {
                    "fallbacks": self.counters.get(
                        "copy_to_host_fallbacks"),
                    "peak": self._depth.peak(),
                }
    """, ["thread-race", "lock-order"], path="tensorflowonspark_tpu/c.py")
    assert hits == []
    # and metrics.py itself (single-role classes) analyzes clean
    project = core.load_project(
        [os.path.join(REPO, "tensorflowonspark_tpu", "metrics.py")])
    fs = core.run_rules(project, [core.REGISTRY["thread-race"],
                                  core.REGISTRY["lock-order"]])
    assert fs == []


# -------------------------------------------------- hostsync inference -----

def test_hostsync_inferred_device_role_no_marker():
    src = BATCHER.replace("x = make_step()",
                          "x = make_step()\n            x.block_until_ready()")
    hits, fs = run(src, ["hostsync"], path="tensorflowonspark_tpu/b.py")
    assert [r for r, _ in hits] == ["hostsync"]
    assert "block_until_ready" in fs[0].message
    assert "_dispatch" in fs[0].message


def test_hostsync_shared_host_method_not_covered():
    # _process runs on the host thread: syncs there are the DESIGN
    src = BATCHER.replace("self.n_done += 1\n",
                          "self.n_done += 1\n            x.item()\n", 1)
    hits, _ = run(src, ["hostsync"], path="tensorflowonspark_tpu/b.py")
    assert hits == []


def test_hostsync_serve_coverage_survives_marker_deletion():
    """Acceptance: serve.py carries zero hotpath markers, yet a sync
    injected into the device-thread dispatch path still reports."""
    path = os.path.join(REPO, "tensorflowonspark_tpu", "serve.py")
    with open(path) as f:
        src = f.read()
    assert "# graftcheck: hotpath" not in src
    bad = src.replace(
        "def _dispatch(self):",
        "def _dispatch(self):\n        self._toks.block_until_ready()", 1)
    assert bad != src
    project = core.Project()
    ctx = core.FileContext.from_source(
        bad, path="tensorflowonspark_tpu/serve.py", project=project)
    project.files.append(ctx)
    fs = core.run_rules(project, [core.REGISTRY["hostsync"]])
    assert any("block_until_ready" in f.message
               and "_dispatch" in f.message for f in fs), fs


def test_hostsync_interproc_helper_sync():
    hits, fs = run("""
        import threading

        class C:
            def __init__(self):
                self._thread = threading.Thread(target=self._loop)

            def _wait(self, x):
                x.block_until_ready()

            def _loop(self):
                x = step()
                x.copy_to_host_async()
                self._wait(x)

            def drain(self, x):
                # shared with the host plane, so _wait is NOT itself a
                # hot path and the report goes through the summary
                self._wait(x)
    """, ["hostsync"], path="tensorflowonspark_tpu/c.py")
    assert [r for r, _ in hits] == ["hostsync"]
    assert "helper '_wait'" in fs[0].message


def test_hostsync_marked_mode_still_strict():
    # marker mode flags a bare-name cast; inferred mode tolerates it
    hits, _ = run("""
        def _tick(self, nxt):  # graftcheck: hotpath
            return float(nxt)
    """, ["hostsync"])
    assert [r for r, _ in hits] == ["hostsync"]


# ---------------------------------------------------------- recompile ------

def test_recompile_varying_slice_bound():
    hits, fs = run("""
        import jax

        @jax.jit
        def f(x):
            return x

        def serve(xs, n):
            return f(xs[:n])
    """, ["jit-recompile"])
    assert [r for r, _ in hits] == ["jit-recompile"]
    assert "new XLA program" in fs[0].message


def test_recompile_bucketed_and_constant_bounds_pass():
    hits, _ = run("""
        import jax

        @jax.jit
        def f(x):
            return x

        class S:
            def serve(self, xs, n):
                m = _pow2_width(n)
                k = _bucket_len(n, self.cap)
                return (f(xs[:m]), f(xs[:k]), f(xs[:8]),
                        f(xs[:self.chunk]))
    """, ["jit-recompile"])
    assert hits == []


def test_recompile_static_argnums_varying_value():
    hits, _ = run("""
        import jax

        g = jax.jit(lambda x, k: x * k, static_argnums=(1,))

        def serve(x, k):
            return g(x, k)

        def fixed(x):
            return g(x, 4)
    """, ["jit-recompile"])
    assert [r for r, _ in hits] == ["jit-recompile"]


def test_recompile_jitted_factory_attr():
    hits, fs = run("""
        class S:
            def __init__(self, model):
                self._step = _jitted_slot_step(model)

            def bad(self, toks, n):
                return self._step(toks[:n])
    """, ["jit-recompile"])
    assert [r for r, _ in hits] == ["jit-recompile"]
    assert "_step" in fs[0].message


# ------------------------------------------------------------ CLI/core -----

def _cli(args, cwd=REPO, timeout=120):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "graftcheck.py")]
        + args, cwd=cwd, capture_output=True, text=True, timeout=timeout)


def test_cli_new_rules_listed():
    proc = _cli(["--list-rules"])
    assert proc.returncode == 0
    for rule in ("thread-race", "lock-order", "jit-recompile", "hostsync"):
        assert rule in proc.stdout


def test_cli_sarif_format_and_side_output(tmp_path):
    out = tmp_path / "gc.sarif"
    proc = _cli(["tensorflowonspark_tpu/analysis", "--format", "sarif",
                 "--sarif-output", str(out)])
    assert proc.returncode == 0, proc.stdout + proc.stderr
    doc = json.loads(proc.stdout)
    assert doc["version"] == "2.1.0"
    assert doc["runs"][0]["tool"]["driver"]["name"] == "graftcheck"
    side = json.loads(out.read_text())
    assert side["version"] == "2.1.0"


def test_cli_sarif_reports_findings(tmp_path):
    pkg = tmp_path / "tensorflowonspark_tpu"
    pkg.mkdir()
    (pkg / "bad.py").write_text(
        "import jax\n\n@jax.jit\ndef f(x):\n    return float(x)\n")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "graftcheck.py"),
         "tensorflowonspark_tpu", "--format", "sarif", "--no-baseline"],
        cwd=tmp_path, capture_output=True, text=True, timeout=60)
    assert proc.returncode == 1
    results = json.loads(proc.stdout)["runs"][0]["results"]
    assert len(results) == 1
    assert results[0]["ruleId"] == "tracer-host-cast"
    loc = results[0]["locations"][0]["physicalLocation"]
    assert loc["artifactLocation"]["uri"] == "tensorflowonspark_tpu/bad.py"
    assert loc["region"]["startLine"] == 5


def test_cli_changed_only_in_repo_and_without_git(tmp_path):
    proc = _cli(["--changed-only"])
    assert proc.returncode == 0, proc.stdout + proc.stderr

    pkg = tmp_path / "tensorflowonspark_tpu"
    pkg.mkdir()
    (pkg / "ok.py").write_text("X = 1\n")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "graftcheck.py"),
         "tensorflowonspark_tpu", "--changed-only"],
        cwd=tmp_path, capture_output=True, text=True, timeout=60,
        env={**os.environ, "GIT_DIR": str(tmp_path / "nope")})
    assert proc.returncode == 2
    assert "git" in proc.stderr


def test_baseline_shrink_only_guard(tmp_path):
    pkg = tmp_path / "tensorflowonspark_tpu"
    pkg.mkdir()
    (pkg / "bad.py").write_text(
        "import jax\n\n@jax.jit\ndef f(x):\n    return float(x)\n")
    bl = tmp_path / "bl.json"

    def update(extra=()):
        return subprocess.run(
            [sys.executable, os.path.join(REPO, "scripts", "graftcheck.py"),
             "tensorflowonspark_tpu", "--baseline", str(bl),
             "--update-baseline", *extra],
            cwd=tmp_path, capture_output=True, text=True, timeout=60)

    # empty -> 1 finding would GROW the baseline: refused, nothing written
    proc = update()
    assert proc.returncode == 2
    assert "shrink-only" in proc.stderr
    assert not bl.exists()

    # explicit opt-in writes it
    proc = update(["--grow-baseline"])
    assert proc.returncode == 0
    assert len(json.loads(bl.read_text())["findings"]) == 1

    # same findings: refresh is a no-op, allowed without the flag
    proc = update()
    assert proc.returncode == 0

    # finding fixed: shrink is allowed
    (pkg / "bad.py").write_text("X = 1\n")
    proc = update()
    assert proc.returncode == 0
    assert json.loads(bl.read_text())["findings"] == []


# CPU-seconds the whole-repo scan may take for each thousand lines it scans
SCAN_CPU_S_PER_KLOC = 0.2


def test_repo_wide_scan_under_wall_clock_budget(tmp_path):
    """Acceptance: the full scan (interprocedural rules, the lifecycle
    typestate pass AND the wireproto contract pass included) stays
    inside its budget, and --stats makes it attributable per rule.

    The budget is the scan's own CPU seconds for each thousand lines it
    scanned (the files of `analysis.core.iter_py`, the scanner's walker),
    so it fails for a slower scan and not for a larger repo, nor for a
    loaded box: wall-clock around a subprocess under six xdist workers
    measures the box.  The CPU time is the rusage `os.wait4` returns for
    the scan's own pid.  `RUSAGE_CHILDREN` read before and after (the way
    until PR 37) also counts every OTHER child the worker reaps in between,
    and `subprocess.Popen.__init__` reaps the abandoned children of the
    files that ran on this worker before (a 2 CPU-s child left running by
    an earlier test read 2.04 CPU-s around `python -c pass`): the flat 13.0
    CPU-s failed in the driver's run two times in five while the scan
    itself never read over 9.5.  Samples at 63,831 lines in 198 files
    (PR 37): 8.01-8.52 CPU-s on a quiet box (0.125-0.134 a thousand
    lines), 8.00-9.50 over 16 samples taken while `-n 6` ran the tests
    (up to 0.149; wall up to 18.0 s); the bound is the loaded maximum
    plus a third."""
    from tensorflowonspark_tpu.analysis.core import iter_py

    paths = ["tensorflowonspark_tpu", "tests", "examples", "chip_smoke.py"]
    klines = sum(sum(1 for _ in open(os.path.join(REPO, f), "rb"))
                 for f in iter_py([os.path.join(REPO, p)
                                   for p in paths])) / 1e3
    out = tmp_path / "scan.out"
    with open(out, "w") as f:
        child = subprocess.Popen(
            [sys.executable, os.path.join(REPO, "scripts", "graftcheck.py")]
            + paths + ["--stats"], cwd=REPO, stdout=f,
            stderr=subprocess.STDOUT, text=True)
        # (the conftest's limit ends a scan that never does)
        _, status, usage = os.wait4(child.pid, 0)
        child.returncode = os.waitstatus_to_exitcode(status)
    cpu_s = usage.ru_utime + usage.ru_stime
    printed = out.read_text()
    assert child.returncode == 0, printed
    assert "graftcheck clean" in printed
    assert klines > 50, "the walker found the repo"
    assert cpu_s < SCAN_CPU_S_PER_KLOC * klines, (
        f"scan took {cpu_s:.1f} CPU-seconds for {klines:.1f} thousand "
        f"lines: {cpu_s / klines:.3f} each")
    # per-rule wall-time / finding-count table
    assert "graftcheck rule stats" in printed
    stats_lines = printed[printed.index("graftcheck rule stats"):]
    for rule in ("lifecycle-double-free", "thread-race",
                 "wire-unhandled-endpoint", "total"):
        assert rule in stats_lines
    assert "ms" in stats_lines
