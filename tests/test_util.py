import os
import re

import pytest

from tensorflowonspark_tpu import util


def test_ip_address_shape():
    ip = util.get_ip_address()
    parts = ip.split(".")
    assert len(parts) == 4


def test_parse_port_spec():
    assert util.parse_port_spec("8080") == [8080]
    assert util.parse_port_spec("8000-8002") == [8000, 8001, 8002]
    with pytest.raises(ValueError):
        util.parse_port_spec("9-5")


def test_executor_id_roundtrip(tmp_path):
    util.write_executor_id(7, cwd=str(tmp_path))
    assert util.read_executor_id(cwd=str(tmp_path)) == 7


def test_find_in_path(tmp_path):
    f = tmp_path / "needle.txt"
    f.write_text("x")
    path = os.pathsep.join(["/nonexistent", str(tmp_path)])
    assert util.find_in_path(path, "needle.txt") == str(f)
    assert util.find_in_path(path, "missing.txt") is False


def test_bind_socket_port_list():
    port = util.get_free_port()
    s1 = util.bind_socket("127.0.0.1", [port])
    try:
        # first port busy -> falls through to the next in range
        s2 = util.bind_socket("127.0.0.1", [port, port + 1, port + 2])
        assert s2.getsockname()[1] in (port + 1, port + 2)
        s2.close()
    finally:
        s1.close()


# what the rule sets wherever the cache lives: the names in the program are
# part of the key, the checkout's root is not (tests/test_trace_scopes.py
# compiles through it)
CACHE_KEY_OPTIONS = ["jax_compilation_cache_include_metadata_in_key",
                     "jax_hlo_source_file_canonicalization_regex"]


def test_compile_cache_placed_from_outside_sets_no_directory(monkeypatch,
                                                             tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: jax honours it itself, the helper
    sets what the cache keys on and no directory."""
    import jax

    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: calls.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert util.enable_compile_cache() == str(tmp_path)
    assert [a[0] for a in calls] == CACHE_KEY_OPTIONS


def test_compile_cache_default_is_fixed_inside_the_checkout(monkeypatch):
    """No env: one fixed absolute directory in the checkout — the path is
    part of the cache key, so never a tempdir, a pid or the clock, and
    never relative (executors chdir into scratch dirs)."""
    import tempfile

    import jax

    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: calls.append(a))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = util.enable_compile_cache()
    assert path == os.path.join(repo, ".jax_cache")
    assert not path.startswith(tempfile.gettempdir())
    assert [a[0] for a in calls] == CACHE_KEY_OPTIONS + [
        "jax_compilation_cache_dir"]
    assert calls[-1][1] == path
    assert calls[0][1] is True
    # the same tree at another path keeps its entries: its root is cut
    # from the file names that the key now holds
    assert re.sub(calls[1][1], "", os.path.join(
        repo, "tensorflowonspark_tpu", "util.py")) == os.path.join(
            "tensorflowonspark_tpu", "util.py")
    monkeypatch.chdir(tempfile.gettempdir())
    assert util.enable_compile_cache() == path


def test_per_test_limit_fails_a_hung_test_by_name(monkeypatch):
    """tests/conftest.py's soft limit (SIGALRM): a test stuck in an
    interruptible wait fails with its own name instead of hanging the
    run to the driver's timeout, and the worker lives on."""
    import threading

    import conftest

    class Item:
        nodeid = "tests/test_x.py::test_hang"

    monkeypatch.setattr(conftest, "SOFT_LIMIT_S", 1)
    limit = conftest._limited(Item())
    next(limit)
    lock = threading.Lock()
    lock.acquire()
    try:
        with pytest.raises(TimeoutError, match="test_x.py::test_hang"):
            lock.acquire()          # hangs until the alarm fires
    finally:
        limit.close()


TESTS = os.path.dirname(os.path.abspath(__file__))


def test_hard_limit_stops_a_process_stuck_where_no_handler_runs():
    """The hard limit works from outside the interpreter: the main thread
    sits in C holding the GIL (`sum` over a range never checks signals,
    as an XLA rendezvous never does), the SIGALRM handler cannot run, and
    the watchdog thread dumps the stacks and exits the process."""
    import subprocess
    import sys

    script = (
        "import sys; sys.path.insert(0, %r)\n"
        "import conftest\n"
        "conftest.SOFT_LIMIT_S, conftest.HARD_LIMIT_S = 1, 3\n"
        "class Item: nodeid = 'tests/test_x.py::test_native_hang'\n"
        "limit = conftest._limited(Item()); next(limit)\n"
        "sum(range(10 ** 12))\n" % TESTS)
    proc = subprocess.run([sys.executable, "-c", script],
                          capture_output=True, text=True, timeout=150)
    assert proc.returncode == 1
    assert "Timeout (0:00:03)!" in proc.stderr
    assert 'File "<string>", line 6' in proc.stderr
    assert "TimeoutError" not in proc.stderr


def test_run_goes_on_after_a_worker_crash(tmp_path):
    """`conftest.pytest_handlecrashitem`, end to end: under `-n 2 --dist
    loadfile` a worker finishes one file, then dies in the second test of
    its next file.  Without the hook xdist 3.8 hands the replacement
    worker the finished file, nothing runs again and the run never ends
    (this subprocess would time out); with it the crashed test fails by
    name once and the rest of its file still runs."""
    import subprocess
    import sys

    (tmp_path / "conftest.py").write_text(
        "import importlib.util\n"
        "spec = importlib.util.spec_from_file_location('repo_conftest', %r)\n"
        "repo = importlib.util.module_from_spec(spec)\n"
        "spec.loader.exec_module(repo)\n"
        "pytest_handlecrashitem = repo.pytest_handlecrashitem\n"
        % os.path.join(TESTS, "conftest.py"))
    # loadfile hands out the largest files first: a and b go to the two
    # workers, then c to the first as its second file
    for name in "ab":
        (tmp_path / f"test_{name}.py").write_text(
            "import pytest\n"
            "@pytest.mark.parametrize('i', range(4))\n"
            "def test_quick(i):\n    pass\n")
    (tmp_path / "test_c.py").write_text(
        "import os, time, pytest\n"
        "@pytest.mark.parametrize('i', range(3))\n"
        "def test_late(i):\n"
        "    time.sleep(1)\n"
        "    if i == 1:\n"
        "        os.abort()\n")
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", str(tmp_path), "-q", "-p", "xdist",
         "-n", "2", "--dist", "loadfile", "-p", "no:cacheprovider",
         "-p", "no:randomly"],
        capture_output=True, text=True, timeout=150, cwd=str(tmp_path))
    assert "1 failed, 10 passed" in proc.stdout, proc.stdout + proc.stderr
    assert "crashed while running 'test_c.py::test_late[1]'" in proc.stdout
