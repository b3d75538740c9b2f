"""CPU rehearsals of a whole run, kept as tests: `harness.drive` and
`harness.node_main` at toy size through `cluster.run(LocalBackend(1), ...,
InputMode.SPARK)`, the four-chip cell on four virtual devices; then the same
with the timed path broken underneath, once for each fault a training cell
can have, and `correct` has to come out false.

The look for a chip is skipped through the spec (`platform="cpu"`), as
`tests/test_chip_smoke.py` does it through `smoke_args`: no command-line
switch.  The executor is spawn-started because pytest's process may be
JAX-threaded.
"""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import toy  # noqa: E402
import harness  # noqa: E402


@pytest.fixture
def cache_env(tmp_path, monkeypatch):
    """The compile cache placed from outside, as the cache rule allows: a
    rehearsal writes nothing into the checkout."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("XLA_FLAGS",
                       "--xla_force_host_platform_device_count=4")


def run(kind, chips, fault=None):
    spec = toy.spec(kind, chips=chips, fault=fault)
    return spec, harness.drive(spec, start_method="spawn", timeout=280)


@pytest.mark.parametrize("kind,chips", [("lm", 1), ("lm", 4), ("resnet", 1)])
def test_rehearsal_is_correct(cache_env, kind, chips):
    spec, r = run(kind, chips)
    assert r["device"]["platform"] == "cpu"
    assert r["correct"], r["numbers"]
    w = r["window"]
    assert w["steps"] >= 2 and w["records"] == w["steps"] * spec.traffic["batch"]
    assert w["compiles_in_window"] == 0
    assert len(w["intervals_ms"]) == w["steps"]
    assert r["setup_s"] > r["launch_s"] > 0
    assert len(r["shard_devices"]) == chips
    assert (r["info"]["all_reduces"] > 0) == (chips > 1)
    assert len(r["program"]["losses"]) == spec.traffic["check_steps"]
    assert r["fed_records"] >= w["records"]


@pytest.mark.parametrize("kind,chips,fault,caught_by", [
    ("lm", 1, "state_unchanged", "update_norm_gap"),
    ("lm", 1, "half_batch", "grad_norm_gap"),
    ("lm", 1, "feed_altered", "feed_rows_wrong"),
    ("lm", 4, "no_exchange", "grad_norm_gap"),
    ("resnet", 1, "state_unchanged", "update_norm_gap"),
    ("resnet", 1, "half_batch", "grad_norm_gap"),
])
def test_broken_timed_path_is_not_correct(cache_env, kind, chips, fault,
                                          caught_by):
    _, r = run(kind, chips, fault)
    assert not r["correct"], r["numbers"]
    n = r["numbers"][caught_by]
    assert n["value"] > n["limit"], r["numbers"]
