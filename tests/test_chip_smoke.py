"""CPU rehearsal of `chip_smoke.py`, kept as a test.

The smoke's own `map_fun` and `drive` run through
`cluster.run(LocalBackend(1), ..., InputMode.SPARK)` at toy width (2
layers, d64, S32) on the CPU platform: every path, argument and check of
the chip run except the chip.  The executor is spawn-started because this
process is JAX-threaded (a forked child that jits can deadlock); the node
itself is forked from that fresh executor, exactly as on the chip.
"""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

TOY = dict(vocab_size=256, d_model=64, n_heads=4, n_kv_heads=2, n_layers=2,
           d_ff=128, max_seq_len=32, dtype="bfloat16", rope=True,
           attention_impl="auto", norm_type="rmsnorm")


def failing_map_fun(args, ctx):
    raise RuntimeError("injected node failure")


@pytest.fixture
def cache_env(tmp_path, monkeypatch):
    """Place the compile cache from outside, as the cache rule allows —
    the rehearsal writes nothing into the checkout."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    return str(tmp_path / "cache")


@pytest.mark.parametrize("chips", [1, 4])
def test_rehearsal_through_cluster_run(cache_env, chips):
    args = chip_smoke.smoke_args(chips=chips, platform="cpu", model=TOY,
                                 batch=8)
    result = chip_smoke.drive(args, start_method="spawn", timeout=240)
    assert result["device"]["platform"] == "cpu"
    lines = {ln["phase"]: ln for ln in result["lines"]}
    train = lines["train"]
    assert train["records_consumed"] == args.steps * args.batch
    assert train["steps"] == args.steps
    assert train["losses"][-1] < train["losses"][0]
    assert len(train["step_ms_block_until_ready"]) == chip_smoke.TIMED_STEPS
    assert len(train["step_ms_readback"]) == chip_smoke.TIMED_STEPS
    assert lines["compile"]["compile_cache_dir"] == cache_env
    if chips == 4:
        assert len(train["shard_devices"]) == 4
        assert lines["compile"]["all_reduces"] > 0
        assert len(lines["one_device_reference"]["losses"]) == \
            chip_smoke.COMPARE_STEPS
    else:
        assert len(train["shard_devices"]) == 1


def test_failing_node_makes_the_driver_raise(cache_env):
    """Two channels race to report a node that dies at once — its
    traceback on the error queue, or the executor's exit-code report —
    and either must surface from `drive`."""
    args = chip_smoke.smoke_args(platform="cpu", model=TOY, batch=8)
    with pytest.raises(RuntimeError, match="injected node failure|"
                                           "exited with code 1"):
        chip_smoke.drive(args, map_fn=failing_map_fun,
                         start_method="spawn", timeout=120)


def test_check_names_each_silent_failure():
    args = chip_smoke.smoke_args(platform="tpu", model=TOY, batch=8)
    n = chip_smoke.TIMED_STEPS
    good = {"device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
            "lines": [
                {"phase": "compile", "tpu_custom_calls": 5,
                 "all_reduces": 0},
                {"phase": "train", "losses": [10.0, 9.0],
                 "records_consumed": 96,
                 "step_ms_block_until_ready": [1.0] * n,
                 "step_ms_readback": [1.0] * n, "shard_devices": ["d0"]}]}
    assert chip_smoke.check(args, good, fed=96) == []

    def broken(**edits):
        r = json.loads(json.dumps(good))
        for path, value in edits.items():
            line, key = path.split("__")
            target = r["device"] if line == "device" else next(
                ln for ln in r["lines"] if ln["phase"] == line)
            target[key] = value
        return chip_smoke.check(args, r, fed=96)

    assert "platform" in broken(device__platform="cpu")[0]
    assert "tpu_custom_call" in broken(compile__tpu_custom_calls=0)[0]
    assert "non-finite" in broken(train__losses=[10.0, float("nan")])[0]
    assert "did not fall" in broken(train__losses=[9.0, 10.0])[0]
    assert "consumed" in broken(train__records_consumed=88)[0]
    assert "fewer timed" in broken(train__step_ms_readback=[1.0])[0]


def test_script_off_the_chip_fails_without_ok_line():
    """`python chip_smoke.py` here (CPU only) must exit non-zero, fast —
    the node refuses before it builds the flagship — and never print
    `"ok": true`."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, os.path.join(REPO,
                                                        "chip_smoke.py")],
                          capture_output=True, text=True, timeout=240,
                          env=env, cwd=REPO)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "not 'tpu'" in proc.stderr


def test_dryrun_multichip_never_moves_to_the_cpu_by_itself():
    """Short of devices it raises (it used to re-pin itself to a virtual
    CPU platform and report success from there)."""
    import __graft_entry__ as entry

    with pytest.raises(RuntimeError, match="needs 16 devices"):
        entry.dryrun_multichip(16)
