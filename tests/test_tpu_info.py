"""Chip-assignment tests (models reference tests/test_TFSparkNode.py GPU paths,
with the same mock-the-discovery-seam technique)."""
from unittest import mock

import pytest

from tensorflowonspark_tpu import tpu_info


class FakeDevice:
    def __init__(self, i, platform="tpu"):
        self.id = i
        self.platform = platform
        self.device_kind = "fake-tpu"
        self.process_index = 0


def fake_devices(n):
    return [FakeDevice(i) for i in range(n)]


def test_assign_default(monkeypatch):
    monkeypatch.setenv("TFOS_TPU_LOCAL_CHIPS", "4")
    assert tpu_info.assign_chips(1) == "0"
    assert tpu_info.assign_chips(2, fmt=tpu_info.AS_LIST) == [0, 1]


def test_assign_multi_worker_same_host(monkeypatch):
    monkeypatch.setenv("TFOS_TPU_LOCAL_CHIPS", "8")
    # Worker-index-based deterministic placement (reference: gpu_info.py:60-87).
    assert tpu_info.assign_chips(2, worker_index=0, fmt=tpu_info.AS_LIST) == [0, 1]
    assert tpu_info.assign_chips(2, worker_index=1, fmt=tpu_info.AS_LIST) == [2, 3]
    assert tpu_info.assign_chips(2, worker_index=3, fmt=tpu_info.AS_LIST) == [6, 7]
    # Oversubscription raises — TPU chips are exclusively locked, so wrapping
    # (the reference's GPU behavior) would crash a sibling at runtime init.
    with pytest.raises(RuntimeError, match="oversubscription"):
        tpu_info.assign_chips(2, worker_index=4)


def test_assign_too_many_raises(monkeypatch):
    monkeypatch.setenv("TFOS_TPU_LOCAL_CHIPS", "2")
    with pytest.raises(RuntimeError, match="only 2 visible"):
        tpu_info.assign_chips(4)


def test_assign_sets_visible_chips_env(monkeypatch):
    monkeypatch.setenv("TFOS_TPU_LOCAL_CHIPS", "8")
    tpu_info.assign_chips(4, worker_index=1)
    import os
    assert os.environ["TPU_VISIBLE_CHIPS"] == "4,5,6,7"


def test_assign_retries_then_fails(monkeypatch):
    monkeypatch.setattr(tpu_info, "RETRY_DELAY_SECS", 0)
    calls = {"n": 0}

    def boom():
        calls["n"] += 1
        raise RuntimeError("backend busy")

    with mock.patch.object(tpu_info, "_count_local_chips", side_effect=boom):
        with pytest.raises(RuntimeError, match="no accelerator devices"):
            tpu_info.assign_chips(1)
    assert calls["n"] == tpu_info.MAX_RETRIES + 1


def test_is_tpu_available_false_on_cpu():
    with mock.patch.object(tpu_info, "_probe_devices", side_effect=RuntimeError("no tpu")):
        assert tpu_info.is_tpu_available() is False


def test_slice_topology_env(monkeypatch):
    monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "h0,h1,h2,h3")
    monkeypatch.setenv("TPU_WORKER_ID", "2")
    topo = tpu_info.get_slice_topology()
    assert topo == {"worker_id": 2, "num_workers": 4, "hosts": ["h0", "h1", "h2", "h3"]}


def test_count_without_devfs_never_probes_in_process(monkeypatch):
    """No env override, no device nodes: the count comes from a throwaway
    child — a JAX probe HERE would take the chip in the executor process
    that then starts the node."""
    import glob

    monkeypatch.delenv("TFOS_TPU_LOCAL_CHIPS", raising=False)
    monkeypatch.setattr(glob, "glob", lambda _pattern: [])
    monkeypatch.setattr(
        tpu_info, "_probe_devices",
        mock.Mock(side_effect=AssertionError("probed in-process")))
    # the child inherits conftest's 8 virtual CPU devices
    assert tpu_info._count_local_chips() == 8
