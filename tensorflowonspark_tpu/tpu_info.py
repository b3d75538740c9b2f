"""Accelerator discovery & per-worker chip assignment (maps reference gpu_info.py:1-98).

The reference shells out to `nvidia-smi`, parses busy GPUs, and sets
CUDA_VISIBLE_DEVICES with retry/backoff.  On TPU the runtime owns device
enumeration, so the equivalents are:

- probing the JAX platform (with the same retry×backoff discipline, since a
  TPU chip can be transiently held by a dying predecessor process),
- deterministic per-worker chip slicing via ``TPU_VISIBLE_CHIPS`` when
  multiple executor processes share one TPU host (the analog of the
  reference's worker-index-based GPU placement, gpu_info.py:60-87),
- topology metadata (slice shape, process index) for mesh construction.

All probing goes through `_probe_devices` so tests can mock the seam
(the reference tests patch `gpu_info.get_gpus`; SURVEY.md §4).
"""
import logging
import os
import time

logger = logging.getLogger(__name__)

MAX_RETRIES = 3
RETRY_DELAY_SECS = 10  # reference used 30s*retry; TPU probes are cheaper

AS_LIST = "list"
AS_STRING = "string"


def _probe_devices(platform=None):
    """Return jax.devices(platform) — isolated seam for mocking."""
    import jax
    return jax.devices(platform) if platform else jax.devices()


def is_tpu_available():
    """True if any TPU chip is visible (reference: gpu_info.py:22-28)."""
    try:
        return len(_probe_devices("tpu")) > 0
    except RuntimeError:
        return False


def get_accelerator_info():
    """Summarize the visible accelerator platform.

    Returns dict(platform, device_kind, num_devices, num_local_devices,
    process_index, num_processes).
    """
    import jax
    devices = _probe_devices()
    local = [d for d in devices if d.process_index == jax.process_index()]
    return {
        "platform": devices[0].platform if devices else "none",
        "device_kind": devices[0].device_kind if devices else "none",
        "num_devices": len(devices),
        "num_local_devices": len(local),
        "process_index": jax.process_index(),
        "num_processes": jax.process_count(),
    }


def _count_local_chips():
    """Count local TPU chips WITHOUT initializing the JAX runtime.

    Order matters: initializing JAX in this process would lock every chip
    (libtpu takes an exclusive lock at runtime init), make a later
    ``TPU_VISIBLE_CHIPS`` restriction a no-op, and — this process being
    the executor that goes on to START the node process — leave the node
    to hang or fail on a chip its own parent holds.  So we count via env
    override, then devfs (a v5e host shows ``/dev/vfio/<n>``, one per
    chip), and otherwise ask a throwaway child, which takes the chips,
    counts, and gives them back by exiting.
    """
    env = os.environ.get("TFOS_TPU_LOCAL_CHIPS")
    if env:
        return int(env)
    import glob
    accels = glob.glob("/dev/accel*") + glob.glob("/dev/vfio/[0-9]*")
    if accels:
        return len(accels)
    import subprocess
    import sys
    probe = subprocess.run(
        [sys.executable, "-c",
         "import jax; print(len(jax.local_devices()))"],
        capture_output=True, text=True, timeout=300)
    if probe.returncode != 0:
        raise RuntimeError(
            f"chip-count probe failed: {probe.stderr.strip()[-500:]}")
    return int(probe.stdout.split()[-1])


def assign_chips(num_chips, worker_index=-1, fmt=AS_STRING):
    """Deterministically assign `num_chips` local chips to this worker.

    Maps reference gpu_info.get_gpus (gpu_info.py:31-98): when several worker
    processes land on one host, worker i takes chips
    [i*num_chips, (i+1)*num_chips); with worker_index < 0 assignment starts
    at 0.  Oversubscription raises — TPU chips are exclusively locked by the
    runtime, so silently sharing them (the reference wrapped GPU indices
    modulo the pool) would crash a sibling at init time instead.  Retries
    with linear backoff to ride out a predecessor process still holding the
    chips.

    Sets ``TPU_VISIBLE_CHIPS`` so a JAX runtime started AFTER this call (in
    this process or a child) sees only the assigned chips, and returns the
    chip ids as a comma string (AS_STRING) or list (AS_LIST).
    """
    num_local = None
    last_err = None
    for retry in range(MAX_RETRIES + 1):
        try:
            num_local = _count_local_chips()
            break
        except RuntimeError as e:
            last_err = e
            if retry < MAX_RETRIES:
                delay = RETRY_DELAY_SECS * (retry + 1)
                logger.warning("accelerator probe failed (%s); retrying in %ds", e, delay)
                time.sleep(delay)
    if num_local is None:
        raise RuntimeError(f"no accelerator devices available: {last_err}")

    if num_chips > num_local:
        raise RuntimeError(
            f"requested {num_chips} chips but only {num_local} visible")

    start = 0 if worker_index < 0 else worker_index * num_chips
    if start + num_chips > num_local:
        raise RuntimeError(
            f"worker {worker_index} needs chips [{start}, {start + num_chips}) "
            f"but only {num_local} exist on this host — oversubscription is "
            f"an error on TPU (chips are exclusively locked)")
    chip_ids = list(range(start, start + num_chips))
    visible = ",".join(str(c) for c in chip_ids)
    os.environ["TPU_VISIBLE_CHIPS"] = visible
    logger.info("worker %d assigned chips [%s] of %d local", worker_index, visible, num_local)
    return chip_ids if fmt == AS_LIST else visible


def get_slice_topology():
    """Best-effort TPU slice topology from env + runtime.

    Cloud TPU VMs export TPU_WORKER_ID / TPU_WORKER_HOSTNAMES; fall back to
    single-host when absent.  Returns dict(worker_id, num_workers, hosts).
    """
    hosts_env = os.environ.get("TPU_WORKER_HOSTNAMES", "")
    hosts = [h for h in hosts_env.split(",") if h] or ["localhost"]
    worker_id = int(os.environ.get("TPU_WORKER_ID", "0"))
    return {"worker_id": worker_id, "num_workers": len(hosts), "hosts": hosts}
