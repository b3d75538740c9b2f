"""Device-mesh construction and axis conventions.

Axis names (fixed vocabulary used by every sharding rule in the framework):

- ``dp``   — data parallel: batch is split, gradients allreduced (the
             TPU-native replacement for the reference's
             MultiWorkerMirroredStrategy path, SURVEY.md §2.3).
- ``fsdp`` — data parallel with parameter sharding (ZeRO-3 style): batch
             split like dp, parameters/optimizer state sharded and
             all-gathered per layer.
- ``pp``   — pipeline parallel: layers are partitioned into stages.
- ``tp``   — tensor parallel (Megatron-style): weight matrices split.
             Sequence parallelism (``sp``) reuses this axis: activations
             outside attention/mlp blocks are sharded over sequence on the
             same devices that shard weights.
- ``ep``   — expert parallel for MoE layers; experts are distributed over
             this axis (aliases a slice of the dp axis when not explicit).

Mesh-axis ORDER is (dp, fsdp, pp, tp): the innermost axis (tp) maps to the
most tightly-coupled devices (same host / shortest ICI hops), which is what
`jax.make_mesh` optimizes for; dp/fsdp collectives tolerate longer paths and
DCN when multi-slice.
"""
import dataclasses
import logging

logger = logging.getLogger(__name__)

AXIS_DP = "dp"
AXIS_FSDP = "fsdp"
AXIS_PP = "pp"
AXIS_TP = "tp"
ALL_AXES = (AXIS_DP, AXIS_FSDP, AXIS_PP, AXIS_TP)

# Axes over which a data batch is split (used for per-host feed sharding and
# for gradient psum).
BATCH_AXES = (AXIS_DP, AXIS_FSDP)


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Logical parallelism layout.  -1 for dp means "whatever is left"."""
    dp: int = -1
    fsdp: int = 1
    pp: int = 1
    tp: int = 1

    def resolve(self, num_devices):
        fixed = self.fsdp * self.pp * self.tp
        if self.dp == -1:
            if num_devices % fixed != 0:
                raise ValueError(
                    f"{num_devices} devices not divisible by fsdp*pp*tp={fixed}")
            dp = num_devices // fixed
        else:
            dp = self.dp
        total = dp * fixed
        if total != num_devices:
            raise ValueError(
                f"mesh {dp}x{self.fsdp}x{self.pp}x{self.tp}={total} does not "
                f"match {num_devices} devices")
        return MeshSpec(dp=dp, fsdp=self.fsdp, pp=self.pp, tp=self.tp)

    @property
    def shape(self):
        return (self.dp, self.fsdp, self.pp, self.tp)

    @property
    def batch_size_divisor(self):
        return self.dp * self.fsdp


def _axis_types():
    """Auto axis types = classic GSPMD propagation: the compiler may insert
    collectives (partial-sum allreduce for row-parallel matmuls,
    reduce-scatter/all-gather at SP boundaries) instead of treating
    shardings as assertions, which is what Megatron-style TP+SP needs."""
    import jax

    return (jax.sharding.AxisType.Auto,) * len(ALL_AXES)


def build_mesh(spec=None, devices=None):
    """Build a `jax.sharding.Mesh` with the framework's canonical axes."""
    import jax
    import numpy as np

    devs = list(devices) if devices is not None else jax.devices()
    spec = (spec or MeshSpec()).resolve(len(devs))
    if devices is None:
        # make_mesh picks a device order that keeps inner axes on short ICI
        # paths — use it whenever we're not given an explicit device list.
        mesh = jax.make_mesh(spec.shape, ALL_AXES, axis_types=_axis_types())
    else:
        mesh = jax.sharding.Mesh(
            np.asarray(devs).reshape(spec.shape), ALL_AXES,
            axis_types=_axis_types())
    logger.info("built mesh %s over %d devices", dict(zip(ALL_AXES, spec.shape)),
                len(devs))
    return mesh


def detect_num_slices(devices):
    """Number of distinct TPU slices in `devices` (1 when the platform does
    not expose ``slice_index``, e.g. CPU or single-slice TPU)."""
    idx = {getattr(d, "slice_index", 0) for d in devices}
    return len(idx)


def hybrid_device_array(spec, devices, num_slices):
    """Arrange `devices` into a (dp, fsdp, pp, tp) array where the slice
    (DCN granule) index varies only along the OUTERMOST part of dp.

    dp is factored as (num_slices, dp_inner): data-parallel gradient
    allreduce is the only collective that crosses slice boundaries and rides
    DCN; fsdp/pp/tp (and dp_inner) collectives stay on intra-slice ICI.
    Devices are grouped by ``slice_index`` when the platform exposes it,
    else by contiguous equal partitions of the given order.
    """
    import numpy as np

    if spec.dp % num_slices != 0:
        raise ValueError(
            f"dp={spec.dp} must be divisible by num_slices={num_slices} "
            "(the dp axis is the only one that crosses DCN)")
    per_slice = len(devices) // num_slices
    if per_slice * num_slices != len(devices):
        raise ValueError(f"{len(devices)} devices not divisible into "
                         f"{num_slices} slices")
    dp_inner = spec.dp // num_slices
    groups = {}
    if all(hasattr(d, "slice_index") for d in devices):
        for d in devices:
            groups.setdefault(d.slice_index, []).append(d)
        if len(groups) != num_slices:
            raise ValueError(
                f"devices span {len(groups)} slices, expected {num_slices}")
        try:
            # Real sliced hardware: let jax pick the ICI-optimal order
            # within each slice (physical-coordinate aware), with slices
            # laid along the outer dp factor.
            from jax.experimental import mesh_utils
            return mesh_utils.create_hybrid_device_mesh(
                (dp_inner, spec.fsdp, spec.pp, spec.tp),
                (num_slices, 1, 1, 1), devices)
        except (ValueError, ImportError, AttributeError) as e:
            # Topology-assignment ValueErrors (e.g. a per-slice shape that
            # doesn't map onto the physical torus) or devices jax can't
            # introspect: the enumeration-order placement below still
            # yields a working mesh with the slice/dp invariant intact.
            logger.warning("create_hybrid_device_mesh failed for platform "
                           "%s (%s); using enumeration-order placement",
                           getattr(devices[0], "platform", "?"), e)
    else:
        for i in range(num_slices):
            groups[i] = list(devices[i * per_slice:(i + 1) * per_slice])
    slice_arrays = []
    for key in sorted(groups):
        grp = groups[key]
        if len(grp) != per_slice:
            raise ValueError(f"slice {key} has {len(grp)} devices, "
                             f"expected {per_slice}")
        slice_arrays.append(
            np.asarray(grp, dtype=object).reshape(
                (dp_inner, spec.fsdp, spec.pp, spec.tp)))
    return np.concatenate(slice_arrays, axis=0)


def build_hybrid_mesh(spec=None, devices=None, num_slices="auto"):
    """Build a multi-slice (ICI x DCN) `jax.sharding.Mesh`.

    Same canonical axes as `build_mesh`, but device placement is
    slice-aware: the outer factor of the dp axis spans slices (DCN) while
    fsdp/pp/tp and the inner dp factor stay within a slice (ICI).  This is
    the TPU-native analog of the reference's multi-worker scaling story
    (gRPC ring across hosts, SURVEY.md §2.4): the only cross-slice traffic
    is the per-step gradient allreduce, which tolerates DCN latency.

    ``num_slices="auto"`` (the default) detects slices from the devices'
    ``slice_index`` and degrades to plain single-slice placement whenever
    the request cannot factor over them (dp not divisible by the slice
    count, or a ragged/truncated device list), so it is always safe to
    call.  Pass an explicit ``num_slices`` to force slice-aware placement
    (raising on impossible factorings) or to emulate slices on platforms
    without ``slice_index`` via contiguous grouping (the CPU-mesh tests).
    """
    import jax

    devs = list(devices) if devices is not None else jax.devices()
    spec = (spec or MeshSpec()).resolve(len(devs))
    arr = None
    if num_slices == "auto":
        num_slices = detect_num_slices(devs)
        if num_slices > 1:
            try:
                arr = hybrid_device_array(spec, devs, num_slices)
            except ValueError as e:
                # single source of factorability rules: hybrid_device_array
                logger.warning("cannot factor mesh %s over %d slices (%s); "
                               "using single-slice placement",
                               spec.shape, num_slices, e)
                num_slices = 1
    if num_slices == 1:
        return build_mesh(spec, devices=devices)
    if arr is None:
        arr = hybrid_device_array(spec, devs, num_slices)
    mesh = jax.sharding.Mesh(arr, ALL_AXES, axis_types=_axis_types())
    logger.info("built hybrid mesh %s over %d devices in %d slices",
                dict(zip(ALL_AXES, spec.shape)), len(devs), num_slices)
    return mesh


def local_mesh_spec(num_devices=None, tp=1, pp=1, fsdp=1):
    """Convenience: all remaining devices to dp."""
    import jax
    n = num_devices or len(jax.devices())
    return MeshSpec(dp=-1, fsdp=fsdp, pp=pp, tp=tp).resolve(n)


def batch_sharding(mesh):
    """NamedSharding for a [batch, ...] input: batch split over whichever
    of dp/fsdp the mesh actually has (a partial mesh — e.g. fsdp-only in
    tests or tp-only serving meshes — must not name absent axes)."""
    import jax
    P = jax.sharding.PartitionSpec
    axes = tuple(a for a in BATCH_AXES if a in mesh.axis_names)
    return jax.sharding.NamedSharding(mesh, P(axes if axes else None))


def replicated_sharding(mesh):
    import jax
    return jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())


def put_batch(tree, sharding):
    """Place a process-local batch (pytree of host arrays) onto the mesh.

    Single-process: a plain ``jax.device_put``. Multi-process SPMD: each
    process passes ITS shard and the result is the global array spanning all
    processes (``jax.make_array_from_process_local_data``) — the device_put
    analog of the reference's per-worker feed shards flowing into a
    collective-synchronized step. Every process must contribute the same
    local batch shape; pad the ragged tail (see examples/mnist) to keep the
    jitted step's shapes static.
    """
    import jax

    if jax.process_count() <= 1:
        return jax.device_put(tree, sharding)
    if isinstance(sharding, jax.sharding.Sharding):
        return jax.tree_util.tree_map(
            lambda x: jax.make_array_from_process_local_data(sharding, x),
            tree)
    # pytree of shardings matching the batch structure
    return jax.tree_util.tree_map(
        lambda x, s: jax.make_array_from_process_local_data(s, x),
        tree, sharding)
