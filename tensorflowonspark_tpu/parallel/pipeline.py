"""Pipeline parallelism: GPipe-style microbatch pipelining over the ``pp``
mesh axis, built from `shard_map` + `lax.ppermute` (net-new vs the reference,
which has no model parallelism — SURVEY.md §2.3).

Each device owns one stage's parameters (leading [n_stages] dim sharded over
pp).  Microbatches flow through the ring: at tick t, stage s processes
microbatch t-s and hands its activation to stage s+1 via a neighbor
ppermute (one ICI hop on a TPU torus).  The schedule runs
T = n_micro + n_stages - 1 ticks; bubbles are the standard GPipe overhead
(n_stages-1)/T.  The whole schedule is a `lax.scan`, so it is jit-compatible
and differentiable (ppermute's transpose is the reverse ppermute, giving the
correct backward pipeline automatically).

Composes with data parallelism: run under a mesh with dp>1 and shard the
microbatch batch dim over dp in `in_specs`.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax


def stack_stage_params(per_stage_params):
    """Stack a list of per-stage param pytrees into leaves with a leading
    [n_stages] dim (to be sharded over the pp axis)."""
    return jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves), *per_stage_params)


def _pipeline_local(params, x, *, stage_fn, axis, n_micro):
    """shard_map-local body: `params` leaves are [1, ...] (this stage's
    slice); `x` is [n_micro, micro_batch, ...] (replicated over pp)."""
    n_stages = lax.psum(1, axis)
    stage_id = lax.axis_index(axis)
    local_params = jax.tree_util.tree_map(lambda p: p[0], params)
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    y0 = stage_fn(local_params, x[0])
    out_shape = y0.shape  # stage output shape == stage input shape (residual nets)
    del y0

    def tick(carry, t):
        recv, outputs = carry
        x_t = lax.dynamic_index_in_dim(
            x, jnp.clip(t, 0, n_micro - 1), axis=0, keepdims=False)
        inp = jnp.where(stage_id == 0, x_t, recv)
        y = stage_fn(local_params, inp)
        m = t - (n_stages - 1)
        is_last = stage_id == n_stages - 1
        updated = lax.dynamic_update_index_in_dim(
            outputs, y.astype(outputs.dtype),
            jnp.clip(m, 0, n_micro - 1), axis=0)
        outputs = jnp.where((m >= 0) & is_last, updated, outputs)
        recv_next = lax.ppermute(y, axis, perm)
        return (recv_next, outputs), None

    T = n_micro + n_stages - 1
    outputs = jnp.zeros((n_micro,) + tuple(out_shape), x.dtype)
    recv = jnp.zeros_like(x[0])
    (recv, outputs), _ = lax.scan(tick, (recv, outputs), jnp.arange(T))
    # Only the last stage holds real outputs; psum over pp replicates them
    # (other stages contribute zeros).
    return lax.psum(outputs, axis)


def pipeline_apply(stage_fn, stacked_params, x_micro, mesh, axis="pp",
                   batch_axes=("dp", "fsdp")):
    """Apply an N-stage pipeline.

    stage_fn(stage_params, x) -> y with y.shape == x.shape
    stacked_params: leaves [n_stages, ...] (see `stack_stage_params`)
    x_micro: [n_micro, micro_batch, ...]; micro_batch is sharded over
             `batch_axes` for dp composition.
    """
    from jax.sharding import PartitionSpec as P

    shard_map = jax.shard_map

    n_micro = x_micro.shape[0]
    param_specs = jax.tree_util.tree_map(
        lambda p: P(axis, *([None] * (p.ndim - 1))), stacked_params)
    x_spec = P(None, batch_axes, *([None] * (x_micro.ndim - 2)))

    fn = functools.partial(_pipeline_local, stage_fn=stage_fn, axis=axis,
                           n_micro=n_micro)
    return shard_map(fn, mesh=mesh, in_specs=(param_specs, x_spec),
                     out_specs=x_spec, check_vma=False)(stacked_params, x_micro)
