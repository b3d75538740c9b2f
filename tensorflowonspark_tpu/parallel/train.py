"""pjit train-step harness: the compute engine the examples plug into.

Replaces the reference's delegation to `tf.distribute` strategies inside the
user map_fun (SURVEY.md §2.3): here the framework owns the step — a jitted
function with explicit in/out shardings over the cluster mesh, so XLA
inserts gradient allreduce over ICI from the sharding layout alone (no
NCCL/gRPC plumbing).  Supports gradient accumulation (lax.scan over
microbatches), bfloat16 compute with float32 params, and rematerialization.
"""
import logging
from typing import Any, NamedTuple

from . import mesh as mesh_mod
from . import sharding as sharding_mod

logger = logging.getLogger(__name__)


class TrainState(NamedTuple):
    step: Any
    params: Any
    opt_state: Any


def create_train_state(params, optimizer, mesh=None, param_shardings=None):
    """Initialize TrainState, placing params/opt state on the mesh."""
    import jax
    import jax.numpy as jnp

    if mesh is not None:
        if param_shardings is None:
            param_shardings = sharding_mod.infer_param_shardings(params, mesh)
        params = sharding_mod.shard_params(params, param_shardings)
    opt_state = optimizer.init(params)
    return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                      opt_state=opt_state)


def make_train_step(loss_fn, optimizer, mesh=None, param_shardings=None,
                    grad_accum=1, compute_dtype=None, donate=True,
                    example_params=None, layouts=None):
    """Build the jitted train step.

    `loss_fn(params, batch, rng) -> scalar loss` — the mean over the LOCAL
    shard; with the batch sharded over dp/fsdp and params replicated (or
    sharded), jit's sharding propagation makes XLA emit the gradient
    allreduce automatically.

    An optimizer exposing a single-pass ``apply(grads, state, params) ->
    (params, state)`` (ops/fused_optim's adamw_fused/lion_fused, via
    ``optim.make_optimizer``) takes that path instead of
    ``update`` + ``optax.apply_updates``: the parameter write happens
    inside the fused kernel's one pass over the state, and jit donation
    recycles the old param/moment buffers.

    ``example_params`` (arrays or ShapeDtypeStructs matching the real
    parameters) is only needed with `param_shardings` AND an optimizer
    whose state the shardings alone cannot place — optim8bit's quantized
    moments, which then shard along their block axis instead of
    replicating (see _quantized_shardings).

    ``layouts`` — the SAME pytree the 8-bit optimizer was built with
    (``optim8bit.layouts_for_shardings(params, shardings)``); declares
    that each param's quantized state uses the shard-aligned block
    layout, so it shards by the param's FULL spec (fsdp and tp axes).
    Explicit on purpose: the aligned payload's shape coincides with the
    row-major one in the common case, so it cannot be detected.

    `loss_fn` may also return `(loss, aux)`, `aux` a dict of scalars that
    add up over microbatches (counts): they join `metrics` under their own
    names.  Where `loss_fn.counters` names some of them, the step object
    hands each step's values to `trace.count_when_ready`, which adds them
    to the process's counters once the device has them: no host callback
    inside the step, no sync in the loop (the `moe.*` routing counters).

    Returns `train_step(state, batch, rng) -> (state, metrics)`.
    """
    import jax
    import jax.numpy as jnp

    def _loss(params, batch, rng):
        if compute_dtype is not None:
            params = jax.tree_util.tree_map(
                lambda x: x.astype(compute_dtype)
                if hasattr(x, "astype") and jnp.issubdtype(x.dtype, jnp.floating)
                else x, params)
        out = loss_fn(params, batch, rng)
        return out if isinstance(out, tuple) else (out, {})

    def _counted(step):
        names = tuple(getattr(loss_fn, "counters", ()))
        return _CountedStep(step, names) if names else step

    def _step(state, batch, rng):
        if grad_accum > 1:
            micro = jax.tree_util.tree_map(
                lambda x: x.reshape((grad_accum, x.shape[0] // grad_accum)
                                    + x.shape[1:]), batch)

            def body(carry, mb):
                (loss, aux), g = jax.value_and_grad(_loss, has_aux=True)(
                    state.params, mb, rng)
                acc_loss, acc_g = carry
                return (acc_loss + loss,
                        jax.tree_util.tree_map(jnp.add, acc_g, g)), aux

            zeros = jax.tree_util.tree_map(jnp.zeros_like, state.params)
            (loss, grads), aux = jax.lax.scan(body, (0.0, zeros), micro)
            loss = loss / grad_accum
            grads = jax.tree_util.tree_map(lambda g: g / grad_accum, grads)
            aux = jax.tree_util.tree_map(lambda x: x.sum(0), aux)
        else:
            (loss, aux), grads = jax.value_and_grad(_loss, has_aux=True)(
                state.params, batch, rng)

        import optax
        fused_apply = getattr(optimizer, "apply", None)
        with jax.named_scope("optimizer"):
            if callable(fused_apply):
                # single-pass fused optimizer: param write fused into the
                # kernel's one pass over grad/moments (no apply_updates
                # pass).  Over a mesh each leaf's kernel is shard_mapped by
                # the param's own sharding (Mosaic cannot be
                # GSPMD-partitioned).
                params, opt_state = fused_apply(
                    grads, state.opt_state, state.params,
                    shardings=fused_param_sh)
            else:
                updates, opt_state = optimizer.update(
                    grads, state.opt_state, state.params)
                params = optax.apply_updates(state.params, updates)
            # the fused path computes this same reduction for its clip
            # scale; XLA CSEs the two, so the metric stays free there
            grad_norm = optax.global_norm(grads)
        new_state = TrainState(step=state.step + 1, params=params,
                               opt_state=opt_state)
        metrics = {"loss": loss, "grad_norm": grad_norm, **aux}
        return new_state, metrics

    fused_param_sh = None   # NamedSharding per param leaf, over a mesh
    if mesh is None and param_shardings is not None:
        # derive the mesh from the shardings rather than silently
        # compiling an unsharded step
        leaves = jax.tree_util.tree_leaves(param_shardings)
        mesh = next((s.mesh for s in leaves if hasattr(s, "mesh")), None)
    if mesh is None:
        return _counted(jax.jit(_step, donate_argnums=(0,) if donate else ()))

    from jax.sharding import NamedSharding, PartitionSpec
    repl = NamedSharding(mesh, PartitionSpec())
    batch_shard = mesh_mod.batch_sharding(mesh)
    if param_shardings is None:
        if callable(getattr(optimizer, "apply", None)):
            # fused-optimizer path: pallas_call is a custom call GSPMD
            # cannot partition, so sharding does not propagate through it
            # the way it does through the optax update — left unpinned,
            # the compiler picks fresh output shardings and the donated
            # state aliases fail at runtime on mismatched shard sizes.
            # Pin the state outputs to the incoming placement, derived
            # from the first state actually passed in.
            cache = {}

            def _jitted(state):
                nonlocal fused_param_sh
                if "fn" not in cache:
                    state_sh = jax.tree_util.tree_map(
                        lambda x: x.sharding
                        if isinstance(x.sharding, NamedSharding) else repl,
                        state)
                    fused_param_sh = state_sh.params
                    cache["fn"] = jax.jit(
                        _step,
                        in_shardings=(state_sh, batch_shard, repl),
                        out_shardings=(state_sh, repl),
                        donate_argnums=(0,) if donate else ())
                return cache["fn"]

            def step(state, batch, rng):
                return _jitted(state)(state, batch, rng)

            # AOT like the plain-jit returns of this function
            step.lower = lambda state, batch, rng: _jitted(state).lower(
                state, batch, rng)
            return _counted(step)
        state_shardings = None  # let jit infer from input placement
        in_shardings = (None, batch_shard, repl)
        out_shardings = (None, repl)
    else:
        fused_param_sh = param_shardings
        state_shardings = TrainState(
            step=repl, params=param_shardings,
            opt_state=_opt_state_shardings(optimizer, param_shardings, repl,
                                           example_params, layouts))
        in_shardings = (state_shardings, batch_shard, repl)
        out_shardings = (state_shardings, repl)

    return _counted(jax.jit(_step, in_shardings=in_shardings,
                            out_shardings=out_shardings,
                            donate_argnums=(0,) if donate else ()))


class _CountedStep:
    """A step (jitted, lowered or compiled) of a loss function that names
    `counters` among its aux metrics: calls and everything else go through
    to the step itself; after a call the named metrics of that step are
    handed to `trace.count_when_ready`; `lower(...)` and `compile()` give
    the same again, so an ahead-of-time compiled step counts too."""

    def __init__(self, step, names):
        self._step, self._names = step, names

    def __call__(self, *args, **kwargs):
        from tensorflowonspark_tpu import trace

        out = self._step(*args, **kwargs)
        trace.count_when_ready({k: out[1][k] for k in self._names})
        return out

    def lower(self, *args, **kwargs):
        return _CountedStep(self._step.lower(*args, **kwargs), self._names)

    def compile(self, *args, **kwargs):
        return _CountedStep(self._step.compile(*args, **kwargs), self._names)

    def __getattr__(self, name):
        return getattr(self._step, name)


def _opt_state_shardings(optimizer, param_shardings, repl,
                         example_params=None, layouts=None):
    """Mirror param shardings onto optimizer slots (mu/nu mirror the param
    tree and inherit its shardings; scalar slots like counts replicate).

    The fused single-pass optimizers (ops/fused_optim's FusedAdamWState /
    FusedLionState) are placed by the NamedTuple recursion below: their
    moments keep each parameter's exact shape and mirror the param
    pytree, so every moment shards by its param's OWN spec — fsdp and tp
    axes alike, the placement f32 optax moments get — and the kernel's
    blocking of a leaf happens per shard inside the jitted step with
    no cross-shard blocks (the fused analog of optim8bit's shard-aligned
    layouts, with alignment by construction instead of a layouts= knob).

    ``example_params`` (a pytree of arrays or ShapeDtypeStructs matching
    the real parameters) enables shape-aware placement for state the
    shardings alone cannot describe — today that is optim8bit's
    blockwise-quantized moments, which shard along their flat block axis
    when the divisibility works out (see _quantized_shardings)."""
    import jax
    import jax.numpy as jnp

    if example_params is not None:
        shapes = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
            example_params)
        state_shapes = jax.eval_shape(optimizer.init, shapes)
        return _map_state(state_shapes, param_shardings, repl,
                          param_shapes=shapes, layouts=layouts)
    dummy = jax.tree_util.tree_map(lambda s: jnp.zeros(()), param_shardings)
    try:
        state = optimizer.init(dummy)
    except ValueError as e:
        # e.g. adamw8bit built with layouts=: its init is shape-dependent
        # and cannot run on placeholder scalars
        raise ValueError(
            "deriving optimizer-state shardings from placeholder scalar "
            "params failed — an optimizer with shape-dependent state "
            "(e.g. adamw8bit with layouts=) needs example_params passed "
            "to make_train_step") from e
    return _map_state(state, param_shardings, repl)


def _map_state(state, param_shardings, repl, param_shapes=None,
               layouts=None):
    import jax

    params_struct = jax.tree_util.tree_structure(param_shardings)
    if jax.tree_util.tree_structure(state) == params_struct:
        return param_shardings
    if _is_params_shaped_quantized(state, params_struct):
        # a quantized-moments tree mirroring the params (ANY container
        # type — dict, NamedTuple, list); checked BEFORE the NamedTuple
        # recursion because Quantized is itself a NamedTuple and naive
        # descent would walk into its q/scale fields and lose the
        # params pairing
        if param_shapes is not None:
            return _quantized_shardings(state, param_shardings, repl,
                                        param_shapes, layouts)
        logger.warning(
            "8-bit optimizer state is replicated under explicit param "
            "shardings; pass example_params to make_train_step to shard "
            "it along the block axis")
        return jax.tree_util.tree_map(lambda _: repl, state)
    if hasattr(state, "_fields"):  # NamedTuple (ScaleByAdamState etc.)
        return type(state)(*(_map_state(getattr(state, f), param_shardings,
                                        repl, param_shapes, layouts)
                             for f in state._fields))
    if isinstance(state, (tuple, list)):
        return type(state)(_map_state(s, param_shardings, repl, param_shapes,
                                      layouts)
                           for s in state)
    if _has_quantized(state):
        if param_shapes is not None:
            # shape-aware path (make_train_step(..., example_params=...)):
            # each param's quantized moments shard along their flat block
            # axis when each mesh shard owns a whole number of blocks
            return _quantized_shardings(state, param_shardings, repl,
                                        param_shapes, layouts)
        # optim8bit state without shape info (checked AFTER container
        # recursion so only the subtrees that actually hold Quantized
        # replicate — a chained f32 ema/accumulator state still gets
        # param shardings): blockwise-quantized payloads are flat
        # [n_blocks, block] views, and without the parameter shapes the
        # divisibility cannot be checked, so they are REPLICATED (loudly
        # — full-size int8 state per chip; still 4x smaller than
        # replicated f32, but NOT sharded like f32 moments would be
        # under fsdp).  Pass example_params to make_train_step for the
        # sharded placement.
        logger.warning(
            "8-bit optimizer state is replicated under explicit param "
            "shardings; pass example_params to make_train_step to shard "
            "it along the block axis")
    return jax.tree_util.tree_map(lambda _: repl, state)


def _quantized_shardings(q_state_shapes, param_shardings, repl,
                         param_shapes, layouts=None):
    """Shardings for a params-shaped tree of Quantized shape-structs.

    Preferred route — shard-aligned layout, declared via ``layouts``
    (the same tree the optimizer was built with): each param's blocks
    were computed over its logical shards (shard-major flatten), so
    q/scale shard on dim 0 by the param's FULL spec (fsdp AND tp axes)
    with zero extra communication.  The layout is NEVER guessed from
    shapes: an aligned payload's shape coincides with the row-major one
    whenever each shard's elements are a block multiple (the common
    production case), and sharding a row-major payload by a multi-dim
    spec would make GSPMD reshard the int8 state every step.  A layout
    that doesn't match the declared sharding or the payload shape is an
    error, not a silent fallback.

    Fallback — dim-0-only: a layout-less payload under fsdp-style row
    sharding still shards on its block axis when each shard owns a whole
    number of blocks (row-major flatten IS shard-major there).  Anything
    else (a TP axis in the spec without a declared layout, indivisible
    blocks) replicates that param's state, loudly.
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec
    from tensorflowonspark_tpu.optim8bit import (
        Quantized, expected_blocks, shard_layout)

    def per_param(sharding, qt, pshape, layout):
        spec = tuple(getattr(sharding, "spec", ()) or ())
        mesh = getattr(sharding, "mesh", None)
        n_blocks, block = qt.q.shape
        shape = tuple(pshape.shape)
        if layout is not None and any(n > 1 for n in layout):
            if layout != shard_layout(shape, sharding):
                raise ValueError(
                    f"declared quantized-state layout {layout} does not "
                    f"match sharding {spec} for param shape {shape} "
                    "(build both from optim8bit.layouts_for_shardings "
                    "with the same shardings)")
            if n_blocks != expected_blocks(shape, layout, block):
                raise ValueError(
                    f"quantized payload {tuple(qt.q.shape)} for param "
                    f"shape {shape} was not built with layout {layout} "
                    "(pass the same layouts= to the optimizer and "
                    "make_train_step)")
            axes = []
            for entry in spec:
                names = (() if entry is None else entry
                         if isinstance(entry, tuple) else (entry,))
                axes.extend(a for a in names if mesh.shape.get(a, 1) > 1)
            s = NamedSharding(mesh, PartitionSpec(tuple(axes), None))
            return Quantized(q=s, scale=s)
        if (mesh is not None and spec and spec[0] is not None
                and all(a is None for a in spec[1:])):
            axis = spec[0]
            n_shards = mesh.shape[axis] if not isinstance(axis, tuple) else 0
            if n_shards and n_blocks % n_shards == 0:
                s = NamedSharding(mesh, PartitionSpec(axis, None))
                return Quantized(q=s, scale=s)
        if any(a is not None for a in spec):
            # the documented loud fallback: a sharded param whose
            # quantized state cannot ride the block axis (layout-less
            # TP sharding or indivisible block count) replicates —
            # build the optimizer with optim8bit.layouts_for_shardings
            # and pass layouts= to make_train_step to shard it
            logger.warning(
                "quantized optimizer state for a param sharded %s "
                "(%d blocks) cannot shard along its block axis; "
                "replicating that param's int8 state (build the "
                "optimizer with layouts=optim8bit.layouts_for_shardings "
                "and pass layouts= to make_train_step)", spec, n_blocks)
        return Quantized(q=repl, scale=repl)

    if layouts is None:
        layouts = jax.tree_util.tree_map(lambda _: None, param_shardings)
    return jax.tree_util.tree_map(
        per_param, param_shardings, q_state_shapes, param_shapes, layouts,
        is_leaf=lambda x: isinstance(x, Quantized))


def _has_quantized(state):
    try:
        from tensorflowonspark_tpu.optim8bit import Quantized
    except Exception:
        return False
    import jax
    found = []
    jax.tree_util.tree_map(
        lambda x: found.append(True) if isinstance(x, Quantized) else None,
        state, is_leaf=lambda x: isinstance(x, Quantized))
    return bool(found)


def _is_params_shaped_quantized(state, params_struct):
    """True when `state` mirrors the params tree with a Quantized subtree
    at every leaf position — the shape of optim8bit's mu/nu_sqrt."""
    try:
        from tensorflowonspark_tpu.optim8bit import Quantized
    except Exception:
        return False
    try:
        flat = params_struct.flatten_up_to(state)
    except (ValueError, TypeError):
        return False
    return bool(flat) and all(isinstance(x, Quantized) for x in flat)


def make_eval_step(forward_fn, mesh=None):
    """Jitted forward/eval step with batch sharded over dp."""
    import jax

    if mesh is None:
        return jax.jit(forward_fn)
    from jax.sharding import NamedSharding, PartitionSpec
    return jax.jit(
        forward_fn,
        in_shardings=(NamedSharding(mesh, PartitionSpec()),
                      mesh_mod.batch_sharding(mesh)),
        out_shardings=mesh_mod.batch_sharding(mesh))


def feed_consensus(has_data):
    """Global stop-consensus for synchronous training over an uneven feed.

    Every process calls this once per step with whether ITS feed produced a
    batch; returns True only while every process has data. The first dry
    process flips the whole cluster to stop on the same step, so sharded
    collectives never go ragged. This replaces the reference's heuristic of
    training only 90% of the per-worker steps to dodge uneven RDD partitions
    (reference: examples/mnist/keras/mnist_spark.py:58-64) with an exact
    consensus; the dropped remainder is bounded by the feed imbalance, and
    callers should df.terminate() to drain it.

    Callers MUST pair this with a bounded feed probe
    (``DataFeed.next_batch(bs, timeout=...)``), never a blocking read: a
    worker blocked in q.get() waiting for records that only arrive after its
    peers advance would never reach this collective, deadlocking the cluster
    until feed_timeout.

    Single-process clusters short-circuit (no collective). Cross-process it
    is one tiny allgather over the cluster fabric (Gloo on CPU hosts, ICI/DCN
    on TPU) per step.
    """
    import jax

    if jax.process_count() <= 1:
        return bool(has_data)
    import numpy as np
    from jax.experimental import multihost_utils

    flags = multihost_utils.process_allgather(
        np.asarray([1 if has_data else 0], np.int32))
    return bool(np.asarray(flags).min())
