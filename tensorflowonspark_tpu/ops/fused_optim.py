"""Single-pass fused optimizer kernels (Pallas): AdamW and Lion.

The optax update for the flagship LM is a chain of elementwise
transforms — clip -> moments -> weight decay -> lr scale -> apply — and
each link reads and writes the full f32 optimizer state in HBM.  At
0.87B params that is several complete passes over ~10 GB of state per
step, pure bandwidth the matmuls cannot hide.  These kernels apply
the ENTIRE update in one pass per parameter block:

    read  grad, param, mu[, nu]   (once)
    write param, mu[, nu]         (once)

Global-norm clipping folds in as a pre-computed scalar: one cheap
reduction pass over the gradients (``optax.global_norm``, which the
train step's metrics already compute — XLA CSEs the two), then the
scale rides into the fused apply as an SMEM scalar.  Bias corrections
and the schedule's learning rate enter the same way, so the kernel body
is a single VPU expression per block.

HBM traffic model (f32 everything, P = param count, one step):

    optax adamw chain   ~>=10 P reads/writes (clip copy, scale_by_adam
                        in/out, decayed-weights add, lr scale, apply)
    fused kernel          7 P  (4 reads + 3 writes), 5 P with bf16 mu

Exposed as an optax-compatible ``GradientTransformation`` with one
extra method:

    ``update(grads, state, params)`` -> (updates, state)   # optax protocol
    ``apply(grads, state, params)``  -> (new_params, state) # single-pass

``update`` keeps every optax composition working (tests verify parity
against ``optax.chain(clip_by_global_norm, adamw)`` step-for-step);
``apply`` additionally fuses the final ``optax.apply_updates`` add into
the kernel (the parameter write shares the pass), so no ``updates`` tree
ever materializes — the path ``parallel.train.make_train_step`` takes
automatically; the train step's jit donation recycles the old
param/moment buffers.  Both run the SAME kernel body, so the CPU test
tier (interpret=True) exercises the real kernel code.

State layout: the moments keep each parameter's exact shape and mirror
the parameter pytree (``FusedAdamWState.mu/nu``), so under explicit
shardings the state shards by the param's OWN spec — fsdp and tp axes
alike (``parallel.train._opt_state_shardings`` maps the mirrored tree
onto the param shardings, the same placement rule f32 optax moments
get).  Over a mesh ``make_train_step`` passes those shardings to
``apply(..., shardings=)`` and each leaf's kernel runs under
``shard_map`` by its param's spec — Mosaic kernels cannot be partitioned
by GSPMD (see ``_run_leaf``); ``update`` takes no shardings and is the
single-device form.

Blocking (PR 33): a leaf whose shape allows it reaches the kernel as its
own 2-D view ``[prod(shape[:-1]), shape[-1]]``, which the compiler makes
by a bitcast, blocked ``(256, 512)`` over a two-axis grid; the last
block of either axis may be ragged (GPT-2's ``50257 x 1280`` table).
``_direct_view`` is the shape test and the only selector: ``ndim >= 2``,
a last dim of a lane tile or more, leading dims that fold into rows over
whole sublane tiles.  Every other leaf (biases, norm scales, a router
``[2304, 64]``, conv taps ``[2048, 3]``) is flattened, padded and packed
to ``[n, 128]`` rows and laid back, as every leaf was before.  That
packing is NOT a bitcast on the TPU: an ``f32[1280,5120]`` and an
``f32[51200,128]``, both in tiles of (8, 128), hold their elements in
different places, so each ``reshape`` of g, p, mu, nu in and of the three
results out was a pass over the leaf: 48 B a parameter beside the
kernel's 24, 47.2 ms of the 462 ms GPT-2 large step against the kernel's
21.7 (ledger, PR 32), and the pad and slice of a leaf that is no whole
number of blocks another 10.  The counters ``adamw.elems.direct`` /
``adamw.elems.packed`` (``trace.py``) say how many elements took each
path.  One residue: where the device's own layout of a leaf is
column-major (``f32[1280,50257]{0,1}``, GPT-2's untied head: the chip
pads the shorter way), XLA copies it to the kernel's row-major operands
and back.

In place: the moments alias their outputs (``input_output_aliases``) and,
in ``apply``, the parameter does, so under the train step's donation
the kernel writes the state where it read it.  Without the aliases the
kernel's fresh outputs were copied into the donated buffers, one
``copy`` an output of every large leaf; with them a caller that does NOT
donate gets XLA's protective copy of the inputs and the same numbers.
Donate parameters and state in the order the results come back
(``make_train_step`` does: one ``TrainState`` in and out), or a donated
buffer is matched to another result of the same shape and copied across.

``mu_dtype="bfloat16"`` stores the first moment in bf16 exactly like
``optax.adamw(mu_dtype=...)`` (compute stays f32 in VMEM; the narrow
store halves that operand's traffic).  The second moment stays at the
parameter dtype, matching optax.  For MEMORY-bound settings prefer
``optim8bit.adamw8bit`` (int8 state, 4x smaller); this kernel is the
SPEED choice (fewest HBM passes, full-precision state).
"""
import functools
import math
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tensorflowonspark_tpu import trace

LANE = 128               # TPU lane width: last dim of every packed block
DEFAULT_BLOCK_ROWS = 256  # rows a block: a multiple of _SUBLANE
_SUBLANE = 16            # sublane multiple that tiles bf16 and f32 alike
# lanes a block of a leaf taken in its own layout: seven operands of
# (256, 512) f32, double-buffered, are 7.3 MB of the 16 MB scoped VMEM.  On
# the v5e the kernel reads 650-670 GB/s of the 819 whatever the block, from
# (256, 128) to (128, 1024) or whole rows (chip runs, PR 33: PERF.md)
_DIRECT_LANES = 4 * LANE


class FusedAdamWState(NamedTuple):
    """Fused-AdamW state; mu/nu mirror the param pytree shape-for-shape
    (so state shardings mirror param shardings — see module doc)."""
    count: Any
    mu: Any
    nu: Any


class FusedLionState(NamedTuple):
    count: Any
    mu: Any


class FusedOptimizer(NamedTuple):
    """Duck-types as `optax.GradientTransformation` (init/update) with an
    extra single-pass `apply(grads, state, params) -> (params, state)`.
    NOTE: `optax.chain` strips `apply` — fold clipping/decay in via the
    constructor arguments instead of chaining."""
    init: Callable
    update: Callable
    apply: Callable


# ---------------------------------------------------------------------------
# kernels — one block of every operand per grid step, everything f32 on the
# VPU; scalars (lr, clip scale, bias corrections) ride in SMEM
# ---------------------------------------------------------------------------

def _adamw_kernel(s_ref, g_ref, p_ref, mu_ref, nu_ref,
                  o_ref, mu_o_ref, nu_o_ref, *, b1, b2, eps, wd,
                  write_param):
    lr = s_ref[0, 0]
    clip = s_ref[0, 1]
    c1 = s_ref[0, 2]          # 1 - b1**t  (bias corrections, host-side pow)
    c2 = s_ref[0, 3]
    g = g_ref[:].astype(jnp.float32) * clip
    # identical expression order to optax.tree_update_moment for tight parity
    mu = (1.0 - b1) * g + b1 * mu_ref[:].astype(jnp.float32)
    nu = (1.0 - b2) * (g * g) + b2 * nu_ref[:].astype(jnp.float32)
    upd = (mu / c1) / (jnp.sqrt(nu / c2) + eps)
    if wd or write_param:
        p = p_ref[:].astype(jnp.float32)
    if wd:
        upd = upd + wd * p
    if write_param:
        o_ref[:] = (p - lr * upd).astype(o_ref.dtype)
    else:
        o_ref[:] = (-lr * upd).astype(o_ref.dtype)
    mu_o_ref[:] = mu.astype(mu_o_ref.dtype)
    nu_o_ref[:] = nu.astype(nu_o_ref.dtype)


def _lion_kernel(s_ref, g_ref, p_ref, mu_ref, o_ref, mu_o_ref,
                 *, b1, b2, wd, write_param):
    lr = s_ref[0, 0]
    clip = s_ref[0, 1]
    g = g_ref[:].astype(jnp.float32) * clip
    mu = mu_ref[:].astype(jnp.float32)
    upd = jnp.sign((1.0 - b1) * g + b1 * mu)     # sign of the interpolation
    new_mu = (1.0 - b2) * g + b2 * mu            # the stored momentum
    if wd or write_param:
        p = p_ref[:].astype(jnp.float32)
    if wd:
        upd = upd + wd * p
    if write_param:
        o_ref[:] = (p - lr * upd).astype(o_ref.dtype)
    else:
        o_ref[:] = (-lr * upd).astype(o_ref.dtype)
    mu_o_ref[:] = new_mu.astype(mu_o_ref.dtype)


# ---------------------------------------------------------------------------
# per-leaf driver: the leaf as [rows, lanes] in its own layout where its
# shape allows, else packed to (rows, LANE) with a padded tail; run the grid
# ---------------------------------------------------------------------------

def _direct_view(shape):
    """`(rows, lanes)` of the 2-D view `[prod(shape[:-1]), shape[-1]]` where
    the compiler makes that view of the leaf by a bitcast, else None: the
    last dim fills a lane tile, and a leading dim folds into rows only over
    whole sublane tiles (of bf16's 16 rows, which holds f32's 8 too).  The
    shape is the only selector (as `flash_attention._heads_a_block`): biases,
    norm scales, a router `[2304, 64]`, taps `[2048, 3]` are packed."""
    if len(shape) < 2 or shape[-1] < LANE:
        return None
    if len(shape) > 2 and shape[-2] % _SUBLANE:
        return None
    return math.prod(shape[:-1]), shape[-1]


def _block_rows_for(n, block_rows):
    """Rows per grid step of a packed leaf: the default, shrunk for small
    params so a bias vector does not pad out to a full block
    (sublane-multiple so one tile size serves f32 and bf16 operands)."""
    rows = -(-n // LANE)
    return min(block_rows, -(-rows // _SUBLANE) * _SUBLANE)


def _to_blocks(x, bm):
    flat = x.reshape(-1)
    per = bm * LANE
    padded = -(-flat.shape[0] // per) * per
    if padded != flat.shape[0]:
        flat = jnp.pad(flat, (0, padded - flat.shape[0]))
    return flat.reshape(-1, LANE)


def _from_blocks(y, shape):
    n = math.prod(shape) if shape else 1
    return y.reshape(-1)[:n].reshape(shape)


def _run_leaf(kernel, scalars, arrays, out_dtypes, block_rows, interpret,
              write_param, sharding=None):
    """Run `kernel` over same-shaped leaf `arrays` (g, p, the moments).

    `arrays[0]` supplies the logical shape; outputs are the first
    `len(out_dtypes)` kernel refs after the inputs (the parameter or the
    update, then the moments), in the leaf's shape.

    Direct (`_direct_view`): every operand is the leaf's own
    `[rows, lanes]`, blocked `(block_rows, _DIRECT_LANES)` (or the whole
    dim where it is smaller) over a two-axis grid with `cdiv` on both: the
    bodies are elementwise, so what a ragged last block reads beyond the
    leaf is never written back.  Packed: flattened, zero-padded to whole
    blocks of `(bm, LANE)` and sliced back, a pass over the leaf each way;
    both kernels map zero grad/state to zero output (eps keeps the adam
    quotient finite), so the pad never NaNs.  The process counters
    `adamw.elems.direct` / `adamw.elems.packed` (`trace.py`) count the
    elements by the path, each time a leaf's call is traced.

    The moments alias their outputs, and under `write_param` the parameter
    does: with the state donated (`make_train_step`) the kernel updates it
    in its own buffers; a caller that does not donate gets XLA's
    protective copy and the same numbers.

    `sharding` — the leaf's NamedSharding when the caller jits over a
    mesh (None/False: a single device).  Mosaic refuses to be partitioned
    by GSPMD ("Mosaic kernels cannot be automatically partitioned" — the
    interpreter's plain-XLA lowering never showed it), so there the leaf
    runs under shard_map by the param's own spec: each device blocks and
    updates its LOCAL shard, whose shape is what `_direct_view` sees (a
    replicated leaf is updated redundantly on every device, which is what
    data parallelism means), and the outputs leave with the same spec, so
    the train step's donated state aliases line up.
    """
    def local(scalars, *arrays):
        shape = arrays[0].shape
        n = math.prod(shape)
        view = _direct_view(shape)
        if view:
            rows, lanes = view
            bm, bl = min(block_rows, rows), min(_DIRECT_LANES, lanes)
            blocks = [a.reshape(rows, lanes) for a in arrays]
        else:
            bm, bl = _block_rows_for(n, block_rows), LANE
            blocks = [_to_blocks(a, bm) for a in arrays]
            rows, lanes = blocks[0].shape
        trace.counters().inc(
            "adamw.elems.direct" if view else "adamw.elems.packed", n)
        bspec = pl.BlockSpec((bm, bl), lambda i, j: (i, j))
        sspec = pl.BlockSpec((1, 4), lambda i, j: (0, 0),
                             memory_space=pltpu.SMEM)
        # operands: scalars, g, p, moments; outputs: p or update, moments
        first = 2 if write_param else 3
        outs = pl.pallas_call(
            kernel,
            grid=(pl.cdiv(rows, bm), pl.cdiv(lanes, bl)),
            in_specs=[sspec] + [bspec] * len(blocks),
            out_specs=[bspec] * len(out_dtypes),
            out_shape=[jax.ShapeDtypeStruct((rows, lanes), d)
                       for d in out_dtypes],
            input_output_aliases={i: i - 2
                                  for i in range(first, len(blocks) + 1)},
            interpret=interpret,
            name="adamw_fused",
        )(scalars, *blocks)
        if view:
            return tuple(o.reshape(shape) for o in outs)
        return tuple(_from_blocks(o, shape) for o in outs)

    if sharding:
        from jax.sharding import PartitionSpec

        spec = sharding.spec
        return jax.shard_map(
            local, mesh=sharding.mesh,
            in_specs=(PartitionSpec(),) + (spec,) * len(arrays),
            out_specs=(spec,) * len(out_dtypes),
            check_vma=False)(scalars, *arrays)

    return local(scalars, *arrays)


def _leaf_shardings(shardings, like):
    """`shardings` (a NamedSharding per param leaf, or None) as a tree
    `tree_map` can zip with `like`; False stands for "none"."""
    if shardings is not None:
        return shardings
    return jax.tree_util.tree_map(lambda _: False, like)


# ---------------------------------------------------------------------------
# transforms
# ---------------------------------------------------------------------------

def _resolve(value, params):
    return value(params) if callable(value) else value


def _decay_tree(params, weight_decay, mask):
    """Static per-leaf weight decay (the mask routes decay away from
    biases/norms; leaves must be static bools — they pick the compiled
    kernel variant)."""
    if not weight_decay:
        return jax.tree_util.tree_map(lambda _: 0.0, params)
    if mask is None:
        return jax.tree_util.tree_map(lambda _: float(weight_decay), params)
    m = _resolve(mask, params)
    return jax.tree_util.tree_map(
        lambda flag: float(weight_decay) if flag else 0.0, m)


def _scalars(learning_rate, count, clip_norm, b1, b2, updates):
    """Pack (lr, clip_scale, 1-b1^t, 1-b2^t) as the kernels' SMEM operand.
    One global-norm reduction when clipping — the only non-fused pass."""
    import optax

    lr = _resolve(learning_rate, count)
    t = optax.safe_int32_increment(count).astype(jnp.float32)
    if clip_norm:
        g_norm = optax.global_norm(updates)
        # optax.clip_by_global_norm: identity below the threshold, exact
        # max_norm/g_norm scale above it
        clip = jnp.where(g_norm < clip_norm, 1.0,
                         clip_norm / g_norm)
    else:
        clip = 1.0
    return jnp.stack([jnp.asarray(lr, jnp.float32),
                      jnp.asarray(clip, jnp.float32),
                      1.0 - b1 ** t,
                      1.0 - b2 ** t]).reshape(1, 4)


def _interpret_flag(interpret):
    if interpret is None:
        from tensorflowonspark_tpu.ops import default_interpret
        return default_interpret()
    return bool(interpret)


def adamw_fused(learning_rate, b1=0.9, b2=0.999, eps=1e-8,
                weight_decay=0.0, mask=None, clip_norm=None, mu_dtype=None,
                block_rows=DEFAULT_BLOCK_ROWS, interpret=None):
    """Fused AdamW: matches ``optax.chain(clip_by_global_norm(clip_norm),
    adamw(...))`` step-for-step (tests assert rtol ~1e-6 in f32) while
    touching HBM once per operand.  ``learning_rate`` may be a schedule
    (called with the update count, optax convention).  See module doc for
    the ``update`` vs ``apply`` split."""
    mu_dtype = jnp.dtype(mu_dtype) if mu_dtype is not None else None

    def init_fn(params):
        # zeros_like, not zeros: it inherits each param's placement, so
        # moments created from already-sharded params land sharded too
        return FusedAdamWState(
            count=jnp.zeros((), jnp.int32),
            mu=jax.tree_util.tree_map(
                lambda p: jnp.zeros_like(p, dtype=mu_dtype or p.dtype),
                params),
            nu=jax.tree_util.tree_map(jnp.zeros_like, params))

    def _run(updates, state, params, write_param, shardings):
        if params is None:
            if weight_decay:
                raise ValueError(
                    "adamw_fused with weight_decay requires params "
                    "(optax convention: update(grads, state, params))")
            if write_param:
                raise ValueError("apply() requires params")
            params = updates     # placeholder operand; kernels skip p reads
        interp = _interpret_flag(interpret)
        scal = _scalars(learning_rate, state.count, clip_norm, b1, b2,
                        updates)
        wds = _decay_tree(updates, weight_decay, mask)

        def leaf(g, p, mu, nu, wd, sharding):
            kern = functools.partial(
                _adamw_kernel, b1=float(b1), b2=float(b2), eps=float(eps),
                wd=float(wd), write_param=write_param)
            out_dtype = p.dtype if write_param else g.dtype
            out, new_mu, new_nu = _run_leaf(
                kern, scal, [g, p, mu, nu],
                [out_dtype, mu.dtype, nu.dtype], block_rows, interp,
                write_param, sharding)
            return _LeafOut(out, new_mu, new_nu)

        flat = jax.tree_util.tree_map(leaf, updates, params, state.mu,
                                      state.nu, wds,
                                      _leaf_shardings(shardings, updates))
        is_out = lambda x: isinstance(x, _LeafOut)  # noqa: E731
        import optax
        new_state = FusedAdamWState(
            count=optax.safe_int32_increment(state.count),
            mu=jax.tree_util.tree_map(lambda t: t.mu, flat, is_leaf=is_out),
            nu=jax.tree_util.tree_map(lambda t: t.nu, flat, is_leaf=is_out))
        out = jax.tree_util.tree_map(lambda t: t.out, flat, is_leaf=is_out)
        return out, new_state

    def update_fn(updates, state, params=None):
        return _run(updates, state, params, False, None)

    def apply_fn(updates, state, params, shardings=None):
        return _run(updates, state, params, True, shardings)

    return FusedOptimizer(init_fn, update_fn, apply_fn)


def lion_fused(learning_rate, b1=0.9, b2=0.99, weight_decay=0.0, mask=None,
               clip_norm=None, mu_dtype=None,
               block_rows=DEFAULT_BLOCK_ROWS, interpret=None):
    """Fused Lion (sign-momentum): matches ``optax.chain(clip_by_global_
    norm, lion(...))``; half the moment state of AdamW and the same
    single-pass traffic model."""
    mu_dtype = jnp.dtype(mu_dtype) if mu_dtype is not None else None

    def init_fn(params):
        # zeros_like inherits each param's placement (see adamw_fused)
        return FusedLionState(
            count=jnp.zeros((), jnp.int32),
            mu=jax.tree_util.tree_map(
                lambda p: jnp.zeros_like(p, dtype=mu_dtype or p.dtype),
                params))

    def _run(updates, state, params, write_param, shardings):
        if params is None:
            if weight_decay:
                raise ValueError(
                    "lion_fused with weight_decay requires params")
            if write_param:
                raise ValueError("apply() requires params")
            params = updates
        interp = _interpret_flag(interpret)
        scal = _scalars(learning_rate, state.count, clip_norm, b1, b2,
                        updates)
        wds = _decay_tree(updates, weight_decay, mask)

        def leaf(g, p, mu, wd, sharding):
            kern = functools.partial(
                _lion_kernel, b1=float(b1), b2=float(b2), wd=float(wd),
                write_param=write_param)
            out_dtype = p.dtype if write_param else g.dtype
            out, new_mu = _run_leaf(
                kern, scal, [g, p, mu], [out_dtype, mu.dtype],
                block_rows, interp, write_param, sharding)
            return _LeafOut(out, new_mu, None)

        flat = jax.tree_util.tree_map(leaf, updates, params, state.mu, wds,
                                      _leaf_shardings(shardings, updates))
        is_out = lambda x: isinstance(x, _LeafOut)  # noqa: E731
        import optax
        new_state = FusedLionState(
            count=optax.safe_int32_increment(state.count),
            mu=jax.tree_util.tree_map(lambda t: t.mu, flat, is_leaf=is_out))
        out = jax.tree_util.tree_map(lambda t: t.out, flat, is_leaf=is_out)
        return out, new_state

    def update_fn(updates, state, params=None):
        return _run(updates, state, params, False, None)

    def apply_fn(updates, state, params, shardings=None):
        return _run(updates, state, params, True, shardings)

    return FusedOptimizer(init_fn, update_fn, apply_fn)


class _LeafOut(NamedTuple):
    """Per-leaf kernel results (a dedicated type so tree_map's is_leaf
    cannot collide with tuple containers inside the user's param pytree —
    same device as optim8bit._UpdOut)."""
    out: Any
    mu: Any
    nu: Any
