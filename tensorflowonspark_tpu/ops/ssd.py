"""SSD: the chunked scan of Mamba-2's selective state space (Dao & Gu,
arXiv:2405.21060, section 6), forward and backward as Pallas kernels.

A head h of P lanes reads the group `h // (H // G)` of the input and output
projections B, C (N lanes each) and runs

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T        (a [P, N] state)
    y_t = h_t C_t

The sequence is cut into chunks of L positions.  With `u = dt x` and `cs` the
chunk's inclusive running sum of `dt A` (non-increasing: dt >= 0, A < 0), a
chunk's output is its own part and what the state at its start carries in:

    y = (C B^T o exp(cs_t - cs_s)[t >= s]) u + exp(cs) o C S0
    S1 = exp(cs_L) S0 + sum_s exp(cs_L - cs_s) u_s B_s^T

`ssd_fwd` walks the chunks of a (row, group) in order on the last grid axis,
the group's heads together (they share C B^T), the states in VMEM; it writes
each chunk's starting state (`f32[B, H, chunks, N, P]`, kept as the state
transposed).  `ssd_bwd` walks them in reverse and carries the state's
gradient the same way: du, d(cs), dB, dC, from which XLA's own rules of the
running sum and of `u = dt x` give dx, d(dt) and d(A).  The forward rule
names its output `ssd_out` and the chunk states `ssd_states`
(`jax.ad_checkpoint.checkpoint_name`): a rematerialised block whose policy
saves them (`models.transformer.remat_block`) does not run `ssd_fwd` again.

The kernels take every operand heads-major (`[B, H, S, P]`, `[B, G, S, N]`
and `B` transposed, `[B, G, N, S]`), so a block is a head's or a group's
whole lanes; the running sums come lane-dense, `[B, H, S]`, a head's chunk a
row `[1, L]`, and the kernel turns a row into the column `[L, 1]` it also
needs by a select against the diagonal (exact), so that no tile is
transposed.  Products run in float32.

`ssd(..., kernel=False)` computes the same chunked algorithm in `jax.numpy`
(XLA's autodiff, no kernel): the CPU's path, as `attention_impl` falls back
for attention.  The process counters `ssd.calls.kernel` and
`ssd.calls.reference` say which a traced call took.
"""
import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tensorflowonspark_tpu import trace

_F32 = jnp.float32


def ssd(x, dt, A, B, C, chunk=128, kernel=False, interpret=None):
    """`y` [batch, S, H, P] in x's type, without the skip term `D x`.

    `x` [batch, S, H, P]; `dt` [batch, S, H] float32 (after the softplus);
    `A` [H] float32 (negative); `B`, `C` [batch, S, G, N], H a multiple of
    G.  A row is padded to whole chunks of `chunk` (zeros: no input, no
    decay) and cut back after.  `kernel`: the Pallas kernels (`interpret`:
    None = on the chip natively, elsewhere in the interpreter); else the
    same algorithm in `jax.numpy`."""
    nb, S, H, P = x.shape
    G = B.shape[2]
    if H % G:
        raise ValueError(f"{H} heads do not divide into {G} groups")
    pad = -S % chunk
    u = (x.astype(_F32) * dt[..., None]).astype(x.dtype)
    a = dt * A
    if pad:
        def padded(t):
            return jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
        u, a, B, C = padded(u), padded(a), padded(B), padded(C)
    n = (S + pad) // chunk
    cs = jnp.cumsum(a.reshape(nb, n, chunk, H), axis=2).reshape(nb, -1, H)
    if kernel:
        trace.counters().inc("ssd.calls.kernel")       # once a traced call
        if interpret is None:
            from tensorflowonspark_tpu.ops import default_interpret
            interpret = default_interpret()
        y = _ssd(u.transpose(0, 2, 1, 3), cs.transpose(0, 2, 1),
                 B.transpose(0, 2, 1, 3), C.transpose(0, 2, 1, 3), chunk,
                 bool(interpret)).transpose(0, 2, 1, 3)
    else:
        trace.counters().inc("ssd.calls.reference")
        y = _ssd_jnp(u, cs, B, C, chunk)
    return y[:, :S]


def _ssd_jnp(u, cs, B, C, L):
    """The chunked algorithm in `jax.numpy`: `u` [b, S, H, P], `cs` [b, S,
    H], `B`, `C` [b, S, G, N], S whole chunks of L."""
    nb, S, H, P = u.shape
    G, N = B.shape[2:]
    n, per = S // L, H // G
    uc = u.reshape(nb, n, L, H, P).astype(_F32)
    bh = jnp.repeat(B.reshape(nb, n, L, G, N), per, axis=3).astype(_F32)
    ch = jnp.repeat(C.reshape(nb, n, L, G, N), per, axis=3).astype(_F32)
    seg = cs.reshape(nb, n, L, H).transpose(0, 1, 3, 2)          # [b,c,h,L]
    causal = jnp.tril(jnp.ones((L, L), bool))
    decay = jnp.exp(jnp.where(causal, seg[..., :, None] - seg[..., None, :],
                              -jnp.inf))                        # [b,c,h,t,s]
    m = jnp.einsum("bclhn,bcshn->bchls", ch, bh) * decay
    y = jnp.einsum("bchts,bcshp->bcthp", m, uc)
    last = seg[..., -1]                                         # [b,c,h]
    into = jnp.einsum("bchs,bcshn,bcshp->bchnp",
                      jnp.exp(last[..., None] - seg), bh, uc)

    def carry(state, step):
        keep, add = step
        return keep[..., None, None] * state + add, state

    _, starts = jax.lax.scan(carry, jnp.zeros((nb, H, N, P), _F32),
                             (jnp.exp(last).transpose(1, 0, 2),
                              into.transpose(1, 0, 2, 3, 4)))
    y = y + jnp.exp(seg).transpose(0, 1, 3, 2)[..., None] * jnp.einsum(
        "bclhn,cbhnp->bclhp", ch, starts)
    return y.reshape(nb, S, H, P).astype(u.dtype)


# ---- the kernels ---------------------------------------------------------


def _dot(a, b):
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               preferred_element_type=_F32)


def _dot_t(a, b):
    """`a @ b^T`, contracting both operands' lanes as they lie."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=_F32)


def _diagonal(L):
    return (jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
            == jax.lax.broadcasted_iota(jnp.int32, (L, L), 1))


def _column(row):
    """A `[1, L]` row as the column `[L, 1]`."""
    return jnp.sum(jnp.where(_diagonal(row.shape[1]), row, 0.0), axis=1,
                   keepdims=True)


def _row(col):
    """A `[L, 1]` column as the row `[1, L]`."""
    return jnp.sum(jnp.where(_diagonal(col.shape[0]), col, 0.0), axis=0,
                   keepdims=True)


def _last(row):
    """The last entry of a `[1, L]` row as `[1, 1]`."""
    lane = jax.lax.broadcasted_iota(jnp.int32, row.shape, 1)
    return jnp.sum(jnp.where(lane == row.shape[1] - 1, row, 0.0), axis=1,
                   keepdims=True)


def _fwd_kernel(u_ref, cs_ref, bt_ref, c_ref, y_ref, st_ref, st_scr):
    """One chunk of one (row, group): its heads' outputs, each head's state
    at the chunk's start written out, the states carried on in VMEM."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        st_scr[...] = jnp.zeros_like(st_scr)

    per, L = u_ref.shape[1], u_ref.shape[2]
    bt = bt_ref[0, 0].astype(_F32)                      # [N, L]
    c = c_ref[0, 0].astype(_F32)                        # [L, N]
    g = _dot(c, bt)                                     # [t, s]
    causal = (jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
              >= jax.lax.broadcasted_iota(jnp.int32, (L, L), 1))
    for j in range(per):
        u = u_ref[0, j].astype(_F32)                    # [L, P]
        cr = cs_ref[0, j:j + 1, :]                      # [1, L]
        cc = _column(cr)
        st = st_scr[j]                                  # [N, P]
        m = g * jnp.exp(jnp.where(causal, cc - cr, -jnp.inf))
        y = _dot(m, u) + jnp.exp(cc) * _dot(c, st)
        y_ref[0, j] = y.astype(y_ref.dtype)
        st_ref[0, j, 0] = st
        last = _last(cr)
        st_scr[j] = jnp.exp(last) * st + _dot(bt * jnp.exp(last - cr), u)


def _bwd_kernel(u_ref, cs_ref, b_ref, bt_ref, c_ref, ct_ref, st_ref, dy_ref,
                du_ref, dcs_ref, db_ref, dc_ref, ds_scr):
    """One chunk of one (row, group), the chunks taken last first: the
    gradients of its heads' `u` and running sums and of the group's B and
    C, the gradient of the state at the chunk's start carried on."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_scr[...] = jnp.zeros_like(ds_scr)

    per, L = u_ref.shape[1], u_ref.shape[2]
    b, bt = b_ref[0, 0].astype(_F32), bt_ref[0, 0].astype(_F32)
    c, ct = c_ref[0, 0].astype(_F32), ct_ref[0, 0].astype(_F32)
    g, gt = _dot(c, bt), _dot(b, ct)                    # [t, s], [s, t]
    rows = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    at_end = jax.lax.broadcasted_iota(jnp.int32, (L, 1), 0) == L - 1
    db = jnp.zeros(b.shape, _F32)
    dc = jnp.zeros(c.shape, _F32)
    for j in range(per):
        u, dy = u_ref[0, j].astype(_F32), dy_ref[0, j].astype(_F32)
        cr = cs_ref[0, j:j + 1, :]                      # [1, L]
        cc = _column(cr)
        st0, ds1 = st_ref[0, j, 0], ds_scr[j]           # [N, P] each
        decay = jnp.exp(jnp.where(rows >= cols, cc - cr, -jnp.inf))
        decay_t = jnp.exp(jnp.where(cols >= rows, cr - cc, -jnp.inf))
        dm, dm_t = _dot_t(dy, u), _dot_t(u, dy)         # [t, s], [s, t]
        dg, dg_t = dm * decay, dm_t * decay_t
        du = _dot(gt * decay_t, dy)                     # within the chunk
        db = db + _dot(dg_t, c)
        dc = dc + _dot(dg, b)
        z = dg * g                                      # d(cs_t - cs_s)
        dcc = jnp.sum(z, axis=1, keepdims=True)
        dcr = -jnp.sum(z, axis=0, keepdims=True)
        ecc = jnp.exp(cc)                               # the carried state
        dcc = dcc + ecc * jnp.sum(dy * _dot(c, st0), axis=1, keepdims=True)
        dye = dy * ecc
        dc = dc + _dot_t(dye, st0)
        last = _last(cr)                                # the state passed on
        w = jnp.exp(last - cc)                          # [L, 1]
        bd = _dot(b, ds1)                               # [L, P]
        du = du + w * bd
        db = db + w * _dot_t(u, ds1)
        q = w * jnp.sum(u * bd, axis=1, keepdims=True)
        kept = jnp.sum(jnp.sum(ds1 * st0, axis=1, keepdims=True), axis=0,
                       keepdims=True)
        into_last = jnp.sum(q, axis=0, keepdims=True) + jnp.exp(last) * kept
        dcc = dcc - q + jnp.where(at_end, into_last, 0.0)
        ds_scr[j] = jnp.exp(last) * ds1 + _dot(ct, dye)
        du_ref[0, j] = du.astype(du_ref.dtype)
        dcs_ref[0, j:j + 1, :] = dcr + _row(dcc)
    db_ref[0, 0] = db.astype(db_ref.dtype)
    dc_ref[0, 0] = dc.astype(dc_ref.dtype)


def _specs(u, b, L, reverse):
    """Block specs over the grid (row, group, chunk); `reverse` walks the
    chunks last first."""
    _, H, S, P = u.shape
    G, N = b.shape[1], b.shape[3]
    per, n = H // G, S // L

    def at(c):
        return n - 1 - c if reverse else c

    return (n, {
        "heads": pl.BlockSpec((1, per, L, P), lambda i, g, c: (i, g, at(c), 0)),
        "sums": pl.BlockSpec((1, per, L), lambda i, g, c: (i, g, at(c))),
        "group": pl.BlockSpec((1, 1, L, N), lambda i, g, c: (i, g, at(c), 0)),
        "group_t": pl.BlockSpec((1, 1, N, L),
                                lambda i, g, c: (i, g, 0, at(c))),
        "states": pl.BlockSpec((1, per, 1, N, P),
                               lambda i, g, c: (i, g, at(c), 0, 0)),
    })


def _call(kernel, name, u, b, interpret, in_specs, out_specs, out_shape):
    nb, H, _, P = u.shape
    G, N = b.shape[1], b.shape[3]
    return pl.pallas_call(
        kernel, name=name, interpret=interpret, grid=(nb, G, in_specs[0]),
        in_specs=in_specs[1], out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((H // G, N, P), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")))


# Both implementations are jitted on their own, as the flash kernels are: a
# model calls them once a layer with the same shapes, and they are traced
# and lowered once.
@functools.partial(jax.jit, static_argnames=("L", "interpret"))
def _fwd_impl(u, cs, b, c, L, interpret):
    """`(y [b, H, S, P], states f32[b, H, S / L, N, P])`."""
    nb, H, _, P = u.shape
    N = b.shape[3]
    n, s = _specs(u, b, L, reverse=False)
    return _call(
        _fwd_kernel, "ssd_fwd", u, b, interpret,
        (n, [s["heads"], s["sums"], s["group_t"], s["group"]]),
        [s["heads"], s["states"]],
        [jax.ShapeDtypeStruct(u.shape, u.dtype),
         jax.ShapeDtypeStruct((nb, H, n, N, P), _F32)],
    )(u, cs, b.swapaxes(2, 3), c)


@functools.partial(jax.jit, static_argnames=("L", "interpret"))
def _bwd_impl(u, cs, b, c, states, dy, L, interpret):
    """`(du, d(cs), dB, dC)` in the operands' shapes and types."""
    n, s = _specs(u, b, L, reverse=True)
    return tuple(_call(
        _bwd_kernel, "ssd_bwd", u, b, interpret,
        (n, [s["heads"], s["sums"], s["group"], s["group_t"], s["group"],
             s["group_t"], s["states"], s["heads"]]),
        [s["heads"], s["sums"], s["group"], s["group"]],
        [jax.ShapeDtypeStruct(u.shape, u.dtype),
         jax.ShapeDtypeStruct(cs.shape, _F32),
         jax.ShapeDtypeStruct(b.shape, b.dtype),
         jax.ShapeDtypeStruct(c.shape, c.dtype)],
    )(u, cs, b, b.swapaxes(2, 3), c, c.swapaxes(2, 3), states, dy))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _ssd(u, cs, b, c, L, interpret):
    return _fwd_impl(u, cs, b, c, L, interpret)[0]


def _ssd_vjp_fwd(u, cs, b, c, L, interpret):
    y, states = _fwd_impl(u, cs, b, c, L, interpret)
    y = checkpoint_name(y, "ssd_out")
    states = checkpoint_name(states, "ssd_states")
    return y, (u, cs, b, c, states)


def _ssd_vjp_bwd(L, interpret, res, dy):
    u, cs, b, c, states = res
    return _bwd_impl(u, cs, b, c, states, dy.astype(u.dtype), L, interpret)


_ssd.defvjp(_ssd_vjp_fwd, _ssd_vjp_bwd)
