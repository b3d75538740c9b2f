"""The Mamba-2 mixer's skip term, gate and norm by groups (Dao & Gu,
arXiv:2405.21060; `models.transformer.Mamba2Mixer`), forward and backward
as Pallas kernels:

    v = (y + D x) o silu(z)
    out = v o rsqrt(mean(v^2 over a group's lanes) + eps) o scale

`y` is the SSD scan's output, `x` the scan's input (the skip term `D x`,
one `D` a head of P lanes), `z` the gate; all three `[batch, S, H*P]` in
the activation type, and the norm runs over `groups` groups of whole heads.
In `jax.numpy` XLA's autodiff of that float32 chain writes its
intermediates (`y + D x`, `silu(z)`, the value before the norm) to HBM in
float32 and reads them back, three passes a step; the kernels read y, x, z
once and write the output once (`gate_norm_fwd`), and the backward reads
y, x, z and the output's gradient once and writes dy, dx, dz once
(`gate_norm_bwd`), recomputing v and the norm in VMEM.  The arithmetic is
float32 inside the kernels, as in `jax.numpy`; inputs and outputs keep
their types.

A grid step is one (row, block of positions, group).  The kernels take y,
and give dy, heads-major, `[batch, H, S, P]`, as `ops.ssd`'s kernels write
y and read its gradient: the transpose here and the scan's own cancel, so
neither is copied (a head's `[rows, P]` slab is a block, and a head's
lanes of x, z and the output are a static lane window of the group's
block).  `z` may be wider than `H*P` lanes, the gate its first ones: the
mixer hands over its input projection whole, so the gate is read where the
projection wrote it and not first sliced out.  The backward also writes
each block's float32 sums over its positions of the gradients of `D` (a
lane's share, folded to its head by the transpose of `jnp.repeat`) and of
`scale`, `[batch, blocks, 1, H*P]`, which XLA sums.  A row that is not
whole blocks is padded with zeros and cut back: a zero position gives a
zero output and zero sums.

`gate_norm(..., kernel=False)` is the same expression in `jax.numpy`: the
CPU's path and the mesh's.  The process counters `gate_norm.calls.kernel`
and `gate_norm.calls.reference` say which a traced call took.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tensorflowonspark_tpu import trace

_F32 = jnp.float32
_ROWS = 512        # positions a block; a shorter row takes whole tiles of 16
_TILE = 16         # a bfloat16 tile's rows


def gate_norm(y, x, z, skip, scale, groups, eps, kernel=False,
              interpret=None):
    """`RMSNorm_groups((y + D x) silu(z)) scale` in y's type.

    `y`, `x` [batch, S, H*P]; `z` [batch, S, >= H*P], the gate its first
    H*P lanes; `skip` D [H] float32, `scale` [H*P] float32; `groups`
    divides H; `eps` inside the root.  `kernel`: the Pallas kernels
    (`interpret`: None = on the chip natively, elsewhere in the
    interpreter); else the same expression in `jax.numpy`."""
    nb, S, W = y.shape
    H = skip.shape[0]
    if W % H or H % groups:
        raise ValueError(f"{W} lanes do not divide into {H} heads, or "
                         f"{H} heads into {groups} groups")
    if not kernel:
        trace.counters().inc("gate_norm.calls.reference")
        return _gate_norm_jnp(y, x, z[..., :W], skip, scale, groups, eps)
    trace.counters().inc("gate_norm.calls.kernel")     # once a traced call
    if interpret is None:
        from tensorflowonspark_tpu.ops import default_interpret
        interpret = default_interpret()
    rows = min(_ROWS, -(-S // _TILE) * _TILE)
    pad = -S % rows
    y = y.reshape(nb, S, H, W // H).transpose(0, 2, 1, 3)
    if pad:
        y = jnp.pad(y, [(0, 0), (0, 0), (0, pad), (0, 0)])
        x, z = (jnp.pad(t, [(0, 0), (0, pad), (0, 0)]) for t in (x, z))
    lanes = jnp.repeat(skip.astype(_F32), W // H)
    out = _gate_norm(y, x, z, lanes.reshape(1, W),
                     scale.astype(_F32).reshape(1, W), groups, float(eps),
                     rows, bool(interpret))
    return out[:, :S]


def _gate_norm_jnp(y, x, z, skip, scale, groups, eps):
    """The expression in `jax.numpy`, float32 throughout."""
    nb, S, W = y.shape
    H, dtype, f32 = skip.shape[0], y.dtype, _F32
    y = y.reshape(nb, S, H, W // H).astype(f32) + skip[:, None] * x.reshape(
        nb, S, H, W // H).astype(f32)
    y = (y.reshape(nb, S, W) * jax.nn.silu(z.astype(f32))).reshape(
        nb, S, groups, W // groups)
    y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), axis=-1, keepdims=True)
                          + eps)
    return (y.reshape(nb, S, W) * scale).astype(dtype)


# ---- the kernels ---------------------------------------------------------


def _head(j, y_ref, x_ref, z_ref, d_ref):
    """Head j of the block's group: its lanes, z, `a = y + D x` and
    sigmoid(z), float32 `[rows, P]`."""
    P = y_ref.shape[3]
    lanes = slice(j * P, (j + 1) * P)
    z = z_ref[0, :, lanes].astype(_F32)
    a = y_ref[0, j].astype(_F32) + d_ref[:, lanes] * x_ref[0, :, lanes].astype(
        _F32)
    return lanes, z, a, jax.nn.sigmoid(z)


def _norm(y_ref, x_ref, z_ref, d_ref, eps):
    """The group's heads' v = a silu(z) and the norm's factor `[rows, 1]`."""
    per, P = y_ref.shape[1], y_ref.shape[3]
    vs, ss = [], 0.0
    for j in range(per):
        _, z, a, sig = _head(j, y_ref, x_ref, z_ref, d_ref)
        vs.append(a * (z * sig))
        ss = ss + jnp.sum(vs[-1] * vs[-1], axis=1, keepdims=True)
    return vs, jax.lax.rsqrt(ss / (per * P) + eps)


def _fwd_kernel(y_ref, x_ref, z_ref, d_ref, s_ref, o_ref, *, eps):
    vs, r = _norm(y_ref, x_ref, z_ref, d_ref, eps)
    P = y_ref.shape[3]
    for j, v in enumerate(vs):
        lanes = slice(j * P, (j + 1) * P)
        o_ref[0, :, lanes] = (v * r * s_ref[:, lanes]).astype(o_ref.dtype)


def _bwd_kernel(y_ref, x_ref, z_ref, d_ref, s_ref, do_ref, dy_ref, dx_ref,
                dz_ref, dd_ref, ds_ref, *, eps):
    vs, r = _norm(y_ref, x_ref, z_ref, d_ref, eps)
    per, P = y_ref.shape[1], y_ref.shape[3]
    t = 0.0
    for j, v in enumerate(vs):
        lanes = slice(j * P, (j + 1) * P)
        do = do_ref[0, :, lanes].astype(_F32)
        n = v * r
        ds_ref[0, 0, :, lanes] = jnp.sum(do * n, axis=0, keepdims=True)
        t = t + jnp.sum(do * s_ref[:, lanes] * n, axis=1, keepdims=True)
    t = t / (per * P)
    for j, v in enumerate(vs):
        lanes, z, a, sig = _head(j, y_ref, x_ref, z_ref, d_ref)
        dn = do_ref[0, :, lanes].astype(_F32) * s_ref[:, lanes]
        dv = r * (dn - v * r * t)
        da = dv * (z * sig)
        dz_ref[0, :, lanes] = (dv * a * sig * (1.0 + z * (1.0 - sig))).astype(
            dz_ref.dtype)
        dy_ref[0, j] = da.astype(dy_ref.dtype)
        dx_ref[0, :, lanes] = (d_ref[:, lanes] * da).astype(dx_ref.dtype)
        dd_ref[0, 0, :, lanes] = jnp.sum(
            da * x_ref[0, :, lanes].astype(_F32), axis=0, keepdims=True)


def _specs(y, groups, rows):
    """The grid (row, block of positions, group) and its block specs: y a
    group's heads `[1, H / groups, rows, P]`, the rest a group's lanes."""
    nb, H, S, P = y.shape
    per = H // groups
    return (nb, S // rows, groups), {
        "heads": pl.BlockSpec((1, per, rows, P),
                              lambda i, s, g: (i, g, s, 0)),
        "act": pl.BlockSpec((1, rows, per * P), lambda i, s, g: (i, s, g)),
        "lane": pl.BlockSpec((1, per * P), lambda i, s, g: (0, g)),
        "sum": pl.BlockSpec((1, 1, 1, per * P),
                            lambda i, s, g: (i, s, 0, g)),
    }


_PARALLEL = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel"))


# Both implementations are jitted on their own, as the SSD kernels are: a
# model calls them once a layer with the same shapes, and they are traced
# and lowered once.
@functools.partial(jax.jit,
                   static_argnames=("groups", "eps", "rows", "interpret"))
def _fwd_impl(y, x, z, d, scale, groups, eps, rows, interpret):
    grid, s = _specs(y, groups, rows)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps), name="gate_norm_fwd",
        interpret=interpret, grid=grid,
        in_specs=[s["heads"], s["act"], s["act"], s["lane"], s["lane"]],
        out_specs=s["act"], out_shape=jax.ShapeDtypeStruct(x.shape, y.dtype),
        compiler_params=_PARALLEL)(y, x, z, d, scale)


@functools.partial(jax.jit,
                   static_argnames=("groups", "eps", "rows", "interpret"))
def _bwd_impl(y, x, z, d, scale, dout, groups, eps, rows, interpret):
    """`(dy, dx, dz, dD, dscale)`: dy heads-major as y; dx the skip term's
    share alone; dD a lane's, `[1, H*P]` as `d`."""
    grid, s = _specs(y, groups, rows)
    nb, S, W = x.shape
    sums = jax.ShapeDtypeStruct((nb, S // rows, 1, W), _F32)
    dy, dx, dz, dd, ds = pl.pallas_call(
        functools.partial(_bwd_kernel, eps=eps), name="gate_norm_bwd",
        interpret=interpret, grid=grid,
        in_specs=[s["heads"], s["act"], s["act"], s["lane"], s["lane"],
                  s["act"]],
        out_specs=[s["heads"], s["act"], s["act"], s["sum"], s["sum"]],
        out_shape=[jax.ShapeDtypeStruct(y.shape, y.dtype),
                   jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(x.shape, z.dtype), sums, sums],
        compiler_params=_PARALLEL)(y, x, z, d, scale, dout)
    # the gate's lanes of z's gradient; what lies past them is not read
    dz = jnp.pad(dz, [(0, 0), (0, 0), (0, z.shape[2] - W)])
    return dy, dx, dz, dd.sum(axis=(0, 1)), ds.sum(axis=(0, 1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _gate_norm(y, x, z, d, scale, groups, eps, rows, interpret):
    return _fwd_impl(y, x, z, d, scale, groups, eps, rows, interpret)


def _gate_norm_vjp_fwd(y, x, z, d, scale, groups, eps, rows, interpret):
    return (_fwd_impl(y, x, z, d, scale, groups, eps, rows, interpret),
            (y, x, z, d, scale))


def _gate_norm_vjp_bwd(groups, eps, rows, interpret, res, dout):
    return _bwd_impl(*res, dout, groups, eps, rows, interpret)


_gate_norm.defvjp(_gate_norm_vjp_fwd, _gate_norm_vjp_bwd)
