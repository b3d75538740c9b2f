"""Pallas fused-dequant weight matmuls (W8A16 / W4A16 decode path).

Decode is HBM-bandwidth-bound: every generated token reads every weight
once.  The materialized path (`quantize.dequantize_tree`) hopes XLA
fuses ``q.astype(dtype) * scale`` into the consuming matmul's operand
read — these kernels make the guarantee structural instead.  Each is a
weight-stationary blocked matmul whose weight operand arrives in its
QUANTIZED storage form; the dense bf16/f32 kernel never exists in HBM:

- ``_int8_kernel``: weight tiles stream as int8 ``[bk, bn]`` blocks with
  a per-output-channel f32 scale row ``[1, bn]``; the tile dequantizes
  in VMEM (``q.astype(f32) * scale``, cast to the activation dtype) and
  feeds the MXU with f32 accumulation across the k grid.  1/4 the
  weight bytes of f32 per token (1/2 of bf16), plus 4 bytes per output
  channel of scale.
- ``_int4_kernel``: weights stream NIBBLE-PACKED (two signed 4-bit rows
  per int8 byte along the input dim — ``quantize.int4_pack``'s layout)
  with per-``group_size`` AWQ-style scales.  Sign-extension is two
  int32 shifts per nibble, done after the VMEM load; the packed byte
  rows never unpack in HBM.  The activation is split OUTSIDE the kernel
  into even/odd input-row planes (``x[:, 0::2]`` / ``x[:, 1::2]``), so
  a packed row ``i`` multiplies plane columns ``i`` directly —
  ``y = sum_g xe_g @ (lo_g * s_g) + xo_g @ (hi_g * s_g)`` — and no
  in-kernel row interleave (an awkward sublane shuffle) is needed.
  1/8 the weight bytes of f32, plus 4 bytes per (group, channel).

Both kernels zero-pad M/K/N up to their block grid outside the call and
slice the result, so any shapes are correct; block shapes are built
from runtime variables and respect the TPU tile grid (lane dim
multiples of 128, sublane multiples of 8 f32 / 16 bf16; the packed int4
lane dim covers two logical input rows per byte — see
``analysis/pallas_tiles`` for the corresponding scan carve-out).
``interpret=`` threads through ``ops.default_interpret()`` so the CPU
tier executes these exact kernel bodies in the Pallas interpreter, and
``quant_matmul_reference`` is the gather/einsum oracle with identical
dequant semantics for the parity tests.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANE = 128


def _scratch(shape, dtype=jnp.float32):
    return pltpu.VMEM(shape, dtype)


def _round_up(x, mult):
    return -(-int(x) // mult) * mult


def _sublane(dtype):
    return 8 if dtype == jnp.float32 else 16


def _pad2(a, rows, cols):
    pr, pc = rows - a.shape[0], cols - a.shape[1]
    if pr or pc:
        a = jnp.pad(a, ((0, pr), (0, pc)))
    return a


def _int8_kernel(x_ref, q_ref, s_ref, o_ref, acc_ref, *, n_k):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros(acc_ref.shape, acc_ref.dtype)

    # dequant in VMEM: int8 tile * per-channel scale, cast to the
    # activation dtype so the MXU sees the same operands the
    # materialized dequantize_tree path feeds it
    w = (q_ref[...].astype(jnp.float32) * s_ref[...]).astype(x_ref.dtype)
    acc_ref[...] = acc_ref[...] + jax.lax.dot_general(
        x_ref[...], w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _finish():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _int4_kernel(xe_ref, xo_ref, p_ref, s_ref, o_ref, acc_ref, *,
                 n_k, gpt, gh):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros(acc_ref.shape, acc_ref.dtype)

    # sign-extend both nibbles of every packed byte: arithmetic shifts
    # in int32 (low nibble = bits 0-3, high = bits 4-7); packed row i
    # holds logical input rows 2i (lo) and 2i+1 (hi), which line up
    # with the even/odd activation planes
    pi = p_ref[...].astype(jnp.int32)
    lo = ((pi << 28) >> 28).astype(jnp.float32)
    hi = ((pi << 24) >> 28).astype(jnp.float32)
    acc = acc_ref[...]
    for g in range(gpt):              # static: scale groups per k-tile
        rows = slice(g * gh, (g + 1) * gh)
        s = s_ref[g:g + 1, :]
        wl = (lo[rows] * s).astype(xe_ref.dtype)
        wh = (hi[rows] * s).astype(xe_ref.dtype)
        acc = acc + jax.lax.dot_general(
            xe_ref[:, rows], wl, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc = acc + jax.lax.dot_general(
            xo_ref[:, rows], wh, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    acc_ref[...] = acc

    @pl.when(k == n_k - 1)
    def _finish():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _int8_call(x2, q, scale, block_m, block_n, block_k, interpret):
    M, K = x2.shape
    _, N = q.shape
    scale = jnp.asarray(scale, jnp.float32).reshape(1, N)
    sub = _sublane(x2.dtype)
    bm = _round_up(min(block_m, _round_up(M, sub)), sub)
    bk = min(block_k, _round_up(K, _LANE))
    bn = min(block_n, _round_up(N, _LANE))
    Mp, Kp, Np = _round_up(M, bm), _round_up(K, bk), _round_up(N, bn)
    nm, nn, nk = Mp // bm, Np // bn, Kp // bk
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        grid=(nm, nn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda m, n, k: (m, k)),
            pl.BlockSpec((bk, bn), lambda m, n, k: (k, n)),
            pl.BlockSpec((1, bn), lambda m, n, k: (0, n)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda m, n, k: (m, n)),
        scratch_shapes=[_scratch((bm, bn))])
    out = pl.pallas_call(
        functools.partial(_int8_kernel, n_k=nk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Mp, Np), x2.dtype),
        interpret=interpret,
        name="qmm_int8",
    )(_pad2(x2, Mp, Kp), _pad2(q, Kp, Np), _pad2(scale, 1, Np))
    return out[:M, :N]


def _int4_call(x2, w, block_m, block_n, interpret):
    M, _ = x2.shape
    p = w.q
    scale = jnp.asarray(w.scale, jnp.float32)
    Kp2, N = p.shape
    gh = w.group_size // 2            # packed rows per scale group
    if _LANE % gh == 0:
        bkp = _LANE                   # whole groups tile the 128 lanes
    elif gh % _LANE == 0:
        bkp = gh                      # one big group spans whole tiles
    else:
        raise ValueError(
            f"group_size {w.group_size} does not tile the {_LANE}-wide "
            f"lane grid: half-group {gh} must divide {_LANE} or be a "
            f"multiple of it")
    gpt = bkp // gh                   # scale groups per k-tile
    sub = _sublane(x2.dtype)
    bm = _round_up(min(block_m, _round_up(M, sub)), sub)
    bn = min(block_n, _round_up(N, _LANE))
    Mp = _round_up(M, bm)
    Kp2p = _round_up(Kp2, bkp)
    Np = _round_up(N, bn)
    nm, nn, nk = Mp // bm, Np // bn, Kp2p // bkp
    # split the activation into even/odd input-row planes so plane
    # column i multiplies packed row i's lo/hi nibble respectively
    x2 = _pad2(x2, Mp, 2 * Kp2p)
    xe, xo = x2[:, 0::2], x2[:, 1::2]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        grid=(nm, nn, nk),
        in_specs=[
            pl.BlockSpec((bm, bkp), lambda m, n, k: (m, k)),
            pl.BlockSpec((bm, bkp), lambda m, n, k: (m, k)),
            pl.BlockSpec((bkp, bn), lambda m, n, k: (k, n)),
            pl.BlockSpec((gpt, bn), lambda m, n, k: (k, n)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda m, n, k: (m, n)),
        scratch_shapes=[_scratch((bm, bn))])
    out = pl.pallas_call(
        functools.partial(_int4_kernel, n_k=nk, gpt=gpt, gh=gh),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Mp, Np), x2.dtype),
        interpret=interpret,
        name="qmm_int4",
    )(xe, xo, _pad2(p, Kp2p, Np), _pad2(scale, Kp2p // gh, Np))
    return out[:M, :N]


def quant_matmul(x, w, *, block_m=128, block_n=128, block_k=512,
                 interpret=None):
    """``x @ dequant(w)`` with the dequant fused into the weight read.

    Args:
      x: ``[..., K]`` floating activations (any leading batch shape).
      w: a quantized kernel leaf — the int8 ``{"q": [K, N] int8,
        "scale": [1, N] f32}`` dict ``quantize.quantize_tree`` emits, or
        a nibble-packed ``quantize.Int4Weight``.
      block_m / block_n / block_k: tile sizes (n/k must be multiples of
        128; clamped down for small operands).  ``block_k`` applies to
        the int8 kernel only — the int4 k-tile is derived from the
        group size.

    Returns ``[..., N]`` in x's dtype (f32-accumulated).
    """
    from tensorflowonspark_tpu import quantize

    if interpret is None:
        from tensorflowonspark_tpu.ops import default_interpret
        interpret = default_interpret()
    if block_n % _LANE or block_k % _LANE:
        raise ValueError(f"block_n/block_k must be multiples of {_LANE}, "
                         f"got {block_n}/{block_k}")
    if not jnp.issubdtype(x.dtype, jnp.floating):
        raise ValueError(f"activations must be floating, got {x.dtype}")

    if isinstance(w, quantize.Int4Weight):
        K, N = w.in_dim, w.out_dim
    elif quantize._is_qleaf(w):
        if w["q"].ndim != 2:
            raise ValueError(f"quant_matmul needs a 2-D [in, out] kernel, "
                             f"got {w['q'].shape}")
        K, N = w["q"].shape
    else:
        raise TypeError(
            f"w must be an int8 quantized-leaf dict or Int4Weight, "
            f"got {type(w)!r}")
    *batch, Kx = x.shape
    if Kx != K:
        raise ValueError(f"activation K {Kx} != weight in_dim {K}")
    M = 1
    for d in batch:
        M *= int(d)
    x2 = x.reshape(M, K)
    if isinstance(w, quantize.Int4Weight):
        out = _int4_call(x2, w, block_m, block_n, interpret)
    else:
        out = _int8_call(x2, w["q"], w["scale"], block_m, block_n,
                         block_k, interpret)
    return out.reshape(*batch, N)


def quant_matmul_reference(x, w):
    """Gather/einsum oracle with the kernel's exact dequant semantics
    (f32 dequant -> cast to the activation dtype -> f32-accumulated
    matmul -> cast back) — the parity-test baseline, and numerically the
    materialized ``dequantize_tree`` + Dense path."""
    from tensorflowonspark_tpu import quantize

    wf = quantize.dequantize_leaf(w).astype(x.dtype)
    out = jnp.einsum("...k,kn->...n", x, wf,
                     preferred_element_type=jnp.float32)
    return out.astype(x.dtype)
