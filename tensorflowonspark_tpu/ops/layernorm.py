"""Pallas fused layernorm.

One VMEM pass per row-block: mean, variance, normalize, scale/shift — all
in f32 on the VPU regardless of the activation dtype, so bf16 residual
streams keep f32 normalization statistics (the standard TPU recipe the
model zoo uses via flax; this kernel fuses it for the serving/AOT path and
as the pattern for custom fusions).

Backward recomputes from saved (x, scale) via the JAX reference — O(N·D)
residuals, XLA-fused backward matmuls.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _ln_kernel(x_ref, s_ref, b_ref, o_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)                  # [bn, D]
    mean = jnp.mean(x, axis=-1, keepdims=True)
    xc = x - mean
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    y = xc * jax.lax.rsqrt(var + eps)
    y = y * s_ref[:].astype(jnp.float32) + b_ref[:].astype(jnp.float32)
    o_ref[:] = y.astype(o_ref.dtype)


def layernorm_reference(x, scale, bias, eps=1e-6):
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    xc = xf - mean
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    y = xc * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)


def _ln_impl(x, scale, bias, eps, block_n, interpret):
    shape = x.shape
    D = shape[-1]
    x2 = x.reshape(-1, D)
    N = x2.shape[0]
    pad = (-N) % block_n
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_ln_kernel, eps=eps),
        grid=(x2.shape[0] // block_n,),
        in_specs=[
            pl.BlockSpec((block_n, D), lambda i: (i, 0)),
            pl.BlockSpec((D,), lambda i: (0,)),
            pl.BlockSpec((D,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_n, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x2.shape, x.dtype),
        interpret=interpret,
        name="layernorm_fwd",
    )(x2, scale, bias)
    return out[:N].reshape(shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _ln(x, scale, bias, eps, block_n, interpret):
    return _ln_impl(x, scale, bias, eps, block_n, interpret)


def _ln_vjp_fwd(x, scale, bias, eps, block_n, interpret):
    return _ln(x, scale, bias, eps, block_n, interpret), (x, scale, bias)


def _ln_vjp_bwd(eps, block_n, interpret, res, g):
    x, scale, bias = res
    _, vjp = jax.vjp(
        lambda x, s, b: layernorm_reference(x, s, b, eps), x, scale, bias)
    return vjp(g)


_ln.defvjp(_ln_vjp_fwd, _ln_vjp_bwd)


def fused_layernorm(x, scale, bias, eps=1e-6, block_n=256, interpret=None):
    """Layernorm over the last dim of `x` with f32 statistics."""
    if interpret is None:
        from tensorflowonspark_tpu.ops import default_interpret
        interpret = default_interpret()
    n_rows = 1
    for d in x.shape[:-1]:
        n_rows *= d
    block_n = max(8, min(block_n, n_rows))
    return _ln(x, scale, bias, float(eps), int(block_n), bool(interpret))
