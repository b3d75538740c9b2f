"""The Mamba-2 mixer's skip, gate and norm by groups (`ops/gate_norm.py`):
the Pallas kernels, in the interpreter on the CPU, against the same
expression in `jax.numpy`, forward and every gradient."""
import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from tensorflowonspark_tpu import trace  # noqa: E402
from tensorflowonspark_tpu.models.transformer import (  # noqa: E402
    Mamba2Mixer, TransformerConfig)
from tensorflowonspark_tpu.ops import gate_norm as gn  # noqa: E402

EPS = 1e-5


def _inputs(S, H, P, dtype, wide=0, seed=0):
    """y, x `[2, S, H*P]` and z `[2, S, H*P + wide]` in `dtype`, D [H], a
    scale of which lane 3 is 0, and a weighting of the output for a scalar
    loss."""
    W = H * P
    k = jax.random.split(jax.random.key(seed), 6)
    y, x, z = (jax.random.normal(kk, (2, S, W + n)).astype(dtype)
               for kk, n in zip(k[:3], (0, 0, wide)))
    skip = 1.0 + 0.5 * jax.random.normal(k[3], (H,))
    scale = (1.0 + 0.5 * jax.random.normal(k[4], (W,))).at[3].set(0.0)
    return y, x, z, skip, scale, jax.random.normal(k[5], (2, S, W))


def _loss(groups, kernel, w):
    def f(y, x, z, skip, scale):
        out = gn.gate_norm(y, x, z, skip, scale, groups, EPS, kernel=kernel,
                           interpret=True)
        return jnp.sum(out.astype(jnp.float32) * w)
    return f


# float32: rounding in another order; bfloat16: two units in the last place
_TOL = {jnp.dtype(jnp.float32): 1e-5, jnp.dtype(jnp.bfloat16): 2.0 ** -6}


def _close(got, want, dtype):
    """Within `_TOL` of each value and of the largest magnitude."""
    got, want = (np.asarray(t, np.float32) for t in (got, want))
    tol = _TOL[jnp.dtype(dtype)]
    np.testing.assert_allclose(got, want, rtol=tol,
                               atol=tol * float(np.abs(want).max()))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("S,H,P,groups,wide,rows", [
    # 264 positions in blocks of 128: two whole and one padded with zeros
    (264, 4, 8, 2, 0, 128),
    (264, 64, 64, 8, 0, 128),
    # 40 positions, shorter than a block: one of 48, the last 8 padded; z
    # 37 lanes wider than the gate, as the mixer hands over its whole input
    # projection
    (40, 4, 8, 2, 37, 512)],
    ids=["toy-2-groups", "8-groups-of-512", "short-row-wide-z"])
def test_the_kernels_match_the_expression(dtype, S, H, P, groups, wide, rows,
                                          monkeypatch):
    """Forward and the gradients of y, x, z, D and the scale, the kernels
    against the `jax.numpy` form."""
    monkeypatch.setattr(gn, "_ROWS", rows)
    y, x, z, skip, scale, w = _inputs(S, H, P, dtype, wide)
    got = gn.gate_norm(y, x, z, skip, scale, groups, EPS, kernel=True,
                       interpret=True)
    want = gn.gate_norm(y, x, z, skip, scale, groups, EPS)
    assert got.dtype == want.dtype == dtype and got.shape == y.shape
    _close(got, want, dtype)
    assert float(jnp.abs(got[..., 3]).max()) == 0.0      # scale 0 there
    args = (y, x, z, skip, scale)
    g_got = jax.grad(_loss(groups, True, w), argnums=range(5))(*args)
    g_want = jax.grad(_loss(groups, False, w), argnums=range(5))(*args)
    for name, a, b, t in zip("y x z D scale".split(), g_got, g_want, args):
        assert a.dtype == t.dtype and a.shape == t.shape, name
        # dy, dx, dz round once to the activation type, as the output does;
        # D's and the scale's are float32 sums over every position
        _close(a, b, t.dtype)
    assert float(jnp.abs(g_got[4][3])) > 0.0  # the zero lane's scale learns
    assert not jnp.any(g_got[2][..., H * P:])  # past the gate, z is not read


def test_gate_norm_calls_are_counted_by_path():
    y, x, z, skip, scale, _ = _inputs(16, 4, 8, jnp.float32)
    names = ("gate_norm.calls.kernel", "gate_norm.calls.reference")
    for kernel in (True, False):
        before = trace.counters().snapshot()
        jax.jit(lambda *a: gn.gate_norm(*a, 2, EPS, kernel=kernel,
                                        interpret=True)).lower(
            y, x, z, skip, scale)
        now = trace.counters().snapshot()
        got = [now.get(n, 0) - before.get(n, 0) for n in names]
        assert got == ([1, 0] if kernel else [0, 1])
    with pytest.raises(ValueError, match="groups"):
        gn.gate_norm(y, x, z, skip, scale, 3, EPS)
    with pytest.raises(ValueError, match="heads"):
        gn.gate_norm(y, x, z, skip[:3], scale, 3, EPS)


@pytest.mark.parametrize("impl,path", [("flash", "kernel"),
                                       ("dense", "reference")])
def test_the_mixer_takes_the_kernels_where_the_scan_does(impl, path):
    """`Mamba2Mixer` switches the norm with the SSD scan: the kernels under
    `attention_impl='flash'`, the `jax.numpy` form under `dense`."""
    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=2, n_layers=2, d_ff=64,
        layer_pattern="M*", ssm_heads=4, ssm_head_dim=8, ssm_groups=2,
        ssm_state=8, conv_kernel=4, norm_type="rmsnorm", ln_eps=EPS,
        attention_impl=impl)
    u = jnp.zeros((1, 24, 32))
    params = Mamba2Mixer(cfg).init(jax.random.key(0), u)
    before = trace.counters().snapshot()
    jax.jit(lambda p: Mamba2Mixer(cfg).apply(p, u)).lower(params)
    now = trace.counters().snapshot()
    for name in ("kernel", "reference"):
        counted = now.get(f"gate_norm.calls.{name}", 0) - before.get(
            f"gate_norm.calls.{name}", 0)
        assert counted == (1 if name == path else 0), name
