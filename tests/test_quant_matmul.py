"""ops.quant_matmul: fused-dequant int8/int4 kernels vs the einsum oracle.

Interpret mode executes the exact kernel bodies on the CPU tier, so the
parity matrix here covers what the TPU runs: both quantized stores,
both activation widths, and shapes that exercise multi-tile grids,
sublane/lane padding remainders, and grouped int4 scales.
"""
import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from tensorflowonspark_tpu import quantize

# the package re-exports the function under the module's name, so a
# plain `import ... as qm` would bind the function; load the module
import importlib
qm = importlib.import_module("tensorflowonspark_tpu.ops.quant_matmul")

# rows deliberately off the sublane grid, K/N off the 128-lane grid in
# the tall/wide cases, so the zero-pad + slice path is always exercised
SHAPES = {"tall": (5, 384, 128), "wide": (4, 128, 320),
          "square": (16, 256, 256)}


def _mk(mode, rows, K, N, dtype, group_size=64, seed=0):
    rs = np.random.RandomState(seed)
    x = jnp.asarray(rs.randn(rows, K), dtype)
    w = jnp.asarray(rs.randn(K, N), jnp.float32)
    if mode == "int8":
        leaf = quantize.quantize_tree({"kernel": w},
                                      min_elements=0)["kernel"]
    else:
        leaf = quantize.int4_pack(w, group_size)
    return x, leaf


def _assert_close(got, ref, dtype):
    assert got.shape == ref.shape and got.dtype == ref.dtype
    g = np.asarray(got, np.float32)
    r = np.asarray(ref, np.float32)
    denom = float(np.max(np.abs(r))) + 1e-6
    # f32: tiling only reorders the f32 accumulation; bf16 pays the
    # operand rounding twice (dequant cast + activation width)
    tol = 2e-2 if dtype == "bfloat16" else 1e-5
    assert float(np.max(np.abs(g - r))) / denom < tol


@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("mode", ["int8", "int4"])
def test_kernel_matches_oracle(mode, dtype, shape):
    rows, K, N = SHAPES[shape]
    x, leaf = _mk(mode, rows, K, N, jnp.dtype(dtype))
    # block_k=128 forces a multi-tile k grid on the tall/square shapes
    got = qm.quant_matmul(x, leaf, block_m=8, block_n=128, block_k=128,
                          interpret=True)
    _assert_close(got, qm.quant_matmul_reference(x, leaf), dtype)


@pytest.mark.parametrize("G,K", [
    (8, 64),      # many groups per k-tile (gpt = 16)
    (64, 200),    # K pads up to whole groups (in_dim slice-back)
    (256, 256),   # one group spans the whole k-tile (gpt = 1)
])
def test_int4_grouped_shapes(G, K):
    x, leaf = _mk("int4", 9, K, 192, jnp.float32, group_size=G, seed=3)
    assert leaf.group_size == G and leaf.in_dim == K
    got = qm.quant_matmul(x, leaf, interpret=True)
    _assert_close(got, qm.quant_matmul_reference(x, leaf), "float32")


def test_batched_activation_dims():
    x, leaf = _mk("int8", 6, 128, 128, jnp.float32, seed=4)
    x3 = x.reshape(2, 3, 128)
    got = qm.quant_matmul(x3, leaf, interpret=True)
    assert got.shape == (2, 3, 128)
    flat = qm.quant_matmul(x, leaf, interpret=True)
    np.testing.assert_array_equal(np.asarray(got).reshape(6, 128),
                                  np.asarray(flat))


def test_jittable_with_quantized_leaf_operands():
    # the QuantDense path traces quant_matmul with the leaf as a jit
    # argument — both the int8 dict and the Int4Weight pytree node
    for mode in ("int8", "int4"):
        x, leaf = _mk(mode, 8, 128, 128, jnp.bfloat16, seed=5)
        fn = jax.jit(lambda x, w: qm.quant_matmul(x, w, interpret=True))
        _assert_close(fn(x, leaf), qm.quant_matmul_reference(x, leaf),
                      "bfloat16")


def test_bad_block_sizes_raise():
    x, leaf = _mk("int8", 4, 128, 128, jnp.float32)
    with pytest.raises(ValueError, match="multiples of 128"):
        qm.quant_matmul(x, leaf, block_n=100, interpret=True)
    with pytest.raises(ValueError, match="multiples of 128"):
        qm.quant_matmul(x, leaf, block_k=100, interpret=True)


def test_integer_activation_raises():
    _, leaf = _mk("int8", 4, 128, 128, jnp.float32)
    with pytest.raises(ValueError, match="floating"):
        qm.quant_matmul(jnp.ones((4, 128), jnp.int32), leaf,
                        interpret=True)


def test_k_mismatch_raises():
    x, leaf = _mk("int8", 4, 128, 128, jnp.float32)
    with pytest.raises(ValueError, match="in_dim"):
        qm.quant_matmul(x[:, :64], leaf, interpret=True)


def test_non_quantized_weight_raises():
    x = jnp.ones((4, 128), jnp.float32)
    with pytest.raises(TypeError, match="Int4Weight"):
        qm.quant_matmul(x, jnp.ones((128, 128)), interpret=True)


def test_untileable_int4_group_raises():
    # half-group 48 neither divides the 128-lane tile nor is a multiple
    # of it — no static k-tile exists, the call must say so
    x, leaf = _mk("int4", 4, 192, 128, jnp.float32, group_size=96)
    with pytest.raises(ValueError, match="does not tile"):
        qm.quant_matmul(x, leaf, interpret=True)
