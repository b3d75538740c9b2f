"""The layout the flash kernels index (`ops/flash_attention.py`,
`_heads_a_block`): the projections' own `[B, S, H*D]` by blocks of 128
lanes, two heads a block at a head of 64, four at 32, and the transposed
staging for the shapes the lane rule cannot serve or does not yet let by.  Interpret
mode against the dense reference; fast tier (`tests/test_ops.py`, which
holds the kernels' other parity tests, is in the slow one).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorflowonspark_tpu.ops.flash_attention import (
    attention_reference, flash_attention)


# (query heads, key/value heads, head size, S, blocks, causal, window, the
# staging the shape test must pick)
LANE_BLOCKS = {
    "two_heads_a_block": (4, 4, 64, 256, 128, True, None, "packed"),
    "four_heads_a_block": (8, 8, 32, 256, 128, True, None, "packed"),
    "two_heads_one_block_of_keys": (4, 4, 64, 256, 256, True, None,
                                    "packed"),
    "two_heads_not_causal": (4, 4, 64, 256, 128, False, None, "packed"),
    "two_heads_a_window": (4, 4, 64, 384, 128, True, 100, "packed"),
    "two_heads_ragged": (2, 2, 64, 1100, 1024, True, None, "packed"),
    # an odd head count at 64: H*D is no multiple of 128 lanes
    "odd_heads_at_64": (3, 3, 64, 256, 128, True, None, "transposed"),
    # narrow key/value heads under 128: a query head and its key/value
    # head would lie in different lanes of their blocks
    "gqa_at_64": (4, 2, 64, 256, 128, True, None, "transposed"),
    # a head that neither divides 128 nor is a multiple of it
    "a_head_of_96": (2, 2, 96, 256, 128, False, None, "transposed"),
    # a head of 128: a block by itself to the kernels, held back by the
    # shape test (the sparse-expert cell's step stopped on the chip with it)
    "a_head_of_128": (2, 2, 128, 256, 128, True, None, "transposed"),
    "gqa_at_128": (4, 1, 128, 256, 128, True, None, "transposed"),
    # ... and what the kernels do with it once the shape test lets it by
    "a_head_of_128_let_by": (2, 2, 128, 256, 128, True, None, "packed"),
    "gqa_at_128_let_by": (4, 1, 128, 256, 128, True, None, "packed"),
    "gqa_at_128_a_window_let_by": (4, 1, 128, 384, 128, True, 100, "packed"),
}


def _heads(H, H_kv, D, S, seed=11):
    ks = jax.random.split(jax.random.key(seed), 4)
    return (jax.random.normal(ks[0], (1, S, H, D)),
            jax.random.normal(ks[1], (1, S, H_kv, D)),
            jax.random.normal(ks[2], (1, S, H_kv, D)),
            jax.random.normal(ks[3], (1, S, H, D)))


@pytest.mark.parametrize("case", list(LANE_BLOCKS))
def test_flash_lane_blocks_match_reference(case, monkeypatch):
    """Forward and all three gradients over the block rule of
    `_heads_a_block`, and which staging each shape is counted under."""
    import sys

    from tensorflowonspark_tpu import trace
    from tensorflowonspark_tpu.ops.flash_attention import (
        _flash_bwd_impl, _flash_fwd_impl, _heads_a_block)

    # the package's attribute of that name is the function
    fa = sys.modules["tensorflowonspark_tpu.ops.flash_attention"]

    H, H_kv, D, S, block, causal, window, staging = LANE_BLOCKS[case]
    if case.endswith("_let_by"):
        assert _heads_a_block(H, H_kv, D) is None
        monkeypatch.setattr(fa, "_heads_a_block", lambda *shape: 1)
    else:
        assert (_heads_a_block(H, H_kv, D) is not None) == (
            staging == "packed")
    q, k, v, w = _heads(H, H_kv, D, S)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=causal, window=window,
                               block_q=block, block_k=block, interpret=True)

    def ref(q, k, v):
        return attention_reference(q, k, v, causal=causal, window=window)

    _flash_fwd_impl.clear_cache()       # a cached trace is not counted
    _flash_bwd_impl.clear_cache()
    names = ("flash.calls.packed", "flash.calls.transposed")
    before = [trace.counters().get(n) for n in names]
    np.testing.assert_allclose(flash(q, k, v), ref(q, k, v), atol=2e-5,
                               rtol=2e-5)
    got = jax.grad(lambda *a: jnp.sum(flash(*a) * w), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(ref(*a) * w), (0, 1, 2))(q, k, v)
    assert got[1].shape == k.shape              # narrow dk
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)
    counted = [trace.counters().get(n) - b for n, b in zip(names, before)]
    # the primal forward, then the forward with lse, dq and dkv
    assert counted == ([4, 0] if staging == "packed" else [0, 4])


@pytest.mark.parametrize("H,D,staging", [(4, 64, "packed"),
                                         (3, 64, "transposed")])
def test_flash_with_lse_over_lane_blocks(H, D, staging):
    """A cotangent on `lse` folds into `delta`, which both stagings make
    from dO and O as `[B, S, H*D]`: two key blocks, so the forward keeps
    its running state with two heads a block."""
    from tensorflowonspark_tpu.ops.flash_attention import (
        _heads_a_block, flash_attention_with_lse)

    assert (_heads_a_block(H, H, D) is not None) == (staging == "packed")
    S = 256
    q, k, v, w = _heads(H, H, D, S)
    u = jax.random.normal(jax.random.key(6), (1, H, S))

    def dense(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / D ** 0.5
        s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -1e30)
        return (jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v),
                jax.nn.logsumexp(s, axis=-1))

    def flash(q, k, v):
        return flash_attention_with_lse(q, k, v, causal=True, block_q=128,
                                        block_k=128, interpret=True)

    def scalar(fn):
        def f(q, k, v):
            out, lse = fn(q, k, v)
            return jnp.sum(out * w) + jnp.sum(lse * u)
        return f

    for a, b in zip(flash(q, k, v), dense(q, k, v)):
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5)
    got = jax.grad(scalar(flash), (0, 1, 2))(q, k, v)
    want = jax.grad(scalar(dense), (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)
