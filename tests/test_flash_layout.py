"""The layout the flash kernels index (`ops/flash_attention.py`,
`_heads_a_block`): the projections' own `[B, S, H*D]` by blocks of 128
lanes, two heads a block at a head of 64, four at 32, and the transposed
staging for the shapes the lane rule cannot serve or does not yet let by.  Interpret
mode against the dense reference; fast tier (`tests/test_ops.py`, which
holds the kernels' other parity tests, is in the slow one).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorflowonspark_tpu.models.transformer import (
    Block, Transformer, TransformerConfig, lm_loss, remat_block)
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


# (query heads, key/value heads, window, latent): S=200 at blocks of 128 is
# padded up to 256 in every one
RESIDUALS = {"plain": (2, 2, None, False), "gqa_window": (4, 2, 60, False),
             "latent": (2, 2, None, True)}


@pytest.mark.parametrize("case", list(RESIDUALS))
def test_backward_holds_the_row_statistics_a_query_not_a_lane(case):
    """Between forward and backward a call keeps q, k, v, the output and
    `f32[B, H, S padded]` named `flash_lse`, never the kernel's own
    `[B, H, S, 128]`; the gradients from the compact residual match the
    dense reference where S is padded up to a block."""
    from jax._src.ad_checkpoint import saved_residuals

    from tensorflowonspark_tpu.ops.flash_attention import (
        flash_attention_latent, latent_attention_reference)

    H, H_kv, window, latent = RESIDUALS[case]
    S, D = 200, 64
    q, k, v, w = _heads(H, H_kv, D, S)
    if latent:
        args = (jnp.concatenate([q, q[..., :32]], -1), k, k[:, :, 0, :32], v)
        fn = lambda *a: jnp.sum(w * flash_attention_latent(  # noqa: E731
            *a, block_q=128, block_k=128, interpret=True))
        ref = lambda *a: jnp.sum(  # noqa: E731
            w * latent_attention_reference(*a))
    else:
        args = (q, k, v)
        fn = lambda *a: jnp.sum(w * flash_attention(  # noqa: E731
            *a, window=window, block_q=128, block_k=128, interpret=True))
        ref = lambda *a: jnp.sum(w * attention_reference(  # noqa: E731
            *a, window=window))
    kept = [(aval.shape, why) for aval, why in saved_residuals(fn, *args)]
    assert ((1, H, 256), "flash_lse") in [
        (shape, why.split("'")[1]) for shape, why in kept if "named" in why]
    assert (1, S, H, D) in [shape for shape, _ in kept]          # the output
    assert not [shape for shape, _ in kept if shape[-1] == 128], kept
    got = jax.grad(fn, tuple(range(len(args))))(*args)
    want = jax.grad(ref, tuple(range(len(args))))(*args)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5)


# ---- remat keeps attention's output and row statistics ---------------------
#
# `remat=True` wraps a block as `remat_block()`: the forward rules of
# `ops/flash_attention.py` name the kernels' output and row log-sum-exp and
# the block's policy saves them, so the backward pass recomputes the block
# with no forward kernel in it.  Interpret mode, float32.  (Here and not
# in `tests/test_transformer.py`, which is in the slow tier.)

CFG = TransformerConfig(vocab_size=128, d_model=64, n_heads=4, n_layers=2,
                        d_ff=128, max_seq_len=32, dtype="float32")


@pytest.fixture(scope="module")
def toy_batch():
    return jnp.asarray(np.random.RandomState(0).randint(
        0, 128, size=(4, 32)).astype(np.int32))


def _counted(fn, *names):
    """What `fn()` added to the process counters `names`."""
    from tensorflowonspark_tpu import trace
    before = trace.counters().snapshot()
    out = fn()
    now = trace.counters().snapshot()
    return out, [now.get(n, 0) - before.get(n, 0) for n in names]


REMAT_COUNTERS = ("remat.attention.saved", "remat.attention.rerun")
REMAT_MIXERS = {
    "plain": dict(),
    "gqa_window": dict(n_kv_heads=2, rope=True, sliding_window=16,
                       layer_types=("sliding_attention", "full_attention")),
    "latent": dict(rope=True, norm_type="rmsnorm", kv_lora_rank=16,
                   q_lora_rank=24, qk_nope_head_dim=16, qk_rope_head_dim=8,
                   v_head_dim=16),
}


@pytest.mark.parametrize("mixer", list(REMAT_MIXERS))
def test_remat_saves_attention_and_changes_no_bit_of_the_gradient(
        toy_batch, mixer):
    base = dataclasses.replace(CFG, attention_impl="flash",
                               **REMAT_MIXERS[mixer])
    impl = "_mla_fwd_impl" if mixer == "latent" else "_flash_fwd_impl"
    grads = {}
    for remat in (False, True):
        model = Transformer(dataclasses.replace(base, remat=remat))
        params = model.init(jax.random.key(0), toy_batch)["params"]

        def loss(p):
            return lm_loss(model.apply({"params": p}, toy_batch[:, :-1]),
                           toy_batch[:, 1:])

        text, counted = _counted(
            lambda: str(jax.make_jaxpr(jax.grad(loss))(params)),
            *REMAT_COUNTERS)
        # a forward kernel a layer: the recomputed block holds none
        assert text.count(f"name={impl}") == CFG.n_layers
        # once a traced mixer, under `remat` only
        assert counted == ([CFG.n_layers, 0] if remat else [0, 0])
        # one program each: op by op the CPU rounds a fused chain its own way
        grads[remat] = jax.jit(jax.grad(loss))(params)
    same = jax.tree.map(lambda a, b: bool((a == b).all()),
                        grads[False], grads[True])
    assert jax.tree.all(same), same


def test_remat_counts_a_mixer_on_the_dense_core_as_rerun():
    cfg = dataclasses.replace(CFG, attention_impl="flash", remat=True)
    x = jax.random.normal(jax.random.key(1), (2, 32, 64))
    mask = jnp.ones((2, 32), bool).at[1, :4].set(False)
    block = remat_block()(cfg)
    params = block.init(jax.random.key(2), x)

    def traced(module, **kw):
        return _counted(lambda: jax.grad(lambda p: jnp.sum(
            module.apply(p, x, **kw)))(params), *REMAT_COUNTERS)[1]

    assert traced(block) == [1, 0]
    assert traced(block, mask=mask) == [0, 1]     # a mask: the dense core
    dense = dataclasses.replace(cfg, attention_impl="dense")
    assert traced(remat_block()(dense)) == [0, 1]
    plain = dataclasses.replace(cfg, remat=False)
    assert traced(remat_block()(plain)) == [0, 0]  # `remat` is the switch
    # the names are a policy's to use: without one nothing changes
    np.testing.assert_array_equal(block.apply(params, x),
                                  Block(plain).apply(params, x))
