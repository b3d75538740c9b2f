"""Pallas kernel ops vs dense references (interpret mode on the CPU mesh).

Analytic/reference ground truth instead of golden files, mirroring the
reference's test style (SURVEY.md §4: "analytic ground truth ... instead of
golden files").
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorflowonspark_tpu.ops.flash_attention import (
    attention_reference, flash_attention)


def _qkv(B=2, S=64, H=4, D=32, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    shape = (B, S, H, D)
    return tuple(jax.random.normal(k, shape, dtype) for k in ks)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_matches_reference(causal):
    q, k, v = _qkv()
    out = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16,
                          interpret=True)
    ref = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_flash_attention_ragged_seq_len():
    # S=48 not a multiple of block 32: padded keys must not leak in
    q, k, v = _qkv(S=48)
    out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32,
                          interpret=True)
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_flash_attention_grad_matches_reference():
    q, k, v = _qkv(S=32)

    def f_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, block_q=16,
                                       block_k=16, interpret=True) ** 2)

    def f_ref(q, k, v):
        return jnp.sum(attention_reference(q, k, v, causal=True) ** 2)

    g_flash = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("group,B,S,D,block", [
    (2, 2, 64, 32, 16),
    (4, 2, 64, 32, 16),
    (4, 1, 1024, 64, 1024),     # one resident block walked in sub-tiles
    (4, 1, 2048, 128, 1024),    # two blocks: loop bounds from program_id
])
def test_flash_attention_gqa_narrow_kv(group, B, S, D, block):
    # GQA-native: narrow k/v feed the kernel directly; outputs match the
    # repeated-kv reference, forward and backward (dk/dv come back
    # NARROW — the repeat's summed cotangent, computed in-kernel)
    H = 4
    ks = jax.random.split(jax.random.key(3), 3)
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, H // group, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, H // group, D), jnp.float32)

    out = flash_attention(q, k, v, causal=True, block_q=block, block_k=block,
                          interpret=True)
    ref = attention_reference(q, k, v, causal=True)   # repeats internally
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def f_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, block_q=block,
                                       block_k=block, interpret=True) ** 2)

    def f_ref(q, k, v):
        return jnp.sum(attention_reference(q, k, v, causal=True) ** 2)

    g_flash = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    assert g_flash[1].shape == k.shape          # narrow dk
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


def test_flash_attention_gqa_rejects_indivisible():
    q, k, v = _qkv(H=4)
    with pytest.raises(ValueError, match="multiple of kv heads"):
        flash_attention(q, k[:, :, :3], v[:, :, :3], interpret=True)


def test_flash_attention_bf16():
    q, k, v = _qkv(dtype=jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True, block_q=16, block_k=16,
                          interpret=True)
    ref = attention_reference(q, k, v, causal=True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(out.astype(np.float32),
                               ref.astype(np.float32), atol=3e-2, rtol=3e-2)


def test_transformer_flash_impl_matches_dense():
    from tensorflowonspark_tpu.models.transformer import (
        Transformer, TransformerConfig)
    base = dict(vocab_size=64, d_model=32, n_heads=2, n_layers=2, d_ff=48,
                max_seq_len=32, dtype="float32")
    tokens = jax.random.randint(jax.random.key(0), (2, 32), 0, 64)
    m_dense = Transformer(TransformerConfig(**base, attention_impl="dense"))
    m_flash = Transformer(TransformerConfig(**base, attention_impl="flash"))
    params = m_dense.init(jax.random.key(1), tokens)["params"]
    out_d = m_dense.apply({"params": params}, tokens)
    out_f = m_flash.apply({"params": params}, tokens)
    np.testing.assert_allclose(out_d, out_f, atol=2e-4, rtol=2e-4)


def test_transformer_flash_under_sharded_mesh():
    # flash must survive GSPMD: under an active mesh the dispatch wraps the
    # pallas kernel in shard_map (batch over dp, heads over tp)
    import numpy as np_mod
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from tensorflowonspark_tpu.models.transformer import (
        Transformer, TransformerConfig)

    devs = np_mod.array(jax.devices()[:8]).reshape(4, 2)
    mesh = Mesh(devs, ("dp", "tp"))
    base = dict(vocab_size=64, d_model=32, n_heads=2, n_layers=2, d_ff=48,
                max_seq_len=32, dtype="float32")
    tokens = jax.random.randint(jax.random.key(0), (8, 32), 0, 64)
    m_flash = Transformer(TransformerConfig(**base, attention_impl="flash"))
    m_dense = Transformer(TransformerConfig(**base, attention_impl="dense"))
    params = m_dense.init(jax.random.key(1), tokens)["params"]
    ref = m_dense.apply({"params": params}, tokens)
    with jax.set_mesh(mesh):
        sharded_tokens = jax.device_put(
            tokens, NamedSharding(mesh, P("dp", None)))
        out = jax.jit(
            lambda p, t: m_flash.apply({"params": p}, t))(params,
                                                          sharded_tokens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-4, rtol=2e-4)


def test_transformer_attention_impl_validated():
    from tensorflowonspark_tpu.models.transformer import (
        Transformer, TransformerConfig)
    cfg = TransformerConfig(vocab_size=16, d_model=16, n_heads=2, n_layers=1,
                            d_ff=16, max_seq_len=8, attention_impl="falsh")
    tokens = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(ValueError, match="attention_impl"):
        Transformer(cfg).init(jax.random.key(0), tokens)


@pytest.mark.parametrize("causal,S,block,D", [
    (True, 48, 32, 32),
    (False, 40, 32, 32),
    (True, 1536, 1024, 64),     # three blocks of 512, sub-tiles of 256
    (False, 1536, 1024, 128),   # no mask at all: every sub-tile plain
    (True, 1100, 1024, 64),     # padded to 2048: sub-tiles the padding
    (False, 1100, 1024, 64),    # crosses are masked, those past it skipped
])
def test_flash_attention_grad_ragged(causal, S, block, D):
    # multi-block accumulation with padded rows/keys in BOTH bwd kernels
    q, k, v = _qkv(B=2 if S < 1024 else 1, S=S, H=4 if S < 1024 else 2, D=D)

    def f_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal, block_q=block,
                                       block_k=block, interpret=True) ** 2)

    def f_ref(q, k, v):
        return jnp.sum(attention_reference(q, k, v, causal=causal) ** 2)

    g_flash = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


def test_flash_attention_grad_bf16():
    q, k, v = _qkv(S=32, dtype=jnp.bfloat16)

    def f(fn, q, k, v):
        return jnp.sum(fn(q, k, v).astype(jnp.float32) ** 2)

    g_flash = jax.grad(lambda *a: f(
        lambda q, k, v: flash_attention(q, k, v, causal=True, block_q=16,
                                        block_k=16, interpret=True),
        *a), argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(lambda *a: f(
        lambda q, k, v: attention_reference(q, k, v, causal=True),
        *a), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        assert a.dtype == jnp.bfloat16
        np.testing.assert_allclose(a.astype(np.float32),
                                   b.astype(np.float32), atol=0.15, rtol=0.15)


def test_flash_block_pick_avoids_padding():
    from tensorflowonspark_tpu.ops.flash_attention import _pick_block
    assert _pick_block(1024, 2048) == 1024   # divides: keep
    assert _pick_block(1024, 1536) == 512    # 1024 pads 33%; 512 divides
    assert _pick_block(1024, 768) == 768     # S <= block: one full block
    assert _pick_block(1024, 3000) == 1024   # no divisor: keep (2.4% pad)
    assert _pick_block(512, 64) == 64        # small sequences clamp
    assert _pick_block(16, 1536) == 16       # explicit small block honored


# ---- a window (sliding-attention layers) -----------------------------------

@pytest.mark.parametrize("S,window,blocks,group,D", [
    (200, 50, (64, 64), 1, 32),      # S and the window both off the block
    (200, 64, (64, 32), 2, 32),      # unequal blocks, GQA
    (96, 1, (32, 32), 1, 32),        # a window of the token itself
    (96, 300, (32, 32), 4, 32),      # wider than the row: masks nothing
    # the sub-tile walk inside resident blocks of 1024 (512 at S=1536)
    (1024, 256, (1024, 1024), 1, 64),    # one block, a sub-tile's window
    (1024, 1024, (1024, 1024), 4, 128),  # the window the row never reaches
    (1024, 300, (1024, 1024), 1, 128),   # no multiple of the sub-tile
    (1536, 256, (1024, 1024), 1, 64),    # ragged for 1024: blocks of 512
    (2048, 1024, (1024, 1024), 4, 64),   # two blocks, edge in the first
    (2048, 1000, (1024, 1024), 1, 128),  # two blocks, off the sub-tile
])
def test_flash_window_forward_and_backward_match_dense(S, window, blocks,
                                                       group, D):
    """Against the dense windowed path the model's CPU branch runs
    (`dot_product_attention(window=)`): key blocks left of the window are
    skipped and their index maps clamped, in all three kernels."""
    from tensorflowonspark_tpu.models.transformer import (
        dot_product_attention)
    from tensorflowonspark_tpu.parallel.ring_attention import _kv_repeat

    q, k, v = _qkv(B=1, S=S, D=D)
    k, v = k[:, :, ::group], v[:, :, ::group]

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, window=window,
                               block_q=blocks[0], block_k=blocks[1],
                               interpret=True)

    def dense(q, k, v):
        kf, vf = _kv_repeat(q, k, v)
        return dot_product_attention(q, kf, vf, causal=True, window=window)

    np.testing.assert_allclose(flash(q, k, v), dense(q, k, v), atol=2e-5,
                               rtol=2e-5)
    w = jax.random.normal(jax.random.key(9), q.shape)
    got = jax.grad(lambda *a: jnp.sum(flash(*a) * w), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(dense(*a) * w), (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


def test_flash_window_skips_the_blocks_left_of_it():
    """The clamps of the block index maps, by hand: query block 5 of 128
    rows under a window of 200 sees keys 441..767, blocks 3..5; key block
    2 is seen by queries 256..582, blocks 2..4."""
    from tensorflowonspark_tpu.ops.flash_attention import (
        _k_blocks_of, _q_blocks_of)

    seen = _k_blocks_of(5, 128, 128, True, 200, 8)
    assert [int(seen(j)) for j in range(8)] == [3, 3, 3, 3, 4, 5, 5, 5]
    seeing = _q_blocks_of(2, 128, 128, True, 200, 8)
    assert [int(seeing(i)) for i in range(8)] == [2, 2, 2, 3, 4, 4, 4, 4]
    same = _k_blocks_of(5, 128, 128, True, None, 8)
    assert [same(j) for j in range(8)] == list(range(8))
    with pytest.raises(ValueError, match="window"):
        flash_attention(*_qkv(S=16), window=0)


@pytest.mark.parametrize("causal,S,D", [(True, 1024, 64), (False, 1024, 128),
                                        (True, 2048, 64)])
def test_flash_with_lse_and_a_cotangent_on_lse(causal, S, D):
    """`flash_attention_with_lse` (ring attention's local step): both
    outputs and the gradients through BOTH, against the dense softmax."""
    from tensorflowonspark_tpu.ops.flash_attention import (
        flash_attention_with_lse)

    q, k, v = _qkv(B=1, S=S, H=2, D=D)
    w = jax.random.normal(jax.random.key(5), q.shape)
    u = jax.random.normal(jax.random.key(6), (1, 2, S))

    def dense(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / D ** 0.5
        if causal:
            s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -1e30)
        return (jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v),
                jax.nn.logsumexp(s, axis=-1))

    def flash(q, k, v):
        return flash_attention_with_lse(q, k, v, causal=causal,
                                        interpret=True)

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
