"""Ulysses all-to-all sequence parallelism == dense attention on the
8-way sequence-sharded mesh (exactness by construction, like ring)."""
import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from tensorflowonspark_tpu.models.transformer import dot_product_attention
from tensorflowonspark_tpu.parallel import mesh as mesh_mod
from tensorflowonspark_tpu.parallel.ulysses import ulysses_attention


@pytest.fixture(scope="module")
def qkv():
    rng = np.random.RandomState(0)
    B, S, H, D = 2, 64, 8, 16  # H=8 divides the 8-way axis
    q = rng.randn(B, S, H, D).astype(np.float32)
    k = rng.randn(B, S, H, D).astype(np.float32)
    v = rng.randn(B, S, H, D).astype(np.float32)
    return jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_matches_dense(qkv, causal):
    q, k, v = qkv
    mesh = mesh_mod.build_mesh(mesh_mod.MeshSpec(dp=1, tp=8))
    dense = dot_product_attention(q, k, v, causal=causal)
    out = ulysses_attention(q, k, v, axis_name="tp", causal=causal,
                            mesh=mesh)
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense),
                               atol=2e-5, rtol=2e-5)


def test_ulysses_under_jit_and_grad(qkv):
    q, k, v = qkv
    mesh = mesh_mod.build_mesh(mesh_mod.MeshSpec(dp=1, tp=8))

    @jax.jit
    def f(q, k, v):
        return ulysses_attention(q, k, v, axis_name="tp", causal=True,
                                 mesh=mesh).sum()

    g = jax.grad(f)(q, k, v)
    assert g.shape == q.shape and bool(jnp.all(jnp.isfinite(g)))

    def f_ref(q, k, v):
        return dot_product_attention(q, k, v, causal=True).sum()

    g_ref = jax.grad(f_ref)(q, k, v)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                               atol=2e-5, rtol=2e-5)


def test_ulysses_rejects_indivisible_heads(qkv):
    q, k, v = qkv
    q6 = q[:, :, :6]  # 6 heads over an 8-way axis
    mesh = mesh_mod.build_mesh(mesh_mod.MeshSpec(dp=1, tp=8))
    with pytest.raises(ValueError, match="divisible by"):
        ulysses_attention(q6, k[:, :, :6], v[:, :, :6], axis_name="tp",
                          mesh=mesh)


@pytest.mark.parametrize("cp_field", ["ulysses_axis", "ring_attention_axis"])
def test_transformer_cp_dispatch_matches_dense(cp_field):
    # the model-level knobs must engage under plain jit + set_mesh (no
    # explicit shard_map): _seqpar_dispatch wraps the attention core itself
    from tensorflowonspark_tpu.models.transformer import (
        Transformer, TransformerConfig)
    base = dict(vocab_size=64, d_model=32, n_heads=8, n_layers=2, d_ff=64,
                max_seq_len=32, dtype="float32")
    tokens = jnp.asarray(
        np.random.RandomState(1).randint(0, 64, (4, 32)), jnp.int32)
    ref_model = Transformer(TransformerConfig(**base))
    params = ref_model.init(jax.random.key(0), tokens)["params"]
    ref = ref_model.apply({"params": params}, tokens)

    cp_model = Transformer(TransformerConfig(**base, **{cp_field: "tp"}))
    mesh = mesh_mod.build_mesh(mesh_mod.MeshSpec(dp=2, tp=4))
    with jax.set_mesh(mesh):
        out = jax.jit(
            lambda p, t: cp_model.apply({"params": p}, t))(params, tokens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=3e-5, rtol=3e-5)


def test_transformer_cp_rejects_indivisible_seq():
    from tensorflowonspark_tpu.models.transformer import (
        Transformer, TransformerConfig)
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=8,
                            n_layers=1, d_ff=64, max_seq_len=32,
                            dtype="float32", ulysses_axis="tp")
    model = Transformer(cfg)
    tokens = jnp.zeros((2, 30), jnp.int32)  # 30 % 4 != 0
    mesh = mesh_mod.build_mesh(mesh_mod.MeshSpec(dp=2, tp=4))
    with jax.set_mesh(mesh):
        with pytest.raises(ValueError, match="divisible by"):
            model.init(jax.random.key(0), tokens)


@pytest.mark.parametrize("cp_field", ["ulysses_axis", "ring_attention_axis"])
def test_transformer_cp_dense_impl_matches(cp_field):
    # attention_impl='dense' must plumb through the CP dispatch (ring:
    # use_flash=False, ulysses: dense attn core) and stay exact
    from tensorflowonspark_tpu.models.transformer import (
        Transformer, TransformerConfig)
    base = dict(vocab_size=64, d_model=32, n_heads=8, n_layers=1, d_ff=64,
                max_seq_len=32, dtype="float32")
    tokens = jnp.asarray(
        np.random.RandomState(2).randint(0, 64, (2, 32)), jnp.int32)
    ref_model = Transformer(TransformerConfig(**base))
    params = ref_model.init(jax.random.key(0), tokens)["params"]
    ref = ref_model.apply({"params": params}, tokens)

    cp_model = Transformer(TransformerConfig(
        **base, attention_impl="dense", **{cp_field: "tp"}))
    mesh = mesh_mod.build_mesh(mesh_mod.MeshSpec(dp=1, tp=8))
    with jax.set_mesh(mesh):
        out = jax.jit(
            lambda p, t: cp_model.apply({"params": p}, t))(params, tokens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize("n_kv", [1, 2, 4])
def test_ulysses_narrow_kv_matches_repeated(qkv, n_kv):
    # GQA: narrow kv through the all-to-alls == dense with repeated kv
    q, k, v = qkv                      # H=8 over the 8-way axis
    kn, vn = k[:, :, :n_kv], v[:, :, :n_kv]
    rep = q.shape[2] // n_kv
    dense = dot_product_attention(q, jnp.repeat(kn, rep, axis=2),
                                  jnp.repeat(vn, rep, axis=2), causal=True)
    mesh = mesh_mod.build_mesh(mesh_mod.MeshSpec(dp=1, tp=8))
    out = ulysses_attention(q, kn, vn, axis_name="tp", causal=True,
                            mesh=mesh)
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense),
                               atol=2e-5, rtol=2e-5)


def test_ulysses_truly_narrow_kv_into_core(qkv):
    # pre < rep: with tp=4 and n_kv=4 no pre-repeat happens (pre=1), so
    # the attention core itself receives GQA-narrow kv after the
    # all-to-all — the round-5 narrow_ok path is genuinely exercised
    # (with tp=8, every n_kv<8 case fully pre-repeats and the skipped
    # local repeat was a no-op)
    q, k, v = qkv                      # H=8
    kn, vn = k[:, :, :4], v[:, :, :4]
    dense = dot_product_attention(q, jnp.repeat(kn, 2, axis=2),
                                  jnp.repeat(vn, 2, axis=2), causal=True)
    mesh = mesh_mod.build_mesh(mesh_mod.MeshSpec(dp=1, tp=4),
                               devices=jax.devices()[:4])
    out = ulysses_attention(q, kn, vn, axis_name="tp", causal=True,
                            mesh=mesh)
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense),
                               atol=2e-5, rtol=2e-5)
