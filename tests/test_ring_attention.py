"""Ring attention == dense attention, on an 8-way sequence-sharded mesh."""
import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from tensorflowonspark_tpu.models.transformer import dot_product_attention
from tensorflowonspark_tpu.parallel import mesh as mesh_mod
from tensorflowonspark_tpu.parallel.ring_attention import ring_attention


@pytest.fixture(scope="module")
def qkv():
    rng = np.random.RandomState(0)
    B, S, H, D = 2, 64, 4, 16
    q = rng.randn(B, S, H, D).astype(np.float32)
    k = rng.randn(B, S, H, D).astype(np.float32)
    v = rng.randn(B, S, H, D).astype(np.float32)
    return jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_matches_dense(qkv, causal):
    q, k, v = qkv
    mesh = mesh_mod.build_mesh(mesh_mod.MeshSpec(dp=1, tp=8))
    dense = dot_product_attention(q, k, v, causal=causal)
    ring = ring_attention(q, k, v, axis_name="tp", causal=causal, mesh=mesh)
    np.testing.assert_allclose(np.asarray(ring), np.asarray(dense),
                               atol=2e-5, rtol=2e-5)


def test_ring_under_jit_and_grad(qkv):
    q, k, v = qkv
    mesh = mesh_mod.build_mesh(mesh_mod.MeshSpec(dp=1, tp=8))

    @jax.jit
    def f(q, k, v):
        return ring_attention(q, k, v, axis_name="tp", causal=True,
                              mesh=mesh).sum()

    @jax.jit
    def f_dense(q, k, v):
        return dot_product_attention(q, k, v, causal=True).sum()

    with jax.set_mesh(mesh):
        g_ring = jax.grad(f)(q, k, v)
    g_dense = jax.grad(f_dense)(q, k, v)
    np.testing.assert_allclose(np.asarray(g_ring), np.asarray(g_dense),
                               atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_flash_local_matches_dense(qkv, causal):
    # kernel-backed ring (interpret mode) must stay exactly dense attention
    q, k, v = qkv
    mesh = mesh_mod.build_mesh(mesh_mod.MeshSpec(dp=1, tp=8))
    dense = dot_product_attention(q, k, v, causal=causal)
    ring = ring_attention(q, k, v, axis_name="tp", causal=causal, mesh=mesh,
                          use_flash=True, interpret=True)
    np.testing.assert_allclose(np.asarray(ring), np.asarray(dense),
                               atol=2e-5, rtol=2e-5)


def test_ring_flash_grad_matches_jnp_path(qkv):
    q, k, v = qkv
    mesh = mesh_mod.build_mesh(mesh_mod.MeshSpec(dp=1, tp=8))

    def loss(impl_kwargs):
        def f(q, k, v):
            return jnp.sum(ring_attention(q, k, v, axis_name="tp",
                                          causal=True, mesh=mesh,
                                          **impl_kwargs) ** 2)
        return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

    g_flash = loss(dict(use_flash=True, interpret=True))
    g_jnp = loss(dict(use_flash=False))
    for a, b in zip(g_flash, g_jnp):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4)


def test_ring_flash_narrow_kv_grad_matches_jnp_path(qkv):
    # round-5: narrow dk/dv come from the kernel's group-grid backward
    # composed with the ring scan/ppermute (no jnp.repeat transpose in
    # the path anymore) — pin the gradient against the jnp ring body
    q, k, v = qkv
    kn, vn = k[:, :, :2], v[:, :, :2]
    mesh = mesh_mod.build_mesh(mesh_mod.MeshSpec(dp=1, tp=8))

    def grads(impl_kwargs):
        def f(q, k, v):
            return jnp.sum(ring_attention(q, k, v, axis_name="tp",
                                          causal=True, mesh=mesh,
                                          **impl_kwargs) ** 2)
        return jax.grad(f, argnums=(0, 1, 2))(q, kn, vn)

    g_flash = grads(dict(use_flash=True, interpret=True))
    g_jnp = grads(dict(use_flash=False))
    assert g_flash[1].shape == kn.shape          # narrow dk stays narrow
    for a, b in zip(g_flash, g_jnp):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("use_flash", [False, True])
def test_ring_narrow_kv_matches_repeated(qkv, use_flash):
    # GQA: kv ride the ring narrow, broadcast per step on-device
    q, k, v = qkv
    kn, vn = k[:, :, :2], v[:, :, :2]
    rep = q.shape[2] // 2
    dense = dot_product_attention(q, jnp.repeat(kn, rep, axis=2),
                                  jnp.repeat(vn, rep, axis=2), causal=True)
    mesh = mesh_mod.build_mesh(mesh_mod.MeshSpec(dp=1, tp=8))
    out = ring_attention(q, kn, vn, axis_name="tp", causal=True, mesh=mesh,
                         use_flash=use_flash, interpret=use_flash or None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense),
                               atol=2e-5, rtol=2e-5)
