"""Transformer family tests: TP/SP/EP numerics on the virtual 8-device mesh.

The key invariant: sharded execution must produce the SAME numbers as
single-device execution (parallelism is an implementation detail)."""
import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp
import optax

from tensorflowonspark_tpu.models.transformer import (
    Transformer, TransformerConfig, lm_loss)
from tensorflowonspark_tpu.parallel import mesh as mesh_mod
from tensorflowonspark_tpu.parallel import sharding as sharding_mod
from tensorflowonspark_tpu.parallel import train as train_mod

CFG = TransformerConfig(vocab_size=128, d_model=64, n_heads=4, n_layers=2,
                        d_ff=128, max_seq_len=32, dtype="float32")


@pytest.fixture(scope="module")
def toy_batch():
    rng = np.random.RandomState(0)
    tokens = rng.randint(0, 128, size=(4, 32)).astype(np.int32)
    return jnp.asarray(tokens)


def test_forward_shapes(toy_batch):
    model = Transformer(CFG)
    params = model.init(jax.random.key(0), toy_batch)["params"]
    logits = model.apply({"params": params}, toy_batch)
    assert logits.shape == (4, 32, 128)


def test_tp_sp_matches_single_device(toy_batch):
    model = Transformer(CFG)
    params = model.init(jax.random.key(0), toy_batch)["params"]
    ref_logits = model.apply({"params": params}, toy_batch)

    mesh = mesh_mod.build_mesh(mesh_mod.MeshSpec(dp=2, tp=4))
    sh = sharding_mod.infer_param_shardings(params, mesh)
    # tp rules must actually engage on this mesh
    flat = jax.tree_util.tree_leaves_with_path(sh)
    tp_sharded = [p for p, s in flat if "tp" in tuple(s.spec)]
    assert tp_sharded, "no parameter picked up a tp sharding"

    sp_model = Transformer(
        TransformerConfig(**{**CFG.__dict__, "sp_axis": "tp"}))
    sharded_params = sharding_mod.shard_params(params, sh)
    with jax.set_mesh(mesh):
        out = jax.jit(
            lambda p, t: sp_model.apply({"params": p}, t),
            in_shardings=(sh, mesh_mod.batch_sharding(mesh)),
        )(sharded_params, toy_batch)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_logits),
                               atol=3e-5, rtol=3e-5)


def test_moe_ep_matches_single_device(toy_batch):
    cfg = TransformerConfig(**{**CFG.__dict__, "num_experts": 4})
    model = Transformer(cfg)
    params = model.init(jax.random.key(1), toy_batch)["params"]
    ref = model.apply({"params": params}, toy_batch)

    # expert weights exist and are ep(=dp)-sharded on the mesh
    mesh = mesh_mod.build_mesh(mesh_mod.MeshSpec(dp=4, tp=2))
    sh = sharding_mod.infer_param_shardings(params, mesh)
    moe_layers = [k for k in params if "layer" in k and
                  "moe" in params[k]]
    assert moe_layers, "MoE layer missing"
    wi_spec = tuple(sh[moe_layers[0]]["moe"]["experts_wi/kernel"].spec)
    assert wi_spec[0] == "dp"  # ep rides the dp axis

    sharded = sharding_mod.shard_params(params, sh)
    with jax.set_mesh(mesh):
        out = jax.jit(
            lambda p, t: model.apply({"params": p}, t),
            in_shardings=(sh, mesh_mod.batch_sharding(mesh)),
        )(sharded, toy_batch)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=3e-5, rtol=3e-5)


def test_lm_training_step_decreases_loss(toy_batch):
    model = Transformer(CFG)
    params = model.init(jax.random.key(0), toy_batch)["params"]
    mesh = mesh_mod.build_mesh(mesh_mod.MeshSpec(dp=2, tp=4))
    sh = sharding_mod.infer_param_shardings(params, mesh)

    def loss_fn(params, batch, rng):
        tokens = batch
        logits = model.apply({"params": params}, tokens[:, :-1])
        return lm_loss(logits, tokens[:, 1:])

    opt = optax.adam(1e-3)
    with jax.set_mesh(mesh):
        state = train_mod.create_train_state(params, opt, mesh, sh)
        step = train_mod.make_train_step(loss_fn, opt, mesh, sh)
        rng = jax.random.key(0)
        losses = []
        for _ in range(10):
            state, m = step(state, toy_batch, rng)
            losses.append(float(m["loss"]))
    assert losses[-1] < losses[0]


def test_lm_loss_ignore_mask():
    logits = jnp.zeros((1, 4, 8))
    targets = jnp.array([[1, 2, -1, -1]])
    # uniform logits -> loss = log(8) over the 2 unmasked positions
    np.testing.assert_allclose(float(lm_loss(logits, targets)),
                               float(np.log(8)), rtol=1e-6)


def test_rope_relative_position_invariance():
    # q·k after rotation must depend only on the position DIFFERENCE
    from tensorflowonspark_tpu.models.transformer import apply_rope
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(1, 4, 2, 16).astype("float32"))
    k = jnp.asarray(rng.randn(1, 4, 2, 16).astype("float32"))

    def scores(shift):
        pos = jnp.arange(4) + shift
        qr, kr = apply_rope(q, pos), apply_rope(k, pos)
        return jnp.einsum("bqhd,bkhd->bhqk", qr, kr)

    np.testing.assert_allclose(np.asarray(scores(0)),
                               np.asarray(scores(37)), atol=1e-4)


def test_rope_model_is_position_sensitive(toy_batch):
    cfg = TransformerConfig(**{**CFG.__dict__, "rope": True})
    model = Transformer(cfg)
    params = model.init(jax.random.key(0), toy_batch)["params"]
    assert "pos_embed" not in params  # rope replaces the learned table
    logits = model.apply({"params": params}, toy_batch)
    rolled = model.apply({"params": params},
                         jnp.roll(toy_batch, 1, axis=1))
    # a pure bag-of-tokens model would produce rolled logits; rope must not
    assert not np.allclose(np.asarray(logits),
                           np.asarray(jnp.roll(rolled, -1, axis=1)),
                           atol=1e-3)


def test_gqa_narrow_kv_and_finite_grads(toy_batch):
    cfg = TransformerConfig(**{**CFG.__dict__, "n_kv_heads": 2, "rope": True})
    model = Transformer(cfg)
    params = model.init(jax.random.key(0), toy_batch)["params"]
    head_dim = cfg.d_model // cfg.n_heads
    kv_kernel = params["layer_0"]["attn"]["key"]["kernel"]
    assert kv_kernel.shape == (cfg.d_model, 2 * head_dim)

    def loss(p):
        return lm_loss(model.apply({"params": p}, toy_batch[:, :-1]),
                       toy_batch[:, 1:])

    g = jax.grad(loss)(params)
    flat = jax.tree_util.tree_leaves(g)
    assert all(bool(jnp.all(jnp.isfinite(x))) for x in flat)


def test_gqa_rejects_indivisible_heads(toy_batch):
    cfg = TransformerConfig(**{**CFG.__dict__, "n_kv_heads": 3})
    with pytest.raises(ValueError, match="divisible"):
        Transformer(cfg).init(jax.random.key(0), toy_batch)


@pytest.mark.parametrize("cp_field", ["ulysses_axis", "ring_attention_axis"])
def test_rope_gqa_compose_with_cp(toy_batch, cp_field):
    # rotation happens on globally-indexed activations before the CP
    # dispatch, and GQA kv ride the collectives narrow — both must stay
    # exactly equal to the dense single-device model
    from tensorflowonspark_tpu.parallel import mesh as mesh_mod
    base = {**CFG.__dict__, "rope": True, "n_kv_heads": 2, "n_heads": 8}
    ref = Transformer(TransformerConfig(**base))
    params = ref.init(jax.random.key(0), toy_batch)["params"]
    want = ref.apply({"params": params}, toy_batch)

    cp = Transformer(TransformerConfig(**{**base, cp_field: "tp"}))
    mesh = mesh_mod.build_mesh(mesh_mod.MeshSpec(dp=2, tp=4))
    with jax.set_mesh(mesh):
        got = jax.jit(lambda p, t: cp.apply({"params": p}, t))(
            params, toy_batch)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=3e-5, rtol=3e-5)


def test_rope_cp_under_enclosing_shard_map(toy_batch):
    # the OTHER CP call shape: whole model inside shard_map with the axis
    # manual and activations sequence-sharded; rope must rotate with GLOBAL
    # token positions (axis_index offset), not per-shard 0..S_local
    from jax.sharding import PartitionSpec as P

    from tensorflowonspark_tpu.parallel import mesh as mesh_mod
    base = {**CFG.__dict__, "rope": True, "n_kv_heads": 2}
    ref = Transformer(TransformerConfig(**base))
    params = ref.init(jax.random.key(0), toy_batch)["params"]
    want = ref.apply({"params": params}, toy_batch)

    cp = Transformer(TransformerConfig(**{**base,
                                          "ring_attention_axis": "tp"}))
    mesh = mesh_mod.build_mesh(mesh_mod.MeshSpec(dp=1, tp=8))
    with jax.set_mesh(mesh):
        fn = jax.shard_map(
            lambda p, t: cp.apply({"params": p}, t),
            in_specs=(P(), P(None, "tp")), out_specs=P(None, "tp"),
            check_vma=False)
        got = jax.jit(fn)(params, toy_batch)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=3e-5, rtol=3e-5)


def test_moe_topk_full_capacity_matches_dense_router(toy_batch):
    # with k=1 and capacity >= all tokens, the GShard dispatch must equal
    # the dense (mask-every-expert) router exactly
    base = {**CFG.__dict__, "num_experts": 4, "moe_every": 1}
    dense = Transformer(TransformerConfig(**base))
    params = dense.init(jax.random.key(2), toy_batch)["params"]
    want = dense.apply({"params": params}, toy_batch)

    assert any("moe" in params[k] for k in params
               if k.startswith("layer")), "no MoE layer materialized"
    topk = Transformer(TransformerConfig(
        **{**base, "moe_router": "topk", "moe_top_k": 1,
           "moe_capacity_factor": 4.0}))  # C = 4*T/E = T: no drops
    got = topk.apply({"params": params}, toy_batch)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_moe_topk_tight_capacity_drops_but_stays_finite(toy_batch):
    cfg = TransformerConfig(**{**CFG.__dict__, "num_experts": 4,
                               "moe_every": 1, "moe_router": "topk",
                               "moe_top_k": 2, "moe_capacity_factor": 0.25})
    model = Transformer(cfg)
    params = model.init(jax.random.key(2), toy_batch)["params"]

    def loss(p):
        return lm_loss(model.apply({"params": p}, toy_batch[:, :-1]),
                       toy_batch[:, 1:])

    val, g = jax.value_and_grad(loss)(params)
    assert bool(jnp.isfinite(val))
    assert all(bool(jnp.all(jnp.isfinite(x)))
               for x in jax.tree_util.tree_leaves(g))


def test_moe_router_validation(toy_batch):
    bad = TransformerConfig(**{**CFG.__dict__, "num_experts": 4,
                               "moe_every": 1, "moe_router": "sorted"})
    with pytest.raises(ValueError, match="moe_router"):
        Transformer(bad).init(jax.random.key(0), toy_batch)
    bad_k = TransformerConfig(**{**CFG.__dict__, "num_experts": 4,
                                 "moe_every": 1, "moe_router": "topk",
                                 "moe_top_k": 9})
    with pytest.raises(ValueError, match="moe_top_k"):
        Transformer(bad_k).init(jax.random.key(0), toy_batch)


def test_rmsnorm_variant(toy_batch):
    cfg = TransformerConfig(**{**CFG.__dict__, "norm_type": "rmsnorm"})
    model = Transformer(cfg)
    params = model.init(jax.random.key(0), toy_batch)["params"]
    # RMSNorm is scale-only: no bias/mean-subtraction params anywhere
    ln1 = params["layer_0"]["ln1"]
    assert set(ln1.keys()) == {"scale"}
    logits = model.apply({"params": params}, toy_batch)
    assert logits.shape == (4, 32, 128)

    def loss(p):
        return lm_loss(model.apply({"params": p}, toy_batch[:, :-1]),
                       toy_batch[:, 1:])

    g = jax.grad(loss)(params)
    assert np.isfinite(float(optax.global_norm(g)))
    # TP sharding rules still apply (scale vectors replicate)
    mesh = mesh_mod.build_mesh(mesh_mod.MeshSpec(dp=2, tp=4))
    sharding_mod.infer_param_shardings(params, mesh)


def test_rmsnorm_validation():
    with pytest.raises(ValueError, match="norm_type"):
        Transformer(TransformerConfig(
            **{**CFG.__dict__, "norm_type": "welch"})).init(
            jax.random.key(0), jnp.zeros((1, 8), jnp.int32))


def test_gated_moe_experts(toy_batch):
    # Mixtral-shape: gated experts carry an experts_up branch that shards
    # like experts_wi (ep + tp axes)
    cfg = TransformerConfig(**{**CFG.__dict__, "num_experts": 4,
                               "mlp_style": "gated", "activation": "silu",
                               "moe_router": "topk", "moe_top_k": 2})
    model = Transformer(cfg)
    params = model.init(jax.random.key(0), toy_batch)["params"]
    moe = params["layer_1"]["moe"] if "moe" in params["layer_1"] \
        else params["layer_0"]["moe"]
    assert "experts_up/kernel" in moe
    assert moe["experts_up/kernel"].shape == moe["experts_wi/kernel"].shape
    mesh = mesh_mod.build_mesh(mesh_mod.MeshSpec(dp=2, tp=4))
    sh = sharding_mod.infer_param_shardings(params, mesh)
    up_spec = (sh["layer_1"]["moe"] if "moe" in sh["layer_1"]
               else sh["layer_0"]["moe"])["experts_up/kernel"].spec
    assert up_spec[0] == "dp"          # ep rides the dp axis
    logits = model.apply({"params": params}, toy_batch)
    assert logits.shape == (4, 32, 128)

    def loss(p):
        return lm_loss(model.apply({"params": p}, toy_batch[:, :-1]),
                       toy_batch[:, 1:])

    g = jax.grad(loss)(params)
    gn = float(optax.global_norm(g))
    assert np.isfinite(gn) and gn > 0
