"""Training-backend tests on the virtual 8-device CPU mesh.

Ground truths are analytic (known regression weights), mirroring the
reference's test style (tests/test_pipeline.py:89-172 trained a linear model
against known weights)."""
import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp
import optax

from tensorflowonspark_tpu.parallel import mesh as mesh_mod
from tensorflowonspark_tpu.parallel import sharding as sharding_mod
from tensorflowonspark_tpu.parallel import train as train_mod


def test_mesh_resolve_and_build():
    spec = mesh_mod.MeshSpec(dp=-1, fsdp=1, pp=2, tp=2).resolve(8)
    assert spec.shape == (2, 1, 2, 2)
    m = mesh_mod.build_mesh(mesh_mod.MeshSpec(dp=-1))
    assert m.shape == {"dp": 8, "fsdp": 1, "pp": 1, "tp": 1}
    with pytest.raises(ValueError):
        mesh_mod.MeshSpec(dp=3, tp=3).resolve(8)


def test_sharding_rules():
    P = sharding_mod.P
    assert sharding_mod.spec_for_path("layer_0/attn/query/kernel") == P(None, "tp")
    assert sharding_mod.spec_for_path("layer_0/attn/out/kernel") == P("tp", None)
    assert sharding_mod.spec_for_path("layer_0/mlp/wi/kernel") == P(None, "tp")
    assert sharding_mod.spec_for_path("layer_0/mlp/wo/kernel") == P("tp", None)
    assert sharding_mod.spec_for_path("token_embed/embedding") == P(None, "tp")
    assert sharding_mod.spec_for_path("layer_0/ln/scale") == P()
    assert sharding_mod.spec_for_path("moe/experts_wi/kernel") == P("dp", None, "tp")
    assert sharding_mod.spec_for_path("moe/router/kernel") == P()
    assert sharding_mod.spec_for_path("some/other/kernel") == P()


def _linreg_data(n=512, d=8, seed=1234):
    rng = np.random.RandomState(seed)
    w_true = rng.randn(d).astype(np.float32)
    b_true = np.float32(0.7)
    X = rng.randn(n, d).astype(np.float32)
    y = X @ w_true + b_true
    return X, y, w_true, b_true


def test_dp_training_converges_to_known_weights():
    mesh = mesh_mod.build_mesh(mesh_mod.MeshSpec(dp=-1))
    X, y, w_true, b_true = _linreg_data()
    params = {"w": jnp.zeros(8), "b": jnp.zeros(())}

    def loss_fn(params, batch, rng):
        X, y = batch
        pred = X @ params["w"] + params["b"]
        return jnp.mean((pred - y) ** 2)

    opt = optax.adam(0.1)
    shardings = sharding_mod.infer_param_shardings(params, mesh)
    state = train_mod.create_train_state(params, opt, mesh, shardings)
    step = train_mod.make_train_step(loss_fn, opt, mesh, shardings)
    rng = jax.random.key(0)
    metrics = None
    for i in range(200):
        state, metrics = step(state, (X, y), rng)
        if i % 16 == 15:
            # Keep the dispatch queue short.  Past 32 programs in flight
            # per device, XLA's CPU client can deadlock an 8-way
            # all-reduce when the box is loaded: 7 of 8 threads join the
            # rendezvous and the runtime aborts the process after 40 s
            # (jaxlib 0.9.0; 5 of 8 runs under 12 busy processes at the
            # seed, 0 of 8 with this wait).
            jax.block_until_ready(metrics["loss"])
    assert float(metrics["loss"]) < 1e-3
    np.testing.assert_allclose(np.asarray(state.params["w"]), w_true, atol=1e-2)
    np.testing.assert_allclose(float(state.params["b"]), b_true, atol=1e-2)
    assert int(state.step) == 200


def test_grad_accum_matches_full_batch():
    X, y, _, _ = _linreg_data(n=64)
    params = {"w": jnp.zeros(8), "b": jnp.zeros(())}

    def loss_fn(params, batch, rng):
        X, y = batch
        pred = X @ params["w"] + params["b"]
        return jnp.mean((pred - y) ** 2)

    opt = optax.sgd(0.01)
    s1 = train_mod.TrainState(jnp.zeros((), jnp.int32), params, opt.init(params))
    s2 = train_mod.TrainState(jnp.zeros((), jnp.int32), params, opt.init(params))
    step1 = train_mod.make_train_step(loss_fn, opt, donate=False)
    step4 = train_mod.make_train_step(loss_fn, opt, grad_accum=4, donate=False)
    rng = jax.random.key(0)
    s1, m1 = step1(s1, (X, y), rng)
    s4, m4 = step4(s2, (X, y), rng)
    # a mean-loss over the full batch == mean of per-microbatch mean losses
    np.testing.assert_allclose(float(m1["loss"]), float(m4["loss"]), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(s1.params["w"]),
                               np.asarray(s4.params["w"]), rtol=1e-5)


def test_fsdp_shards_largest_dim():
    mesh = mesh_mod.build_mesh(mesh_mod.MeshSpec(dp=-1, fsdp=4))
    assert mesh.shape["fsdp"] == 4
    params = {"fc1": {"kernel": jnp.zeros((784, 512)), "bias": jnp.zeros(512)}}
    sh = sharding_mod.infer_param_shardings(params, mesh, fsdp=True)
    kernel_spec = sh["fc1"]["kernel"].spec
    assert "fsdp" in tuple(kernel_spec)
    # ZeRO-3 shards every divisible param, biases included
    assert tuple(sh["fc1"]["bias"].spec) == ("fsdp",)
    # indivisible params stay replicated
    odd = {"w": jnp.zeros((7, 3))}
    sh_odd = sharding_mod.infer_param_shardings(odd, mesh, fsdp=True)
    assert tuple(sh_odd["w"].spec) == ()


def test_mlp_trains_on_mesh():
    from tensorflowonspark_tpu.models.mlp import MnistMLP, cross_entropy_loss
    mesh = mesh_mod.build_mesh(mesh_mod.MeshSpec(dp=-1))
    model = MnistMLP(hidden=32)
    rng = jax.random.key(0)
    X = jax.random.normal(rng, (64, 784))
    y = jax.random.randint(rng, (64,), 0, 10)
    params = model.init(rng, X)["params"]

    def loss_fn(params, batch, rng):
        X, y = batch
        return cross_entropy_loss(model.apply({"params": params}, X), y)

    opt = optax.adam(1e-2)
    shardings = sharding_mod.infer_param_shardings(params, mesh)
    state = train_mod.create_train_state(params, opt, mesh, shardings)
    step = train_mod.make_train_step(loss_fn, opt, mesh, shardings)
    losses = []
    for _ in range(30):
        state, m = step(state, (X, y), rng)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] * 0.5  # memorizes the batch
