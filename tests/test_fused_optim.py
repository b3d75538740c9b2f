"""Single-pass fused optimizer kernels (ops/fused_optim) — parity vs the
optax references, the fused apply path, and sharded-state placement.

Runs in the fast tier: interpret mode executes the REAL kernel bodies on
the CPU mesh (ops.default_interpret), so the math that ships to TPU is
what these tests check.
"""
import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp
import optax

from tensorflowonspark_tpu import optim, trace
from tensorflowonspark_tpu.ops import fused_optim


def _params():
    r = np.random.RandomState(0)
    return {
        "w": jnp.asarray(r.randn(20, 48), jnp.float32),    # pads: 960 % 128
        "emb": jnp.asarray(r.randn(4, 2, 64), jnp.float32),  # 3-D, exact
        "b": jnp.asarray(r.randn(7), jnp.float32),         # tiny tail block
    }


_MASK = {"w": True, "emb": True, "b": False}


def _grads(params, i):
    # step 2 blows the global norm up so clipping ENGAGES there and stays
    # inactive on the other steps — both clip branches get exercised
    scale = 40.0 if i == 2 else 0.4
    return jax.tree_util.tree_map(
        lambda p: scale * p + 0.1 * (i + 1), params)


def test_adamw_fused_matches_optax_chain():
    sched = optim.make_schedule(3e-3, "cosine", warmup_steps=2,
                                total_steps=20)
    ref = optax.chain(optax.clip_by_global_norm(1.0),
                      optax.adamw(sched, weight_decay=0.1, mask=_MASK))
    fused = fused_optim.adamw_fused(sched, weight_decay=0.1, mask=_MASK,
                                    clip_norm=1.0)
    p_ref = p_upd = p_app = _params()
    s_ref, s_upd, s_app = ref.init(p_ref), fused.init(p_upd), fused.init(p_app)
    for i in range(5):
        g = _grads(p_ref, i)
        u, s_ref = ref.update(g, s_ref, p_ref)
        p_ref = optax.apply_updates(p_ref, u)
        u2, s_upd = fused.update(g, s_upd, p_upd)
        p_upd = optax.apply_updates(p_upd, u2)
        p_app, s_app = fused.apply(g, s_app, p_app)
    for k in p_ref:
        np.testing.assert_allclose(p_upd[k], p_ref[k], rtol=1e-6, atol=1e-7)
        np.testing.assert_allclose(p_app[k], p_ref[k], rtol=1e-6, atol=1e-7)
        np.testing.assert_allclose(s_upd.mu[k], s_app.mu[k], rtol=0, atol=0)
        np.testing.assert_allclose(s_upd.nu[k], s_app.nu[k], rtol=0, atol=0)
    assert int(s_upd.count) == 5
    # the undecayed leaf really skipped decay: compare against a no-decay
    # run (masking must differ from decaying everything)
    nofused = fused_optim.adamw_fused(sched, weight_decay=0.1, clip_norm=1.0)
    p2, s2 = _params(), None
    s2 = nofused.init(p2)
    for i in range(5):
        p2, s2 = nofused.apply(_grads(p2, i), s2, p2)
    assert not np.allclose(p2["b"], p_app["b"])   # "b" is masked off above


def test_clip_actually_engages():
    """Same grads, clip on vs off -> different params (the clip scale is
    not a silent 1.0), and the clipped run matches optax exactly."""
    on = fused_optim.adamw_fused(1e-2, clip_norm=0.5)
    off = fused_optim.adamw_fused(1e-2)
    ref = optax.chain(optax.clip_by_global_norm(0.5), optax.adam(1e-2))
    p_on = p_off = p_ref = _params()
    s_on, s_off, s_ref = on.init(p_on), off.init(p_off), ref.init(p_ref)
    # two steps with DIFFERENT grads: adam's per-element normalization makes
    # a uniform scale cancel on step one, but momentum mixing across steps
    # keeps the clip scale observable
    for i in (0, 2):
        g = _grads(p_on, i)
        p_on, s_on = on.apply(g, s_on, p_on)
        p_off, s_off = off.apply(g, s_off, p_off)
        u, s_ref = ref.update(g, s_ref, p_ref)
        p_ref = optax.apply_updates(p_ref, u)
    assert not np.allclose(p_on["w"], p_off["w"])
    np.testing.assert_allclose(p_on["w"], p_ref["w"], rtol=1e-6, atol=1e-7)


def test_lion_fused_matches_optax_chain():
    ref = optax.chain(optax.clip_by_global_norm(1.0),
                      optax.lion(1e-3, weight_decay=0.05, mask=_MASK))
    fused = fused_optim.lion_fused(1e-3, weight_decay=0.05, mask=_MASK,
                                   clip_norm=1.0)
    p_ref = p_f = _params()
    s_ref, s_f = ref.init(p_ref), fused.init(p_f)
    for i in range(5):
        g = _grads(p_ref, i)
        u, s_ref = ref.update(g, s_ref, p_ref)
        p_ref = optax.apply_updates(p_ref, u)
        p_f, s_f = fused.apply(g, s_f, p_f)
    for k in p_ref:
        np.testing.assert_allclose(p_f[k], p_ref[k], rtol=1e-6, atol=1e-7)
    assert int(s_f.count) == 5


def test_mu_dtype_bf16_variant():
    ref = optax.adamw(1e-2, mu_dtype=jnp.bfloat16)
    fused = fused_optim.adamw_fused(1e-2, mu_dtype="bfloat16")
    p_ref = p_f = _params()
    s_ref, s_f = ref.init(p_ref), fused.init(p_f)
    for i in range(4):
        g = _grads(p_ref, i)
        u, s_ref = ref.update(g, s_ref, p_ref)
        p_ref = optax.apply_updates(p_ref, u)
        p_f, s_f = fused.apply(g, s_f, p_f)
    assert s_f.mu["w"].dtype == jnp.bfloat16
    assert s_f.nu["w"].dtype == jnp.float32
    for k in p_ref:
        # both sides store bf16 momentum (~3 decimal digits), so expression
        # -order drift lands at bf16 resolution, not f32
        np.testing.assert_allclose(p_f[k], p_ref[k], rtol=1e-3, atol=1e-4)


# leaf shapes on every side of `_direct_view`'s test, with the path each
# takes: direct leaves are blocked in their own [rows, lanes], the others
# packed to [n, 128]
LEAF_SHAPES = {
    "rows-ragged": ((1003, 256), "direct"),
    "lanes-ragged": ((64, 300), "direct"),
    "both-ragged": ((1003, 300), "direct"),
    "lane-blocks-ragged": ((48, 1100), "direct"),   # 512 + 512 + 76 lanes
    "3d-folds": ((4, 32, 256), "direct"),
    "3d-does-not-fold": ((3, 10, 256), "packed"),
    "narrow-last-dim": ((256, 64), "packed"),
    "1d": ((1280,), "packed"),
    "scalar": ((), "packed"),
}

_OPTIMIZERS = {
    # name -> (fused, optax reference, rtol, atol)
    "adamw-f32": (
        lambda: fused_optim.adamw_fused(3e-3, weight_decay=0.1,
                                        clip_norm=1.0),
        lambda: optax.chain(optax.clip_by_global_norm(1.0),
                            optax.adamw(3e-3, weight_decay=0.1)),
        1e-6, 1e-7),
    # both sides store bf16 momentum: drift lands at bf16 resolution
    "adamw-bf16-mu": (
        lambda: fused_optim.adamw_fused(3e-3, weight_decay=0.1,
                                        clip_norm=1.0, mu_dtype="bfloat16"),
        lambda: optax.chain(optax.clip_by_global_norm(1.0),
                            optax.adamw(3e-3, weight_decay=0.1,
                                        mu_dtype=jnp.bfloat16)),
        1e-3, 1e-4),
    "lion": (
        lambda: fused_optim.lion_fused(1e-3, weight_decay=0.05,
                                       clip_norm=1.0),
        lambda: optax.chain(optax.clip_by_global_norm(1.0),
                            optax.lion(1e-3, weight_decay=0.05)),
        1e-6, 1e-7),
}


@pytest.mark.parametrize("method", ["apply", "update"])
@pytest.mark.parametrize("optimizer", list(_OPTIMIZERS))
@pytest.mark.parametrize("leaf", list(LEAF_SHAPES))
def test_fused_matches_optax_by_leaf_shape(leaf, optimizer, method):
    """Step for step against the optax chain, whichever blocking the
    leaf's shape chooses; ragged last blocks in rows and in lanes write
    nothing beyond the leaf and read nothing into it."""
    shape, _ = LEAF_SHAPES[leaf]
    make_fused, make_ref, rtol, atol = _OPTIMIZERS[optimizer]
    fused, ref = make_fused(), make_ref()
    p_ref = p_f = {"x": jnp.asarray(
        np.random.RandomState(1).randn(*shape), jnp.float32)}
    s_ref, s_f = ref.init(p_ref), fused.init(p_f)
    for i in range(3):
        g = _grads(p_ref, i)
        u, s_ref = ref.update(g, s_ref, p_ref)
        p_ref = optax.apply_updates(p_ref, u)
        if method == "apply":
            p_f, s_f = fused.apply(g, s_f, p_f)
        else:
            u_f, s_f = fused.update(g, s_f, p_f)
            p_f = optax.apply_updates(p_f, u_f)
        assert p_f["x"].shape == shape and s_f.mu["x"].shape == shape
        np.testing.assert_allclose(p_f["x"], p_ref["x"], rtol=rtol,
                                   atol=atol)
    # a bf16 moment may round the other way: one unit of its last place
    np.testing.assert_allclose(
        np.asarray(s_f.mu["x"], np.float32),
        np.asarray(s_ref[1][0].mu["x"], np.float32),
        rtol=1e-2 if s_f.mu["x"].dtype == jnp.bfloat16 else 1e-5, atol=atol)


def test_counters_say_which_path_each_leaf_took():
    """`adamw.elems.direct` / `adamw.elems.packed` count each leaf's
    elements by the blocking its shape chose, once a traced call."""
    params = {k: jnp.ones(shape, jnp.float32)
              for k, (shape, _) in LEAF_SHAPES.items()}
    want = {"direct": 0, "packed": 0}
    for shape, path in LEAF_SHAPES.values():
        assert (fused_optim._direct_view(shape) is not None) == (
            path == "direct")
        want[path] += int(np.prod(shape, dtype=np.int64))
    names = {k: "adamw.elems." + k for k in want}
    for opt in (fused_optim.adamw_fused(1e-3), fused_optim.lion_fused(1e-3)):
        before = {k: trace.counters().get(n) for k, n in names.items()}
        step = jax.jit(opt.apply)
        state = opt.init(params)
        for _ in range(2):          # the second call is not traced again
            new, state = step(params, state, params)
        assert {k: trace.counters().get(n) - before[k]
                for k, n in names.items()} == want
    assert new["scalar"].shape == ()


def test_update_requires_params_for_decay():
    fused = fused_optim.adamw_fused(1e-3, weight_decay=0.1)
    p = _params()
    s = fused.init(p)
    g = jax.tree_util.tree_map(jnp.ones_like, p)
    with pytest.raises(ValueError, match="requires params"):
        fused.update(g, s)
    # decay-less update without params is the optax-legal form
    nodecay = fused_optim.lion_fused(1e-3)
    u, _ = nodecay.update(g, nodecay.init(p))
    assert u["w"].shape == p["w"].shape


def test_make_optimizer_fused_wiring():
    params = {"dense": {"kernel": jnp.ones((4, 4)), "bias": jnp.zeros(4)}}
    opt, sched = optim.make_optimizer(
        "adamw_fused", learning_rate=1e-2, schedule="cosine", warmup_steps=2,
        total_steps=50, weight_decay=0.1, clip_norm=1.0,
        mu_dtype="bfloat16", decay_mask=optim.default_decay_mask(params))
    assert callable(opt.apply)          # the single-pass entry point
    state = opt.init(params)

    def loss(p):
        return jnp.sum(p["dense"]["kernel"] ** 2) + jnp.sum(
            p["dense"]["bias"] ** 2)

    for _ in range(5):
        params, state = opt.apply(jax.grad(loss)(params), state, params)
    assert float(loss(params)) < 16.0
    lion, _ = optim.make_optimizer("lion_fused", learning_rate=1e-3,
                                   weight_decay=0.01)
    lion.init(params)
    with pytest.raises(ValueError):     # mu_dtype stays adam/adamw/lion-only
        optim.make_optimizer("adafactor", mu_dtype="bfloat16")


def test_train_step_takes_fused_apply_path():
    """make_train_step must route through .apply (param write fused) and
    produce the same params as the optax reference step."""
    from tensorflowonspark_tpu.parallel import train as train_mod

    def loss_fn(p, batch, rng):
        return jnp.mean((batch @ p["w"] + p["b"]) ** 2)

    params = {"w": jnp.asarray(np.random.RandomState(3).randn(16, 8),
                               jnp.float32),
              "b": jnp.zeros((8,), jnp.float32)}
    batch = jnp.asarray(np.random.RandomState(4).randn(4, 16), jnp.float32)

    fused, _ = optim.make_optimizer("adamw_fused", learning_rate=1e-2,
                                    clip_norm=1.0)
    ref, _ = optim.make_optimizer("adamw", learning_rate=1e-2, clip_norm=1.0)
    sf = train_mod.TrainState(jnp.zeros((), jnp.int32), params,
                              fused.init(params))
    sr = train_mod.TrainState(jnp.zeros((), jnp.int32), params,
                              ref.init(params))
    step_f = train_mod.make_train_step(loss_fn, fused, donate=False)
    step_r = train_mod.make_train_step(loss_fn, ref, donate=False)
    for _ in range(3):
        sf, mf = step_f(sf, batch, jax.random.key(0))
        sr, mr = step_r(sr, batch, jax.random.key(0))
    np.testing.assert_allclose(sf.params["w"], sr.params["w"],
                               rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(float(mf["grad_norm"]), float(mr["grad_norm"]),
                               rtol=1e-6)
    assert int(sf.step) == 3


def test_sharded_params_place_fused_state():
    """Under explicit fsdp x tp shardings the fused moments shard by each
    param's FULL spec (they mirror the param tree), count replicates."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from tensorflowonspark_tpu.parallel import train as train_mod

    mesh = Mesh(np.array(jax.devices()[:8]).reshape(4, 2), ("fsdp", "tp"))
    params = {"w": jnp.ones((16, 8), jnp.float32),
              "b": jnp.zeros((8,), jnp.float32)}
    shardings = {"w": NamedSharding(mesh, P("fsdp", "tp")),
                 "b": NamedSharding(mesh, P())}
    opt, _ = optim.make_optimizer("adamw_fused", learning_rate=1e-2,
                                  weight_decay=0.1, clip_norm=1.0,
                                  decay_mask={"w": True, "b": False})

    repl = NamedSharding(mesh, P())
    placed = train_mod._opt_state_shardings(opt, shardings, repl)
    assert placed.mu == shardings and placed.nu == shardings
    assert placed.count == repl

    def loss_fn(p, batch, rng):
        return jnp.mean((batch @ p["w"] + p["b"]) ** 2)

    state = train_mod.create_train_state(params, opt, mesh=mesh,
                                         param_shardings=shardings)
    step = train_mod.make_train_step(loss_fn, opt, mesh=mesh,
                                     param_shardings=shardings)
    batch = jnp.ones((8, 16), jnp.float32)
    losses = []
    for _ in range(2):
        state, m = step(state, batch, jax.random.key(0))
        losses.append(float(m["loss"]))
    assert losses[1] < losses[0]
    assert state.opt_state.mu["w"].sharding.spec == P("fsdp", "tp")
    assert state.params["w"].sharding.spec == P("fsdp", "tp")
