"""Sparse experts with a chip's share, window and full attention by layer
kind, explicit head size, rotary parameters by kind: the program
(`models/transformer.py`, `ops/grouped_matmul.py`, `ops/flash_attention.py`)
against the benchmark's plain reference (`benchmark/families/moe_lm.py`,
which imports nothing of the program) at toy size on the CPU; the Pallas
kernels run in the interpreter."""
import dataclasses
import os
import sys

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "benchmark", "tests"),
                os.path.join(ROOT, "benchmark")]
import toy_moe  # noqa: E402
import harness  # noqa: E402
import traffic  # noqa: E402
import weights  # noqa: E402

from tensorflowonspark_tpu.models import transformer as tfm  # noqa: E402
from tensorflowonspark_tpu.models.transformer import (  # noqa: E402
    MoEMLP, Transformer, TransformerConfig, lm_loss)
import tensorflowonspark_tpu.ops.grouped_matmul  # noqa: E402,F401
from tensorflowonspark_tpu.ops.flash_attention import (  # noqa: E402
    flash_attention)

# the module (`ops.grouped_matmul` itself is the function of that name)
gmm_mod = sys.modules["tensorflowonspark_tpu.ops.grouped_matmul"]
FAMILY = harness.load_module("families", "moe_lm")
HI = jax.lax.Precision.HIGHEST


def mm(a, b):
    return jnp.matmul(a, b, precision=HI)


# ---- (a) the whole model against the reference ---------------------------

def test_loss_and_gradients_match_the_plain_reference():
    """A 4-layer toy: three window layers (window 16 < S = 48) and a full
    one with YaRN, GQA 4/2 at head 32 (not 64 // 4), top-2 of 8 experts of
    which 4 are held, a sliced vocabulary; float32 so the comparison is
    tight."""
    cfg = toy_moe.config(dtype="float32")
    spec = toy_moe.spec()
    shapes = FAMILY.param_shapes(cfg)
    batch = traffic.first_batches(spec.traffic, cfg, 11, 1)[0]
    ref = FAMILY.reference(cfg, lambda: weights.make(11, shapes), [batch],
                           row_block=2)
    loss_fn, _ = FAMILY.build(cfg)
    params = weights.nest(weights.make(11, shapes))
    (loss, stats), grads = jax.jit(jax.value_and_grad(loss_fn, has_aux=True))(
        params, jnp.asarray(batch), None)
    assert float(stats["moe.pairs.local"] + stats["moe.pairs.absent"]) == \
        4 * 48 * 2 * 4          # rows x tokens x picks x layers
    assert float(loss) == pytest.approx(ref["losses"][0], rel=2e-6)
    got = {k: float(jnp.linalg.norm(v.ravel()))
           for k, v in weights.flatten(grads).items()}
    assert set(got) == set(ref["grad_norms"])
    scale = float(np.median(list(ref["grad_norms"].values())))
    for k, want in ref["grad_norms"].items():
        assert got[k] == pytest.approx(want, rel=2e-4, abs=2e-4 * scale), k


def test_yarn_frequencies_and_factor_are_the_references():
    cfg = traffic.load("configs", "mellum2-12b-a2.5b")
    m = cfg["program"]["model"]
    for kind, inv, factor in (
            ("full_attention", tfm.rope_inv_freq(
                128, m["rope_theta"], m["rope_yarn_factor"],
                m["rope_yarn_original_max"], m["rope_yarn_beta_fast"],
                m["rope_yarn_beta_slow"]), m["rope_attention_factor"]),
            ("sliding_attention", tfm.rope_inv_freq(
                128, m["rope_local_theta"]), 1.0)):
        cos, sin = FAMILY.rope_tables(cfg, kind, 64)
        angles = np.arange(64)[:, None] * np.asarray(inv, np.float64)[None]
        np.testing.assert_allclose(cos, np.cos(angles) * factor, atol=2e-5)
        np.testing.assert_allclose(sin, np.sin(angles) * factor, atol=2e-5)
    # the slow pairs are divided by the factor, the fast ones are kept
    full = np.asarray(tfm.rope_inv_freq(128, 5e5, 16.0, 8192, 32.0, 1.0))
    plain = np.asarray(tfm.rope_inv_freq(128, 5e5))
    assert full[0] == plain[0] and full[-1] == pytest.approx(plain[-1] / 16)


# ---- (c) the grouped matmul ----------------------------------------------

@pytest.mark.parametrize("sizes", [
    [300, 0, 500, 100],          # an empty group, rows past the last one
    [0, 0, 1536, 0],             # one group holds every row
    [1, 127, 129, 600],          # groups that share row tiles
    [0, 0, 0, 0],                # nothing routed here
])
def test_grouped_matmul_forward_and_both_gradients(monkeypatch, sizes):
    monkeypatch.setattr(gmm_mod, "TILE_M", 128)
    monkeypatch.setattr(gmm_mod, "TILE_KN", 128)     # 2 x 3 width tiles
    m, k, n = 1536, 256, 384
    key = jax.random.key(0)
    lhs = jax.random.normal(jax.random.fold_in(key, 1), (m, k))
    rhs = jax.random.normal(jax.random.fold_in(key, 2), (4, k, n))
    sizes = jnp.asarray(sizes, jnp.int32)
    real = (jnp.arange(m) < sizes.sum())[:, None]

    def kernel(l, r):       # rows past the last group are not written
        return jnp.where(real, gmm_mod.grouped_matmul(l, r, sizes), 0)

    def loop(l, r):
        return gmm_mod.grouped_matmul_reference(l, r, sizes)

    np.testing.assert_allclose(kernel(lhs, rhs), loop(lhs, rhs), atol=1e-3)
    w = jax.random.normal(jax.random.fold_in(key, 3), (m, n))
    got = jax.grad(lambda l, r: jnp.sum(kernel(l, r) * w), (0, 1))(lhs, rhs)
    want = jax.grad(lambda l, r: jnp.sum(loop(l, r) * w), (0, 1))(lhs, rhs)
    np.testing.assert_allclose(jnp.where(real, got[0], 0), want[0],
                               atol=1e-3)
    np.testing.assert_allclose(got[1], want[1], atol=2e-2, rtol=1e-5)
    empty = np.asarray(sizes) == 0
    assert not np.asarray(got[1])[empty].any()


def test_grouped_matmul_pads_rows_and_refuses_a_mesh():
    lhs = jnp.ones((700, 64))           # not a multiple of the row tile
    rhs = jnp.ones((2, 64, 128))
    sizes = jnp.asarray([400, 300], jnp.int32)
    out = gmm_mod.grouped_matmul(lhs, rhs, sizes)
    assert out.shape == (700, 128) and float(out.min()) == 64.0
    from tensorflowonspark_tpu.parallel import mesh as mesh_mod
    mesh = mesh_mod.build_mesh(mesh_mod.MeshSpec(dp=-1))
    with jax.set_mesh(mesh), pytest.raises(NotImplementedError,
                                           match="one chip"):
        gmm_mod.grouped_matmul(lhs, rhs, sizes)


# ---- (d), (e) the share and dropless routing -------------------------------

def _moe_cfg(held=None, offset=0, experts=8, k=2):
    return TransformerConfig(
        vocab_size=64, d_model=32, n_heads=2, n_layers=1, d_ff=64,
        dtype="float32", num_experts=experts, moe_every=1,
        moe_router="dropless", moe_top_k=k, moe_d_ff=24,
        moe_experts_held=held, moe_expert_offset=offset,
        activation="silu", mlp_style="gated")


def _reference_layer(whole, x, held, offset, experts=8, k=2):
    z = {"e": experts, "k": k, "held": held, "off": offset}
    p = {"moe/router/kernel": whole["router"]["kernel"]}
    for name in ("experts_wi", "experts_up", "experts_wo"):
        p[f"moe/{name}/kernel"] = whole[name]["kernel"][offset:offset + held]
    return FAMILY._experts(p, x.reshape(-1, x.shape[-1]), z, mm).reshape(
        x.shape)


def _share(whole, held, offset):
    return {"router": whole["router"], **{
        name: {"kernel": whole[name]["kernel"][offset:offset + held]}
        for name in ("experts_wi", "experts_up", "experts_wo")}}


@pytest.fixture
def row_chunks(monkeypatch):
    """The toy's 96 rows as three chunks of `_take_first`'s loop (at the
    cell's size: 131072 rows in chunks of 8192)."""
    monkeypatch.setattr(tfm, "_ROW_CHUNK", 32)


def test_take_first_moves_the_chunks_that_hold_rows(row_chunks):
    src = jax.random.normal(jax.random.key(0), (40, 8))
    idx = jax.random.randint(jax.random.key(1), (128,), 0, 40)
    for n in (0, 1, 32, 33, 128):
        got = jax.jit(tfm._take_first)(src, idx, n)
        moved = -(-n // 32) * 32
        assert np.array_equal(got[:moved], src[idx][:moved])
        assert not np.asarray(got[moved:]).any()
    # rows that are not whole chunks: one plain gather
    assert np.array_equal(tfm._take_first(src, idx[:50], 7), src[idx[:50]])


def test_the_four_shares_add_up_to_the_whole_layer(row_chunks):
    x = jax.random.normal(jax.random.key(1), (2, 24, 32))
    whole = MoEMLP(_moe_cfg()).init(jax.random.key(2), x)["params"]
    uncut = _reference_layer(whole, x, 8, 0)
    np.testing.assert_allclose(
        MoEMLP(_moe_cfg()).apply({"params": whole}, x), uncut, atol=1e-5)
    parts = [MoEMLP(_moe_cfg(2, off)).apply(
        {"params": _share(whole, 2, off)}, x) for off in (0, 2, 4, 6)]
    for off, part in zip((0, 2, 4, 6), parts):
        np.testing.assert_allclose(
            part, _reference_layer(whole, x, 2, off), atol=1e-5)
    assert float(jnp.abs(parts[0]).max()) > 1e-3
    np.testing.assert_allclose(sum(parts), uncut, atol=1e-5)


def test_dropless_when_every_pick_is_held(row_chunks):
    """A router biased so that every token picks the two held experts: the
    row buffer is full to its last row, and no row is lost."""
    x = jnp.abs(jax.random.normal(jax.random.key(3), (2, 24, 32))) + 1.0
    whole = MoEMLP(_moe_cfg()).init(jax.random.key(4), x)["params"]
    bias = jnp.full((32, 8), -0.5).at[:, 4:6].set(0.5)
    whole = dict(whole, router={"kernel": whole["router"]["kernel"] + bias})
    cfg = _moe_cfg(2, 4)
    out, sown = MoEMLP(cfg).apply({"params": _share(whole, 2, 4)}, x,
                                  mutable=["intermediates"])
    local, absent, fullest, mean, moved, kept = np.asarray(
        sown["intermediates"]["moe_stats"][0])
    assert (local, absent) == (2 * 24 * 2, 0) and fullest == 48 == mean
    assert (moved, kept) == (0, 2 * 24 * 2)     # no selection bias here
    np.testing.assert_allclose(out, _reference_layer(whole, x, 2, 4),
                               atol=1e-5)
    # and its gradients: the rows, the router, the experts
    def program(p, x_):
        return jnp.sum(MoEMLP(cfg).apply({"params": _share(p, 2, 4)}, x_)
                       ** 2)

    def plain(p, x_):
        return jnp.sum(_reference_layer(p, x_, 2, 4) ** 2)

    got = jax.grad(program, (0, 1))(whole, x)
    want = jax.grad(plain, (0, 1))(whole, x)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, atol=2e-4, rtol=1e-4)


def test_a_step_counts_its_routing_once_and_without_a_callback():
    """The stats ride the step's metrics; the step object hands them to
    `trace.count_when_ready`, jitted or compiled ahead of time; remat runs
    the forward twice and the step still counts once; nothing in the
    compiled step calls back to the host."""
    import optax

    from tensorflowonspark_tpu import trace
    from tensorflowonspark_tpu.parallel import train as train_mod

    cfg = dataclasses.replace(_moe_cfg(4, 2), n_layers=2, remat=True,
                              max_seq_len=16)
    model = Transformer(cfg)
    tokens = jax.random.randint(jax.random.key(5), (2, 16), 0, 64)
    params = model.init(jax.random.key(6), tokens)["params"]

    def loss_fn(p, batch, rng):
        logits, sown = model.apply({"params": p}, batch,
                                   mutable=["intermediates"])
        return (lm_loss(logits[:, :-1], batch[:, 1:]),
                tfm.moe_stats(sown["intermediates"]))

    loss_fn.counters = tfm.MOE_COUNTERS
    opt = optax.sgd(0.1)
    step = train_mod.make_train_step(loss_fn, opt, donate=False)
    state = train_mod.create_train_state(params, opt)
    compiled = step.lower(state, tokens, None).compile()
    import re
    assert not re.search(r'custom_call_target="[^"]*callback',
                         compiled.as_text())
    before = trace.report()["counters"]
    for fn in (step, compiled, compiled):
        state, metrics = fn(state, tokens, None)
    assert set(tfm.MOE_COUNTERS) <= set(metrics) and "loss" in metrics
    after = trace.report()["counters"]           # a report waits for them
    moved = {k: after[k] - before.get(k, 0) for k in tfm.MOE_COUNTERS}
    assert moved["moe.pairs.local"] + moved["moe.pairs.absent"] == \
        3 * 2 * 16 * 2 * 2      # steps x tokens x picks x layers, once each
    assert moved["moe.load.mean"] == moved["moe.pairs.local"] / 4
    # a model without such layers has nothing to count, and a loss that
    # names no counters gets the plain step back
    assert tfm.moe_stats({}) == {}
    plain = train_mod.make_train_step(lambda p, b, r: loss_fn(p, b, r)[0],
                                      opt, donate=False)
    assert not isinstance(plain, train_mod._CountedStep)
    assert set(plain(state, tokens, None)[1]) == {"loss", "grad_norm"}


def test_a_share_needs_the_dropless_router_and_no_mesh():
    x = jnp.ones((1, 8, 32))
    bad = dataclasses.replace(_moe_cfg(2, 0), moe_router="topk")
    with pytest.raises(ValueError, match="dropless"):
        MoEMLP(bad).init(jax.random.key(0), x)
    with pytest.raises(ValueError, match="not among"):
        MoEMLP(_moe_cfg(4, 6)).init(jax.random.key(0), x)


# ---- (f) the defaults are the parent's -----------------------------------

BASE = dict(vocab_size=128, d_model=64, n_heads=4, n_layers=2, d_ff=128,
            max_seq_len=32, dtype="float32")
# with each, `logits[0, -1, :6]` of `Transformer(cfg)` initialised from
# key(0) as the PARENT commit (8ab2891) gave them on this CPU, tokens
# RandomState(0).randint(0, 128, (4, 32)); there the whole output and the
# gradient were the same bytes as here (sha256), which a test on another
# thread count cannot pin
PARENT = {
    "plain": ({}, [
        -0.9746941924095154, -1.3706409931182861, -0.3283471465110779,
        0.02623891457915306, -0.6336374878883362, -0.30494391918182373]),
    "rope_gqa_flash": (dict(
        rope=True, n_kv_heads=2, attention_impl="flash", norm_type="rmsnorm",
        mlp_style="gated", activation="silu"), [
        1.314789891242981, 1.079333782196045, -3.476524591445923,
        1.1445717811584473, 1.0042768716812134, -0.4113817811012268]),
    "moe_topk": (dict(num_experts=4, moe_every=1, moe_router="topk",
                      moe_top_k=2), [
        -0.21999149024486542, -1.1547185182571411, 0.15429340302944183,
        -0.7179229855537415, -2.8634181022644043, -0.9156523942947388]),
}


@pytest.mark.parametrize("name", sorted(PARENT))
def test_new_fields_left_at_none_change_no_bit(name):
    """`head_dim=None`, `layer_types=None`, `sliding_window=None` against
    the same written out (`d_model // n_heads`, every layer full): the same
    bits, logits and gradients; and against what the parent commit gave."""
    tokens = jnp.asarray(np.random.RandomState(0).randint(
        0, 128, size=(4, 32)).astype(np.int32))
    extra, parent_logits = PARENT[name]
    cfg = TransformerConfig(**BASE, **extra)
    spelt = dataclasses.replace(
        cfg, head_dim=16, layer_types=("full_attention",) * 2,
        sliding_window=None, rope_local_theta=cfg.rope_theta)

    def run(c):
        model = Transformer(c)
        params = model.init(jax.random.key(0), tokens)["params"]
        grads = jax.grad(lambda p: lm_loss(
            model.apply({"params": p}, tokens[:, :-1]), tokens[:, 1:]))(
                params)
        return model.apply({"params": params}, tokens), grads

    (out, grads), (out2, grads2) = run(cfg), run(spelt)
    assert np.array_equal(out, out2)
    for a, b in zip(jax.tree_util.tree_leaves(grads),
                    jax.tree_util.tree_leaves(grads2)):
        assert np.array_equal(a, b)
    np.testing.assert_allclose(out[0, -1, :6], parent_logits, rtol=2e-6,
                               atol=2e-6)


def test_flash_without_a_window_is_the_same_call():
    key = jax.random.key(7)
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i), (1, 64, 4, 32))
               for i in range(3))
    assert np.array_equal(flash_attention(q, k, v),
                          flash_attention(q, k, v, window=None))
    # a window that covers the row masks nothing
    np.testing.assert_allclose(flash_attention(q, k, v, window=64),
                               flash_attention(q, k, v), atol=1e-6)


# ---- (g) decode ----------------------------------------------------------

@pytest.mark.parametrize("extra,names", [
    (dict(sliding_window=16), "sliding_window"),
    (dict(layer_types=("sliding_attention", "full_attention"),
          sliding_window=16), "layer_types"),
    (dict(num_experts=8, moe_every=1, moe_router="dropless", moe_top_k=2,
          moe_experts_held=4), "moe_experts_held"),
])
def test_decode_with_a_window_kinds_or_a_share_raises(extra, names):
    with pytest.raises(NotImplementedError, match=names):
        TransformerConfig(**BASE, decode=True, **extra)
    TransformerConfig(**BASE, **extra)           # training: fine


def test_layer_types_are_checked():
    with pytest.raises(ValueError, match="names 1 layers"):
        TransformerConfig(**BASE, layer_types=["full_attention"])
    with pytest.raises(ValueError, match="not in"):
        TransformerConfig(**BASE, layer_types=["full_attention", "local"])
    with pytest.raises(ValueError, match="sliding_window"):
        TransformerConfig(**BASE, layer_types=["sliding_attention"] * 2)
