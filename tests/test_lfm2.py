"""Gated short-convolution layers beside attention with query/key norms,
sigmoid top-k routing with a selection bias over a chip's share of the
experts, leading dense layers, one tied table: the program
(`models/transformer.py`) against the benchmark's plain reference
(`benchmark/families/lfm2_moe.py`, which imports nothing of the program) or
against a loop written here, at toy size on the CPU, float32 unless said;
the Pallas kernels run in the interpreter."""
import dataclasses
import math
import os
import sys

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "benchmark", "tests"),
                os.path.join(ROOT, "benchmark")]
import toy_lfm2  # noqa: E402
import harness  # noqa: E402
import traffic  # noqa: E402
import weights  # noqa: E402

from tensorflowonspark_tpu import trace  # noqa: E402
from tensorflowonspark_tpu.models import transformer as tfm  # noqa: E402
from tensorflowonspark_tpu.models.transformer import (  # noqa: E402
    Attention, MoEMLP, ShortConv, Transformer, TransformerConfig, lm_loss)

FAMILY = harness.load_module("families", "lfm2_moe")
HI = jax.lax.Precision.HIGHEST


def mm(a, b):
    return jnp.matmul(a, b, precision=HI)


def _flat(tree, prefix):
    return {f"{prefix}/{k}": v for k, v in weights.flatten(tree).items()}


# ---- the short convolution ------------------------------------------------

CONV_CFG = TransformerConfig(vocab_size=64, d_model=16, n_heads=2, n_layers=1,
                             d_ff=32, dtype="float32", conv_kernel=3)


def _conv_by_loop(p, x, taps=3):
    """The mixer a position and a channel at a time."""
    x, w_in = np.asarray(x, np.float64), np.asarray(p["in_proj"]["kernel"])
    w, w_out = np.asarray(p["taps"]), np.asarray(p["out_proj"]["kernel"])
    d = x.shape[-1]
    out = np.zeros_like(x)
    for r in range(x.shape[0]):
        bcz = x[r] @ w_in
        b, c, z = bcz[:, :d], bcz[:, d:2 * d], bcz[:, 2 * d:]
        g = b * z
        s = np.zeros_like(g)
        for t in range(g.shape[0]):
            for j in range(taps):
                src = t - (taps - 1) + j
                if src >= 0:          # zeros left of the row's start
                    s[t] += w[:, j] * g[src]
        out[r] = (c * s) @ w_out
    return out


def test_short_conv_forward_and_gradients():
    x = jax.random.normal(jax.random.key(0), (2, 12, 16))
    p = ShortConv(CONV_CFG).init(jax.random.key(1), x)["params"]
    assert {k: v.shape for k, v in weights.flatten(p).items()} == {
        "in_proj/kernel": (16, 48), "taps": (16, 3),
        "out_proj/kernel": (16, 16)}
    got = ShortConv(CONV_CFG).apply({"params": p}, x)
    np.testing.assert_allclose(got, _conv_by_loop(p, x), atol=1e-5)
    # gradients: against the reference family's sum over shifted arrays
    z = {"taps": 3}

    def program(p_, x_):
        return jnp.sum(ShortConv(CONV_CFG).apply({"params": p_}, x_) ** 2)

    def plain(p_, x_):
        return jnp.sum(FAMILY._short_conv(_flat(p_, "conv"), x_, z, mm,
                                          None) ** 2)

    got, want = jax.grad(program, (0, 1))(p, x), jax.grad(plain, (0, 1))(p, x)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5)


def test_short_conv_is_causal_and_starts_on_zeros():
    x = jax.random.normal(jax.random.key(2), (1, 10, 16))
    p = ShortConv(CONV_CFG).init(jax.random.key(3), x)["params"]
    run = lambda x_: ShortConv(CONV_CFG).apply({"params": p}, x_)  # noqa: E731
    out = run(x)
    t = 5
    later = x.at[:, t + 1:].set(jax.random.normal(jax.random.key(4),
                                                  (1, 10 - t - 1, 16)))
    assert np.array_equal(run(later)[:, :t + 1], out[:, :t + 1])
    assert not np.allclose(run(later)[:, t + 1:], out[:, t + 1:])
    # positions 0 and 1 see zeros where positions -2 and -1 would be: a row
    # cut to its first position(s) gives them the same output
    np.testing.assert_allclose(run(x[:, :1]), out[:, :1], atol=1e-6)
    np.testing.assert_allclose(run(x[:, :2]), out[:, :2], atol=1e-6)
    # one tap set to zero moves the output: all three are read
    for j in range(3):
        q = dict(p, taps=p["taps"].at[:, j].set(0.0))
        assert not np.allclose(
            ShortConv(CONV_CFG).apply({"params": q}, x)[:, 2:], out[:, 2:])


# ---- query/key norms --------------------------------------------------------

@pytest.mark.parametrize("impl", ["dense", "flash"])
def test_query_key_norms_before_the_rotation(impl):
    cfg = toy_lfm2.config(dtype="float32")
    mcfg = dataclasses.replace(
        TransformerConfig(**cfg["program"]["model"]), attention_impl=impl)
    z = FAMILY._sizes(cfg)
    x = jax.random.normal(jax.random.key(5), (2, 48, 64))
    p = Attention(mcfg).init(jax.random.key(6), x)["params"]
    assert p["q_norm"]["scale"].shape == p["k_norm"]["scale"].shape == (16,)
    # scales that are not one, so that each is seen to be its own
    p = dict(p, q_norm={"scale": 1.0 + 0.3 * jax.random.normal(
        jax.random.key(7), (16,))}, k_norm={"scale": 1.0 + 0.3 * (
            jax.random.normal(jax.random.key(8), (16,)))})
    cos, sin = FAMILY.rope_tables(cfg, 48)

    def program(p_, x_):
        return Attention(mcfg).apply({"params": p_}, x_)

    def plain(p_, x_, fault=None):
        return FAMILY._attention(_flat(p_, "attn"), x_, z, cfg["norm_eps"],
                                 cos, sin, mm, fault)

    np.testing.assert_allclose(program(p, x), plain(p, x), atol=2e-5)
    assert not np.allclose(plain(p, x, "no_qk_norm"), plain(p, x), atol=1e-3)
    got = jax.grad(lambda p_: jnp.sum(program(p_, x) ** 2))(p)
    want = jax.grad(lambda p_: jnp.sum(plain(p_, x) ** 2))(p)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, atol=2e-4, rtol=2e-4)


# ---- sigmoid scores and the selection bias ---------------------------------

def _moe_cfg(held=None, offset=0, experts=8, k=2, dtype="float32", **kw):
    return TransformerConfig(
        vocab_size=64, d_model=32, n_heads=2, n_layers=1, d_ff=64,
        dtype=dtype, num_experts=experts, moe_every=1,
        moe_router="dropless", moe_top_k=k, moe_d_ff=24,
        moe_experts_held=held, moe_expert_offset=offset,
        moe_scoring="sigmoid", moe_expert_bias=True,
        activation="silu", mlp_style="gated", **kw)


def _moe_params(key, x, bias_std=0.1, experts=8):
    whole = MoEMLP(_moe_cfg(experts=experts)).init(key, x)["params"]
    assert whole["expert_bias"].shape == (experts,)
    assert not np.asarray(whole["expert_bias"]).any()     # starts at zero
    return dict(whole, expert_bias=bias_std * jax.random.normal(
        jax.random.fold_in(key, 1), (experts,)))


def _share(whole, held, offset):
    return dict(whole, **{
        name: {"kernel": whole[name]["kernel"][offset:offset + held]}
        for name in ("experts_wi", "experts_up", "experts_wo")})


def _reference_layer(whole, x, held, offset, experts=8, k=2, fault=None):
    z = {"e": experts, "k": k, "held": held, "off": offset, "scale": 1}
    p = _flat(_share(whole, held, offset), "moe")
    return FAMILY._experts(p, x.reshape(-1, x.shape[-1]), z, mm,
                           fault).reshape(x.shape)


def test_picks_follow_score_plus_bias_and_weights_the_score():
    x = jax.random.normal(jax.random.key(9), (2, 24, 32))
    whole = _moe_params(jax.random.key(10), x)
    out, sown = MoEMLP(_moe_cfg()).apply({"params": whole}, x,
                                         mutable=["intermediates"])
    scores = jax.nn.sigmoid(mm(x.reshape(-1, 32), whole["router"]["kernel"]))
    want = np.argsort(-np.asarray(scores + whole["expert_bias"]), axis=1)
    picks = np.asarray(sown["intermediates"]["moe_picks"][0])
    assert np.array_equal(np.sort(picks, 1), np.sort(want[:, :2], 1))
    # the bias changed some of the choices, and not all
    plain = np.argsort(-np.asarray(scores), axis=1)[:, :2]
    moved = int(sum(e not in row for row, pick in zip(plain, picks)
                    for e in pick))
    assert 0 < moved < picks.size
    np.testing.assert_allclose(out, _reference_layer(whole, x, 8, 0),
                               atol=1e-5)
    # weights from the score alone: neither the faulty reference that
    # weighs by score + bias nor the one that picks without it agrees
    for fault in ("bias_in_weights", "bias_out_of_choice"):
        assert not np.allclose(
            out, _reference_layer(whole, x, 8, 0, fault=fault), atol=1e-4)
    # the layer's counters say what the bias moved
    stats = np.asarray(sown["intermediates"]["moe_stats"][0])
    assert dict(zip(tfm.MOE_COUNTERS, stats))["moe.picks.moved"] == moved
    assert stats[4] + stats[5] == picks.size == stats[0] + stats[1]


def test_a_bias_large_enough_changes_the_output_a_small_one_does_not():
    x = jax.random.normal(jax.random.key(11), (1, 16, 32))
    whole = _moe_params(jax.random.key(12), x, bias_std=0.0)
    base = MoEMLP(_moe_cfg()).apply({"params": whole}, x)
    # too small to reorder any token's experts: the same bits
    tiny = dict(whole, expert_bias=jnp.full((8,), 1e-9).at[3].set(2e-9))
    assert np.array_equal(MoEMLP(_moe_cfg()).apply({"params": tiny}, x), base)
    # every token now picks experts 6 and 7
    big = dict(whole, expert_bias=jnp.zeros((8,)).at[6:].set(5.0))
    out, sown = MoEMLP(_moe_cfg()).apply({"params": big}, x,
                                         mutable=["intermediates"])
    assert not np.allclose(out, base, atol=1e-4)
    assert set(np.asarray(sown["intermediates"]["moe_picks"][0]).ravel()) \
        == {6, 7}


def test_the_bias_has_no_gradient_and_adamw_leaves_it():
    from tensorflowonspark_tpu.optim import make_optimizer
    from tensorflowonspark_tpu.parallel import train as train_mod

    x = jax.random.normal(jax.random.key(13), (2, 16, 32))
    whole = _moe_params(jax.random.key(14), x)
    cfg = _moe_cfg()

    def loss_fn(p, batch, rng):
        return jnp.mean(MoEMLP(cfg).apply({"params": p}, batch) ** 2)

    g = jax.grad(loss_fn)(whole, x, None)
    assert not np.asarray(g["expert_bias"]).any()
    assert np.asarray(g["router"]["kernel"]).any()
    opt, _ = make_optimizer("adamw_fused", learning_rate=1e-2,
                            mu_dtype="bfloat16")
    step = train_mod.make_train_step(loss_fn, opt, donate=False)
    state = train_mod.create_train_state(whole, opt)
    for _ in range(3):
        state, _ = step(state, x, None)
    assert np.array_equal(state.params["expert_bias"], whole["expert_bias"])
    assert not np.allclose(state.params["router"]["kernel"],
                           whole["router"]["kernel"])


@pytest.mark.parametrize("dtype,atol", [
    # float32: sums of the same products in another order
    ("float32", 2e-5),
    # bfloat16: each share rounds its own output to 8 bits once (outputs of
    # size 0.02-0.05 here), the uncut layer once for all four
    ("bfloat16", 2e-3)])
def test_the_four_shares_add_up_to_the_whole_layer(dtype, atol):
    """Offsets 0, 8, 16, 24 of 32 experts, 4 picks a token, the bias with
    the router on every chip."""
    x = jax.random.normal(jax.random.key(15), (2, 24, 32))
    whole = _moe_params(jax.random.key(16), x, experts=32)
    cfgs = {off: _moe_cfg(8, off, experts=32, k=4, dtype=dtype)
            for off in (0, 8, 16, 24)}
    uncut = _reference_layer(whole, x, 32, 0, experts=32, k=4)
    got = MoEMLP(_moe_cfg(experts=32, k=4, dtype=dtype)).apply(
        {"params": whole}, x)
    np.testing.assert_allclose(got.astype(jnp.float32), uncut, atol=atol)
    parts = [MoEMLP(cfgs[off]).apply({"params": _share(whole, 8, off)}, x)
             .astype(jnp.float32) for off in cfgs]
    for off, part in zip(cfgs, parts):
        np.testing.assert_allclose(
            part, _reference_layer(whole, x, 8, off, experts=32, k=4),
            atol=atol)
        assert float(jnp.abs(part).max()) > 1e-3
    np.testing.assert_allclose(sum(parts), uncut, atol=2 * atol)


def test_counters_on_a_hand_made_routing():
    """Four experts, two picks, three tokens with known scores: the bias
    lifts expert 3 over expert 1 for the second token only."""
    logits = jnp.asarray([[4.0, 3.0, -4.0, -3.0],     # picks 0, 1 either way
                          [4.0, 0.2, -4.0, 0.0],      # 1 -> 3 under the bias
                          [-4.0, -3.0, 4.0, 3.0]])    # picks 2, 3 either way
    cfg = dataclasses.replace(_moe_cfg(2, 2, experts=4), d_model=4)
    x = jnp.eye(4)[None, :3] * 1.0                    # token t reads row t
    p = MoEMLP(cfg).init(jax.random.key(17), x)["params"]
    p = dict(p, router={"kernel": jnp.zeros((4, 4)).at[:3].set(logits)},
             expert_bias=jnp.asarray([0.0, 0.0, 0.0, 0.1]))
    _, sown = MoEMLP(cfg).apply({"params": p}, x, mutable=["intermediates"])
    stats = {k: float(v) for k, v in
             tfm.moe_stats(sown["intermediates"]).items()}
    # held experts 2 and 3: token 1's second pick and both of token 2's
    assert stats == {"moe.pairs.local": 3, "moe.pairs.absent": 3,
                     "moe.load.max": 2, "moe.load.mean": 1.5,
                     "moe.picks.moved": 1, "moe.picks.kept": 5}
    # without a bias: nothing moved, every pick kept
    plain = dataclasses.replace(cfg, moe_expert_bias=False)
    q = {k: v for k, v in p.items() if k != "expert_bias"}
    _, sown = MoEMLP(plain).apply({"params": q}, x, mutable=["intermediates"])
    stats = np.asarray(sown["intermediates"]["moe_stats"][0])
    assert (stats[4], stats[5]) == (0, 6) and stats[0] == 2


def test_scoring_and_bias_need_the_dropless_router():
    x = jnp.ones((1, 8, 32))
    for kw in (dict(moe_scoring="sigmoid"), dict(moe_expert_bias=True)):
        bad = TransformerConfig(d_model=32, n_heads=2, d_ff=64, num_experts=4,
                                moe_router="topk", **kw)
        with pytest.raises(ValueError, match="dropless"):
            MoEMLP(bad).init(jax.random.key(0), x)
    with pytest.raises(ValueError, match="moe_scoring"):
        MoEMLP(dataclasses.replace(_moe_cfg(), moe_scoring="tanh")).init(
            jax.random.key(0), x)


# ---- where the dense layers are, and the one table --------------------------

BASE = dict(vocab_size=96, d_model=32, n_heads=2, n_layers=4, d_ff=80,
            max_seq_len=16, dtype="float32", rope=True, norm_type="rmsnorm",
            activation="silu", mlp_style="gated")


@pytest.mark.parametrize("n_dense,every,sparse", [
    (0, 1, [0, 1, 2, 3]), (2, 1, [2, 3]), (1, 2, [1, 3]), (2, 2, [3]),
    (4, 1, [])])
def test_the_first_n_layers_are_dense_each_kind_with_its_width(
        n_dense, every, sparse):
    cfg = TransformerConfig(**BASE, num_experts=4, moe_every=every,
                            moe_router="dropless", moe_top_k=2, moe_d_ff=24,
                            moe_dense_layers=n_dense)
    shapes = jax.eval_shape(lambda: Transformer(cfg).init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"])
    for i in range(4):
        layer = shapes[f"layer_{i}"]
        if i in sparse:
            assert "mlp" not in layer
            assert layer["moe"]["experts_wi"]["kernel"].shape == (4, 32, 24)
        else:
            assert "moe" not in layer
            assert layer["mlp"]["wi_gate"]["kernel"].shape == (32, 80)


def test_one_table_for_the_embedding_and_the_head():
    tied = TransformerConfig(**BASE, tie_embeddings=True)
    untied = TransformerConfig(**BASE)
    tokens = jax.random.randint(jax.random.key(18), (2, 9), 0, 96)
    p = Transformer(tied).init(jax.random.key(19), tokens[:, :-1])["params"]
    assert "lm_head" not in p and "lm_head" in Transformer(untied).init(
        jax.random.key(19), tokens[:, :-1])["params"]
    # a table that is not tiny, so that both uses weigh in the gradient
    p = dict(p, token_embed={"embedding": 0.5 * jax.random.normal(
        jax.random.key(20), (96, 32))})
    logits = Transformer(tied).apply({"params": p}, tokens[:, :-1])
    hidden = Transformer(tied).apply({"params": p}, tokens[:, :-1],
                                     return_hidden=True)
    np.testing.assert_allclose(
        logits, mm(hidden, p["token_embed"]["embedding"].T), atol=1e-5)
    # the same numbers as the untied model whose head is the table
    # transposed, and the table's gradient is the sum of both uses'
    q = dict(p, lm_head={"kernel": p["token_embed"]["embedding"].T})
    np.testing.assert_allclose(
        Transformer(untied).apply({"params": q}, tokens[:, :-1]), logits,
        atol=1e-5)

    def loss(model, p_):
        return lm_loss(model.apply({"params": p_}, tokens[:, :-1]),
                       tokens[:, 1:])

    g_tied = jax.grad(lambda p_: loss(Transformer(tied), p_))(p)
    g_two = jax.grad(lambda p_: loss(Transformer(untied), p_))(q)
    both = g_two["token_embed"]["embedding"] + g_two["lm_head"]["kernel"].T
    np.testing.assert_allclose(g_tied["token_embed"]["embedding"], both,
                               atol=1e-6, rtol=1e-5)
    for part in (g_two["token_embed"]["embedding"],
                 g_two["lm_head"]["kernel"].T):
        assert float(jnp.linalg.norm(part)) > 0.05 * float(
            jnp.linalg.norm(both))
    # the fused loss over the table transposed is the plain loss
    from tensorflowonspark_tpu.ops.xent import fused_unembed_xent
    fused = fused_unembed_xent(hidden, p["token_embed"]["embedding"].T,
                               tokens[:, 1:], 4)
    assert float(fused) == pytest.approx(
        float(loss(Transformer(tied), p)), rel=1e-5)


# ---- the whole model -------------------------------------------------------

def test_loss_gradient_and_three_steps_match_the_plain_reference():
    """The toy of the cell: layer 0 conv + dense, layer 1 attention with
    query/key norms + sparse, layers 2-4 conv + sparse (sigmoid top-2 of 8,
    4 held at offset 2, a bias that moves picks), a tied table of 256 rows;
    float32 with float32 moments, so the comparison is tight: what is left
    is the order of float32 sums, and near-ties at the last pick."""
    from tensorflowonspark_tpu.parallel import train as train_mod

    cfg = toy_lfm2.config(dtype="float32")
    cfg["program"]["optimizer"]["mu_dtype"] = "float32"
    spec = toy_lfm2.spec()
    shapes = FAMILY.param_shapes(cfg)
    batches = traffic.first_batches(spec.traffic, cfg, 11, 3)
    ref = FAMILY.reference(cfg, lambda: weights.make(11, shapes), batches,
                           row_block=2)
    loss_fn, opt = FAMILY.build(cfg)
    params = weights.nest(weights.make(11, shapes))
    assert "lm_head" not in params
    (loss, stats), grads = jax.jit(jax.value_and_grad(loss_fn, has_aux=True))(
        params, jnp.asarray(batches[0]), None)
    pairs = 4 * 48 * 2 * 4              # rows x tokens x picks x sparse layers
    assert float(stats["moe.pairs.local"] + stats["moe.pairs.absent"]) == pairs
    assert float(stats["moe.picks.moved"] + stats["moe.picks.kept"]) == pairs
    assert 0 < float(stats["moe.picks.moved"]) < 0.5 * pairs
    assert float(loss) == pytest.approx(ref["losses"][0], rel=2e-6)
    got = {k: float(jnp.linalg.norm(v.ravel()))
           for k, v in weights.flatten(grads).items()}
    assert set(got) == set(ref["grad_norms"])
    scale = float(np.median(list(ref["grad_norms"].values())))
    for k, want in ref["grad_norms"].items():
        assert got[k] == pytest.approx(want, rel=2e-4, abs=2e-4 * scale), k
    assert not any(got[k] for k in got if k.endswith("expert_bias"))
    # three steps of the step object the cell drives
    step = train_mod.make_train_step(loss_fn, opt, donate=False)
    state = train_mod.create_train_state(params, opt)
    losses = []
    for batch in batches:
        state, metrics = step(state, jnp.asarray(batch), None)
        losses.append(float(metrics["loss"]))
    assert losses == pytest.approx(ref["losses"], rel=5e-6)
    start = weights.make(11, shapes)
    moved = {k: float(jnp.linalg.norm((v - start[k]).ravel()))
             for k, v in weights.flatten(state.params).items()}
    scale = float(np.median(list(ref["update_norms"].values())))
    for k, want in ref["update_norms"].items():
        # Adam's quotient magnifies a float32 difference where a gradient
        # entry is next to nothing
        assert moved[k] == pytest.approx(want, rel=2e-3, abs=2e-3 * scale), k
        assert (want == 0) == k.endswith("expert_bias"), k


def test_the_cells_tree_is_the_familys_and_holds_no_head():
    cfg = traffic.load("configs", "lfm2-8b-a1b")
    model = Transformer(TransformerConfig(**cfg["program"]["model"]))
    theirs = weights.flatten(jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"]))
    ours = FAMILY.param_shapes(cfg)
    assert {k: v.shape for k, v in theirs.items()} == \
        {k: s for k, (s, _) in ours.items()}
    assert sum(math.prod(v.shape) for v in theirs.values()) == 507820288
    assert not any(k.startswith("lm_head") for k in theirs)
    assert theirs["layer_0/conv/in_proj/kernel"].shape == (2048, 6144)
    assert theirs["layer_0/mlp/wi_gate/kernel"].shape == (2048, 7168)
    assert theirs["layer_1/attn/q_norm/scale"].shape == (64,)
    for i in (1, 2, 3, 4):
        assert theirs[f"layer_{i}/moe/experts_wi/kernel"].shape == \
            (8, 2048, 1792)
        assert theirs[f"layer_{i}/moe/router/kernel"].shape == (2048, 32)
        assert theirs[f"layer_{i}/moe/expert_bias"].shape == (32,)
        assert (f"layer_{i}/conv/taps" in theirs) == (i != 1)


# ---- what is refused, and what a step program was built of -----------------

@pytest.mark.parametrize("extra,names", [
    (dict(layer_types=("conv", "full_attention")), "layer_types"),
    (dict(qk_norm=True), "qk_norm"),
    (dict(num_experts=4, moe_router="dropless", moe_scoring="sigmoid"),
     "moe_scoring='sigmoid'"),
    (dict(num_experts=4, moe_router="dropless", moe_expert_bias=True),
     "moe_expert_bias"),
])
def test_decode_with_what_has_no_incremental_form_raises(extra, names):
    base = dict(vocab_size=64, d_model=32, n_heads=2, n_layers=2, d_ff=64)
    with pytest.raises(NotImplementedError, match=names):
        TransformerConfig(**base, decode=True, **extra)
    TransformerConfig(**base, **extra)           # training: fine


def test_conv_layers_refuse_a_split_sequence_and_a_mask():
    base = dict(vocab_size=64, d_model=32, n_heads=2, n_layers=2, d_ff=64,
                layer_types=("conv", "full_attention"))
    with pytest.raises(NotImplementedError, match="sequence"):
        TransformerConfig(**base, ring_attention_axis="tp")
    x = jnp.ones((1, 8, 32))
    with pytest.raises(NotImplementedError, match="mask"):
        ShortConv(TransformerConfig(**base)).init(
            jax.random.key(0), x, mask=jnp.ones((1, 8), bool))


def test_mixer_calls_are_counted_by_kind():
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=2, n_layers=3,
                            d_ff=64, dtype="float32", rope=True,
                            layer_types=("conv", "full_attention", "conv"))
    tokens = jnp.zeros((1, 8), jnp.int32)
    model = Transformer(cfg)
    params = model.init(jax.random.key(0), tokens)["params"]
    before = trace.counters().snapshot()
    jax.jit(lambda p: model.apply({"params": p}, tokens)).lower(params)
    now = trace.counters().snapshot()
    assert now.get("mixer.calls.conv", 0) - before.get(
        "mixer.calls.conv", 0) == 2
    assert now.get("mixer.calls.attention", 0) - before.get(
        "mixer.calls.attention", 0) == 1
