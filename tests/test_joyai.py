"""Latent attention (a query/key width unlike the value width, one rotary
key a token for all heads), a shared expert beside scaled sigmoid top-k
routing over a chip's share of the experts, and a multi-token-prediction
module with its second loss: the program (`models/transformer.py`,
`ops/flash_attention.py`) against the benchmark's plain reference
(`benchmark/families/mla_moe.py`, which imports nothing of the program) or
against a few lines written here, at toy size on the CPU, float32 unless
said; the Pallas kernels run in the interpreter."""
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
import toy_mla  # noqa: E402
import harness  # noqa: E402
import traffic  # noqa: E402
import weights  # noqa: E402

from tensorflowonspark_tpu import trace  # noqa: E402
from tensorflowonspark_tpu.models import transformer as tfm  # noqa: E402
from tensorflowonspark_tpu.models.transformer import (  # noqa: E402
    Attention, MoEMLP, Transformer, TransformerConfig, apply_rope,
    dot_product_attention, lm_loss, next_token_losses)
from tensorflowonspark_tpu.ops.flash_attention import (  # noqa: E402
    flash_attention_latent, latent_attention_reference)

FAMILY = harness.load_module("families", "mla_moe")
HI = jax.lax.Precision.HIGHEST


def mm(a, b):
    return jnp.matmul(a, b, precision=HI)


def _flat(tree, prefix):
    return {f"{prefix}/{k}": v for k, v in weights.flatten(tree).items()}


def _counted(fn, *names):
    """What `fn()` added to the process counters `names`."""
    before = trace.counters().snapshot()
    fn()
    now = trace.counters().snapshot()
    return [now.get(n, 0) - before.get(n, 0) for n in names]


# ---- (b) the kernels with two widths and one rotary key -------------------

def _latent_operands(seq, heads=3, dn=16, dr=8, dv=16, batch=2, seed=0):
    ks = jax.random.split(jax.random.key(seed), 5)
    return (jax.random.normal(ks[0], (batch, seq, heads, dn + dr)),
            jax.random.normal(ks[1], (batch, seq, heads, dn)),
            jax.random.normal(ks[2], (batch, seq, dr)),
            jax.random.normal(ks[3], (batch, seq, heads, dv)),
            jax.random.normal(ks[4], (batch, seq, heads, dv)))


def _explicit_key(kn, kr):
    return jnp.concatenate([kn, jnp.broadcast_to(
        kr[:, :, None, :], kn.shape[:3] + kr.shape[2:])], axis=-1)


# one resident block; several, the last one padded; several whole ones
@pytest.mark.parametrize("seq,block", [(64, 64), (200, 64), (256, 128)])
def test_latent_kernels_match_dense_attention_over_the_explicit_key(
        seq, block):
    q, kn, kr, v, w = _latent_operands(seq)
    dn = kn.shape[-1]

    def kernels(q_, kn_, kr_, v_):
        return flash_attention_latent(q_, kn_, kr_, v_, block_q=block,
                                      block_k=block)

    def dense(q_, k_, v_):          # the key written out a head: [kn | kr]
        return dot_product_attention(q_, k_, v_, causal=True)

    k = _explicit_key(kn, kr)
    out = kernels(q, kn, kr, v)
    assert out.shape == v.shape                 # the value's width, not 24
    np.testing.assert_allclose(out, dense(q, k, v), atol=2e-6)
    np.testing.assert_allclose(
        out, latent_attention_reference(q, kn, kr, v), atol=2e-6)
    dq, dkn, dkr, dv = jax.grad(
        lambda *a: jnp.sum(kernels(*a) * w), (0, 1, 2, 3))(q, kn, kr, v)
    gq, gk, gv = jax.grad(
        lambda *a: jnp.sum(dense(*a) * w), (0, 1, 2))(q, k, v)
    np.testing.assert_allclose(dq, gq, atol=1e-5)
    np.testing.assert_allclose(dv, gv, atol=1e-5)
    np.testing.assert_allclose(dkn, gk[..., :dn], atol=1e-5)
    # the rotary key's gradient is the sum over the heads that share it
    assert dkr.shape == kr.shape
    np.testing.assert_allclose(dkr, gk[..., dn:].sum(axis=2), atol=2e-5)
    assert float(jnp.abs(dkr).max()) > 0.1


def test_latent_kernels_at_the_published_head_widths():
    """192 = 128 + 64 over 128, two heads, two blocks of 128."""
    q, kn, kr, v, w = _latent_operands(256, heads=2, dn=128, dr=64, dv=128,
                                       batch=1, seed=3)
    fn = lambda *a: jnp.sum(flash_attention_latent(  # noqa: E731
        *a, block_q=128, block_k=128) * w)
    ref = lambda *a: jnp.sum(latent_attention_reference(*a) * w)  # noqa: E731
    got = jax.grad(fn, (0, 1, 2, 3))(q, kn, kr, v)
    want = jax.grad(ref, (0, 1, 2, 3))(q, kn, kr, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=2e-5)


def test_latent_kernel_calls_are_counted_and_shapes_checked():
    q, kn, kr, v, _ = _latent_operands(32, heads=2, seed=5)
    latent, packed, transposed = _counted(
        lambda: jax.grad(lambda q_: jnp.sum(flash_attention_latent(
            q_, kn, kr, v, block_q=32, block_k=32)))(q),
        "flash.calls.latent", "flash.calls.packed", "flash.calls.transposed")
    assert (latent, packed, transposed) == (3, 0, 0)    # forward, dq, dk/dv
    with pytest.raises(ValueError, match="latent attention wants"):
        flash_attention_latent(q, kn, kr[..., :4], v)
    with pytest.raises(ValueError, match="latent attention wants"):
        flash_attention_latent(q, kn[:, :, :1], kr, v)


def test_interleaved_rotation_scores_as_the_rotation_in_place():
    x = jax.random.normal(jax.random.key(1), (2, 12, 3, 8))
    y = jax.random.normal(jax.random.key(2), (2, 12, 3, 8))
    pos = jnp.arange(12)
    cfg = {"qk_rope_head_dim": 8, "rope_theta": 32e6, "rope_scaling": None}
    cos, sin = FAMILY.rope_tables(cfg, 12)
    rx, ry = (apply_rope(t, pos, theta=32e6, interleave=True) for t in (x, y))
    wx, wy = (FAMILY._rotate_pairs(t, cos, sin) for t in (x, y))
    # the program leaves the pairs de-interleaved: a permutation of lanes
    np.testing.assert_allclose(rx[..., :4], wx[..., 0::2], atol=1e-6)
    np.testing.assert_allclose(rx[..., 4:], wx[..., 1::2], atol=1e-6)
    np.testing.assert_allclose(jnp.einsum("bqhd,bkhd->bhqk", rx, ry),
                               jnp.einsum("bqhd,bkhd->bhqk", wx, wy),
                               atol=1e-5)
    # and it is not the split-half pairing
    assert not np.allclose(
        jnp.einsum("bqhd,bkhd->bhqk", apply_rope(x, pos, theta=32e6),
                   apply_rope(y, pos, theta=32e6)),
        jnp.einsum("bqhd,bkhd->bhqk", wx, wy), atol=1e-3)


# ---- the latent mixer -------------------------------------------------------

@pytest.mark.parametrize("impl", ["dense", "flash"])
def test_latent_mixer_matches_the_reference_on_both_paths(impl):
    cfg = toy_mla.config(dtype="float32")
    mcfg = dataclasses.replace(
        TransformerConfig(**cfg["program"]["model"]), attention_impl=impl)
    z = FAMILY._sizes(cfg)
    x = jax.random.normal(jax.random.key(5), (2, 48, 64))
    p = Attention(mcfg).init(jax.random.key(6), x)["params"]
    assert {k: v.shape for k, v in weights.flatten(p).items()} == {
        "q_a/kernel": (64, 24), "q_a_norm/scale": (24,),
        "q_b/kernel": (24, 4 * 24), "kv_a/kernel": (64, 16 + 8),
        "kv_a_norm/scale": (16,), "kv_b/kernel": (16, 4 * 32),
        "out/kernel": (4 * 16, 64)}
    # scales that are not one, so that each norm is seen to be its own
    p = dict(p, q_a_norm={"scale": 1.0 + 0.3 * jax.random.normal(
        jax.random.key(7), (24,))}, kv_a_norm={"scale": 1.0 + 0.3 * (
            jax.random.normal(jax.random.key(8), (16,)))})
    cos, sin = FAMILY.rope_tables(cfg, 48)

    def program(p_, x_):
        return Attention(mcfg).apply({"params": p_}, x_)

    def plain(p_, x_, fault=None):
        return FAMILY._attention(_flat(p_, "attn"), x_, z,
                                 cfg["rms_norm_eps"], cos, sin, mm, fault)

    np.testing.assert_allclose(program(p, x), plain(p, x), atol=2e-5)
    for fault in ("rope_key_per_head", "rope_all_lanes",
                  "scale_from_value_width"):
        assert not np.allclose(plain(p, x, fault), plain(p, x), atol=1e-3)
    got = jax.grad(lambda p_: jnp.sum(program(p_, x) ** 2))(p)
    want = jax.grad(lambda p_: jnp.sum(plain(p_, x) ** 2))(p)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, atol=2e-4, rtol=2e-4)


def test_latent_mixer_under_a_key_padding_mask_takes_the_dense_path():
    cfg = toy_mla.config(dtype="float32")
    mcfg = TransformerConfig(**cfg["program"]["model"])     # impl flash
    x = jax.random.normal(jax.random.key(9), (2, 16, 64))
    p = Attention(mcfg).init(jax.random.key(10), x)["params"]
    mask = jnp.ones((2, 16), bool).at[1, :4].set(False)
    (latent,) = _counted(
        lambda: Attention(mcfg).apply({"params": p}, x, mask=mask),
        "flash.calls.latent")
    assert latent == 0
    out = Attention(mcfg).apply({"params": p}, x, mask=mask)
    np.testing.assert_allclose(out[0], Attention(mcfg).apply(
        {"params": p}, x)[0], atol=1e-5)      # row 0 sees every key
    assert not np.allclose(out[1, 8:], Attention(mcfg).apply(
        {"params": p}, x)[1, 8:], atol=1e-4)


# ---- (c) the shared expert, the factor, and the shares -------------------

def _moe_cfg(held=None, offset=0, dtype="float32", **kw):
    kw = dict(dict(moe_shared_experts=1, moe_routed_scale=2.5), **kw)
    return TransformerConfig(
        vocab_size=64, d_model=32, n_heads=2, n_layers=1, d_ff=64,
        dtype=dtype, num_experts=16, moe_every=1, moe_router="dropless",
        moe_top_k=4, moe_d_ff=24, moe_experts_held=held,
        moe_expert_offset=offset, moe_scoring="sigmoid",
        moe_expert_bias=True, activation="silu", mlp_style="gated", **kw)


def _moe_params(key, x):
    whole = MoEMLP(_moe_cfg()).init(key, x)["params"]
    assert whole["shared"]["wi_gate"]["kernel"].shape == (32, 24)
    return dict(whole, expert_bias=0.05 * jax.random.normal(
        jax.random.fold_in(key, 1), (16,)))


def _share(whole, held, offset):
    return dict(whole, **{
        name: {"kernel": whole[name]["kernel"][offset:offset + held]}
        for name in ("experts_wi", "experts_up", "experts_wo")})


def _reference_layer(whole, x, held, offset, fault=None, scale=2.5):
    z = {"e": 16, "k": 4, "held": held, "off": offset, "scale": scale}
    p = _flat(_share(whole, held, offset), "moe")
    return FAMILY._sparse_ff(p, x.reshape(-1, x.shape[-1]), z, mm,
                             fault).reshape(x.shape)


def test_shared_expert_and_scaling_factor_match_the_reference():
    x = jax.random.normal(jax.random.key(11), (2, 24, 32))
    whole = _moe_params(jax.random.key(12), x)
    out = MoEMLP(_moe_cfg()).apply({"params": whole}, x)
    np.testing.assert_allclose(out, _reference_layer(whole, x, 16, 0),
                               atol=2e-5)
    for fault in ("no_shared_expert", "no_routed_scale", "bias_in_weights",
                  "zero_expert"):
        assert not np.allclose(
            out, _reference_layer(whole, x, 16, 0, fault=fault), atol=1e-4)
    # each mechanism by its own field: routed = (out - shared) / 2.5
    routed = MoEMLP(_moe_cfg(moe_shared_experts=0, moe_routed_scale=1.0)
                    ).apply({"params": {k: v for k, v in whole.items()
                                        if k != "shared"}}, x)
    shared = tfm.DenseMLP(dataclasses.replace(_moe_cfg(), d_ff=24)).apply(
        {"params": whole["shared"]}, x)
    np.testing.assert_allclose(out, 2.5 * routed + shared, atol=2e-5)
    assert float(jnp.abs(shared).max()) > 1e-3
    (calls,) = _counted(lambda: jax.jit(lambda x_: MoEMLP(_moe_cfg()).apply(
        {"params": whole}, x_)).lower(x), "moe.shared.calls")
    assert calls == 1


@pytest.mark.parametrize("dtype,atol", [
    # float32: sums of the same products in another order
    ("float32", 3e-5),
    # bfloat16: outputs of size 0.4 (3.4 at the most) rounded to 8 bits,
    # each share once and the uncut layer once
    ("bfloat16", 4e-2)])
def test_the_four_shares_add_up_with_the_shared_expert_counted_once(
        dtype, atol):
    """Offsets 0, 4, 8, 12 of 16 experts, 4 picks a token; router, bias and
    shared expert on every chip: every share holds the shared expert's
    output in full, so the layer is the shares' sum less three of it."""
    x = jax.random.normal(jax.random.key(15), (2, 24, 32))
    whole = _moe_params(jax.random.key(16), x)
    uncut = _reference_layer(whole, x, 16, 0)
    got = MoEMLP(_moe_cfg(dtype=dtype)).apply({"params": whole}, x)
    np.testing.assert_allclose(got.astype(jnp.float32), uncut, atol=atol)
    offsets = (0, 4, 8, 12)
    parts = [MoEMLP(_moe_cfg(4, off, dtype=dtype)).apply(
        {"params": _share(whole, 4, off)}, x).astype(jnp.float32)
        for off in offsets]
    shared = _reference_layer(whole, x, 16, 0) - _reference_layer(
        whole, x, 16, 0, fault="no_shared_expert")
    for off, part in zip(offsets, parts):
        np.testing.assert_allclose(
            part, _reference_layer(whole, x, 4, off), atol=atol)
        assert float(jnp.abs(part - shared).max()) > 1e-3   # routed rows
    np.testing.assert_allclose(sum(parts) - 3 * shared, uncut, atol=3 * atol)
    # the shared expert counted four times would stand out: three of it
    assert float(jnp.abs(shared).mean()) > 10 * atol


def test_shared_expert_and_factor_need_the_dropless_router():
    x = jnp.ones((1, 8, 32))
    for kw in (dict(moe_shared_experts=1), dict(moe_routed_scale=2.5)):
        bad = TransformerConfig(d_model=32, n_heads=2, d_ff=64, num_experts=4,
                                moe_router="topk", **kw)
        with pytest.raises(ValueError, match="dropless"):
            MoEMLP(bad).init(jax.random.key(0), x)


# ---- (e) the prediction module and the second loss ------------------------

MTP = dict(vocab_size=96, d_model=32, n_heads=2, n_layers=2, d_ff=80,
           max_seq_len=16, dtype="float32", rope=True, norm_type="rmsnorm",
           activation="silu", mlp_style="gated", mtp_modules=1,
           mtp_loss_weight=0.3)


def _mtp_model(key=19):
    cfg = TransformerConfig(**MTP)
    rows = jax.random.randint(jax.random.key(18), (2, 10), 0, 96)
    p = Transformer(cfg).init(jax.random.key(key), rows[:, :-1])["params"]
    # a table and a head that are not tiny, so that every use weighs in
    p = dict(p, token_embed={"embedding": 0.5 * jax.random.normal(
        jax.random.key(20), (96, 32))}, lm_head={"kernel": 0.3 * (
            jax.random.normal(jax.random.key(21), (32, 96)))})
    return cfg, rows, p


def test_the_module_reads_the_last_block_and_the_next_tokens_embedding():
    cfg, rows, p = _mtp_model()
    assert {k for k in p if k.startswith("mtp_")} == {
        "mtp_0_hnorm", "mtp_0_enorm", "mtp_0_proj", "mtp_0_block",
        "mtp_0_ln_f"}
    assert p["mtp_0_proj"]["kernel"].shape == (64, 32)
    tokens = rows[:, :-1]
    hidden, ahead = Transformer(cfg).apply({"params": p}, tokens,
                                           return_hidden=True)
    assert len(ahead) == 1 and ahead[0].shape == hidden.shape
    # without `return_hidden`: the logits of the main model alone
    plain = Transformer(dataclasses.replace(cfg, mtp_modules=0))
    q = {k: v for k, v in p.items() if not k.startswith("mtp_")}
    np.testing.assert_allclose(
        Transformer(cfg).apply({"params": p}, tokens),
        plain.apply({"params": q}, tokens), atol=1e-6)
    np.testing.assert_allclose(
        hidden, plain.apply({"params": q}, tokens, return_hidden=True),
        atol=1e-6)
    # position t of the module follows token t + 1's embedding, and, the
    # attention being causal, no later one's; the row's last position has
    # no token ahead and reads zeros
    t = 4
    other = tokens.at[:, t + 1].set((tokens[:, t + 1] + 1) % 96)
    _, moved = Transformer(cfg).apply({"params": p}, other,
                                      return_hidden=True)
    np.testing.assert_allclose(moved[0][:, :t], ahead[0][:, :t], atol=1e-6)
    assert not np.allclose(moved[0][:, t], ahead[0][:, t], atol=1e-4)


def test_the_second_loss_is_two_ahead_masked_and_weighted():
    cfg, rows, p = _mtp_model()
    model = Transformer(cfg)
    hidden, ahead = model.apply({"params": p}, rows[:, :-1],
                                return_hidden=True)
    kernel = p["lm_head"]["kernel"]
    loss, terms = next_token_losses(hidden, ahead, kernel, rows, 0.3, 4)
    first = lm_loss(mm(hidden, kernel), rows[:, 1:])
    # targets shifted by two; the last position has none and is masked
    two = jnp.concatenate([rows[:, 2:], jnp.full((2, 1), -1)], axis=1)
    second = lm_loss(mm(ahead[0], kernel), two)
    by_hand = lm_loss(mm(ahead[0][:, :-1], kernel), rows[:, 2:])
    assert float(second) == pytest.approx(float(by_hand), rel=1e-6)
    assert float(terms["loss.terms.next1"]) == pytest.approx(float(first),
                                                             rel=1e-5)
    assert float(terms["loss.terms.next2"]) == pytest.approx(
        0.3 * float(second), rel=1e-5)           # AFTER its weight
    assert float(loss) == pytest.approx(float(first + 0.3 * second),
                                        rel=1e-5)
    # the last position's prediction does not reach the loss
    bent = (ahead[0].at[:, -1].add(3.0),)
    assert float(next_token_losses(hidden, bent, kernel, rows, 0.3, 4)[0]) \
        == pytest.approx(float(loss), rel=1e-6)
    # shifted by one instead: another number
    one = lm_loss(mm(ahead[0], kernel), rows[:, 1:])
    assert abs(float(one) - float(second)) > 1e-3


def test_the_table_and_the_head_receive_both_gradients():
    cfg, rows, p = _mtp_model()
    model = Transformer(cfg)

    def term(p_, which):
        hidden, ahead = model.apply({"params": p_}, rows[:, :-1],
                                    return_hidden=True)
        loss, terms = next_token_losses(hidden, ahead, p_["lm_head"]["kernel"],
                                        rows, 0.3, 4)
        return loss if which is None else terms[which]

    g = jax.grad(term)(p, None)
    g1 = jax.grad(term)(p, "loss.terms.next1")
    g2 = jax.grad(term)(p, "loss.terms.next2")
    for path in (("token_embed", "embedding"), ("lm_head", "kernel"),
                 ("layer_1", "attn", "out", "kernel")):
        a, b, both = (_at(t, path) for t in (g1, g2, g))
        np.testing.assert_allclose(a + b, both, atol=1e-6, rtol=1e-5)
        assert float(jnp.linalg.norm(b)) > 0.02 * float(
            jnp.linalg.norm(both)), path
        assert float(jnp.linalg.norm(a)) > 0.02 * float(
            jnp.linalg.norm(both)), path
    # the module's own leaves see the second term only
    assert not np.asarray(g1["mtp_0_proj"]["kernel"]).any()
    assert np.asarray(g2["mtp_0_proj"]["kernel"]).any()
    assert np.asarray(g2["mtp_0_enorm"]["scale"]).any()


def _at(tree, path):
    for key in path:
        tree = tree[key]
    return tree


def test_the_loss_terms_ride_the_step_into_the_counters():
    from tensorflowonspark_tpu.optim import make_optimizer
    from tensorflowonspark_tpu.parallel import train as train_mod

    cfg, rows, p = _mtp_model()
    model = Transformer(cfg)

    def loss_fn(p_, batch, rng):
        hidden, ahead = model.apply({"params": p_}, batch[:, :-1],
                                    return_hidden=True)
        return next_token_losses(hidden, ahead, p_["lm_head"]["kernel"],
                                 batch, cfg.mtp_loss_weight, 4)

    loss_fn.counters = tfm.LOSS_COUNTERS
    opt, _ = make_optimizer("adamw", learning_rate=1e-3)
    step = train_mod.make_train_step(loss_fn, opt, donate=False)
    state = train_mod.create_train_state(p, opt)
    before = trace.counters().snapshot()
    seen = []
    for _ in range(2):
        state, metrics = step(state, rows, None)
        seen.append((float(metrics["loss.terms.next1"]),
                     float(metrics["loss.terms.next2"])))
        assert float(metrics["loss"]) == pytest.approx(sum(seen[-1]),
                                                       rel=1e-5)
    jax.block_until_ready(state)
    trace.report()                  # waits for every step dispatched
    now = trace.counters().snapshot()
    for i, name in enumerate(tfm.LOSS_COUNTERS):
        assert now.get(name, 0) - before.get(name, 0) == pytest.approx(
            sum(s[i] for s in seen), rel=1e-4)


# ---- (a) the whole model ----------------------------------------------------

def test_loss_gradient_and_three_steps_match_the_plain_reference():
    """The toy of the cell: layer 0 latent attention + dense, layers 1-2
    latent attention + sparse (a shared expert, sigmoid top-4 of 16, 4 held
    at offset 4, a bias that moves picks, the factor 2.5), the prediction
    module, an untied table and head of 256 rows; float32 with float32
    moments, so the comparison is tight: what is left is the order of
    float32 sums, and near-ties at the last pick."""
    from tensorflowonspark_tpu.parallel import train as train_mod

    cfg = toy_mla.config(dtype="float32")
    cfg["program"]["optimizer"]["mu_dtype"] = "float32"
    spec = toy_mla.spec()
    shapes = FAMILY.param_shapes(cfg)
    batches = traffic.first_batches(spec.traffic, cfg, 11, 3)
    ref = FAMILY.reference(cfg, lambda: weights.make(11, shapes), batches,
                           row_block=2)
    loss_fn, opt = FAMILY.build(cfg)
    params = weights.nest(weights.make(11, shapes))
    (loss, stats), grads = jax.jit(jax.value_and_grad(loss_fn, has_aux=True))(
        params, jnp.asarray(batches[0]), None)
    pairs = 4 * 48 * 4 * 3          # rows x tokens x picks x sparse layers
    assert float(stats["moe.pairs.local"] + stats["moe.pairs.absent"]) == pairs
    assert 0 < float(stats["moe.picks.moved"]) < 0.5 * pairs
    assert float(stats["loss.terms.next1"] + stats["loss.terms.next2"]) == \
        pytest.approx(float(loss), rel=1e-6)
    assert 0.05 < float(stats["loss.terms.next2"]) / float(loss) < 0.15
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
        assert moved[k] == pytest.approx(want, rel=2e-3, abs=2e-3 * scale), k
        assert (want == 0) == k.endswith("expert_bias"), k


@pytest.fixture(scope="module")
def one_step():
    """`follow(fault)`: the reference's first step at the toy's size."""
    cfg = toy_mla.config(dtype="float32")
    shapes = FAMILY.param_shapes(cfg)
    batches = traffic.first_batches(toy_mla.spec().traffic, cfg, 5, 1)
    return lambda fault=None: FAMILY.reference(
        cfg, lambda: weights.make(5, shapes), batches, row_block=2,
        fault=fault)


@pytest.fixture(scope="module")
def sound(one_step):
    return one_step()


@pytest.mark.parametrize("fault", FAMILY.FAULTS)
def test_each_planted_fault_moves_the_reference(one_step, sound, fault):
    bent = one_step(fault)
    gap = max(abs(bent["grad_norms"][k] - v) / max(v, 1e-6)
              for k, v in sound["grad_norms"].items()
              if not k.endswith("expert_bias"))
    assert gap > 0.02 or bent["grad_norms"][
        "layer_1/moe/expert_bias"] > 0, (fault, gap)


# ---- (d) the cell's tree ------------------------------------------------------

def test_the_cells_tree_is_the_familys_and_the_issues_table():
    cfg = traffic.load("configs", "joyai-llm-flash")
    model = Transformer(TransformerConfig(**cfg["program"]["model"]))
    theirs = weights.flatten(jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"]))
    ours = FAMILY.param_shapes(cfg)
    assert {k: v.shape for k, v in theirs.items()} == \
        {k: s for k, (s, _) in ours.items()}

    def held(prefix):
        return sum(math.prod(v.shape) for k, v in theirs.items()
                   if k.startswith(prefix))

    mla = 3145728 + 1536 + 9437184 + 1179648 + 512 + 4194304 + 8388608
    assert mla == 26347520 == held("layer_3/attn/")
    assert held("layer_0/") == mla + 44040192 + 4096 == 70391808
    sparse = mla + 524288 + 256 + 4718592 + 75497472 + 4096
    for i in (1, 2, 3, 4):
        assert held(f"layer_{i}/") == sparse == 107092224
    assert held("token_embed/") + held("lm_head/") + held("ln_f/") == \
        33095680 + 33095680 + 2048 == 66193408
    assert held("mtp_0_") == 4096 + 8388608 + sparse + 2048 == 115486976
    assert sum(math.prod(v.shape) for v in theirs.values()) == 680441088
    assert theirs["layer_2/attn/kv_a/kernel"].shape == (2048, 576)
    assert theirs["layer_2/attn/kv_b/kernel"].shape == (512, 8192)
    assert theirs["layer_2/attn/q_b/kernel"].shape == (1536, 6144)
    assert theirs["layer_2/attn/out/kernel"].shape == (4096, 2048)
    assert theirs["mtp_0_block/moe/experts_wi/kernel"].shape == \
        (16, 2048, 768)
    assert theirs["layer_1/moe/router/kernel"].shape == (2048, 256)
    assert theirs["layer_1/moe/shared/wo/kernel"].shape == (768, 2048)
    assert FAMILY.step_work(cfg, 2)["n_params"] == 680441088


# ---- (f) what is refused, and what a step program was built of -----------

@pytest.mark.parametrize("extra,names", [
    (dict(kv_lora_rank=8, q_lora_rank=8, qk_nope_head_dim=8,
          qk_rope_head_dim=4, v_head_dim=8), "kv_lora_rank"),
    (dict(q_lora_rank=8), "q_lora_rank"),
    (dict(qk_nope_head_dim=8), "qk_nope_head_dim"),
    (dict(qk_rope_head_dim=4), "qk_rope_head_dim"),
    (dict(v_head_dim=8), "v_head_dim"),
    (dict(rope_interleave=True), "rope_interleave"),
    (dict(num_experts=4, moe_router="dropless", moe_shared_experts=1),
     "moe_shared_experts"),
    (dict(num_experts=4, moe_router="dropless", moe_routed_scale=2.5),
     "moe_routed_scale"),
    (dict(mtp_modules=1), "mtp_modules"),
    (dict(mtp_loss_weight=0.1), "mtp_loss_weight"),
])
def test_decode_with_each_new_field_raises(extra, names):
    base = dict(vocab_size=64, d_model=32, n_heads=2, n_layers=2, d_ff=64)
    with pytest.raises(NotImplementedError, match=names):
        TransformerConfig(**base, decode=True, **extra)
    TransformerConfig(**base, **extra)           # training: fine


def test_latent_attention_wants_all_its_widths_and_no_split_sequence():
    base = dict(vocab_size=64, d_model=32, n_heads=2, n_layers=2, d_ff=64)
    with pytest.raises(ValueError, match="q_lora_rank"):
        TransformerConfig(**base, kv_lora_rank=8)
    full = dict(kv_lora_rank=8, q_lora_rank=8, qk_nope_head_dim=8,
                qk_rope_head_dim=4, v_head_dim=8)
    with pytest.raises(NotImplementedError, match="latent"):
        TransformerConfig(**base, **full, ring_attention_axis="tp")
    with pytest.raises(NotImplementedError, match="latent"):
        TransformerConfig(**base, **full, sliding_window=4, layer_types=(
            "sliding_attention", "full_attention"))


def test_latent_mixers_and_shared_experts_are_counted_once_a_traced_call():
    cfg = toy_mla.config(dtype="float32")
    model = Transformer(TransformerConfig(**cfg["program"]["model"]))
    tokens = jnp.zeros((1, 16), jnp.int32)
    params = jax.eval_shape(lambda: model.init(jax.random.key(0), tokens))
    latent, plain, shared = _counted(
        lambda: jax.jit(lambda p: model.apply(
            p, tokens, return_hidden=True)).lower(params),
        "mixer.calls.latent", "mixer.calls.attention", "moe.shared.calls")
    # three blocks and the module's; its sparse layer beside the two
    assert (latent, plain, shared) == (4, 0, 3)
