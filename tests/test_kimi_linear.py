"""Kimi Linear's hybrid stack: Kimi Delta Attention on the chunked gated
delta rule, latent attention with no positions and no query low-rank,
sigmoid top-k experts beside a shared one.  The program
(`models/transformer.py`, `ops/kda.py`) against the benchmark's plain
reference (`benchmark/families/kimi_linear.py`, which imports nothing of
the program: KDA as the recurrence itself), at toy size on the CPU, float32
unless said; the Pallas kernels run in the interpreter."""
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
import toy_kimi  # noqa: E402
import harness  # noqa: E402
import traffic  # noqa: E402
import weights  # noqa: E402

from tensorflowonspark_tpu import trace  # noqa: E402
from tensorflowonspark_tpu.models.transformer import (  # noqa: E402
    Attention, KimiDeltaMixer, MoEMLP, Transformer, TransformerConfig)
from tensorflowonspark_tpu.ops.kda import kda  # noqa: E402

FAMILY = harness.load_module("families", "kimi_linear")
HI = jax.lax.Precision.HIGHEST


def mm(a, b):
    return jnp.matmul(a, b, precision=HI)


def _counted(fn, *names):
    before = trace.counters().snapshot()
    fn()
    now = trace.counters().snapshot()
    return [now.get(n, 0) - before.get(n, 0) for n in names]


# ---- (a) the chunked delta rule against the recurrence ----------------------

def _unit(x):
    """The reference's L2 norm a head."""
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _kda_inputs(key, S, H=2, D=16, decay=1.0):
    """q, k, v, g `[2, S, H * D]` as the mixer gives them to `kda` (q and k
    not normalised), beta `[2, H, S]`, the head norm's weight `[D]`, and a
    cotangent."""
    k = jax.random.split(key, 7)
    q = 2.0 * jax.random.normal(k[0], (2, S, H * D))
    kk = jax.random.normal(k[1], (2, S, H * D))
    v = jax.random.normal(k[2], (2, S, H * D))
    g = -decay * jax.nn.softplus(
        jax.random.normal(k[3], (2, S, H * D)) - 2.0)
    beta = jax.nn.sigmoid(jax.random.normal(k[4], (2, H, S)))
    w = 0.5 + jax.random.uniform(k[5], (D,))
    return q, kk, v, g, beta, w, jax.random.normal(k[6], (2, S, H * D))


EPS = 1e-5      # the head norm's


def _recurrence(q, k, v, g, beta, w=None, fault=None):
    """The reference on views a head: the L2 norms of q and k, then the
    recurrence, which scales k and v by beta itself; then, given `w`, the
    head's RMSNorm."""
    nb, S, _ = q.shape
    H = beta.shape[1]

    def heads(t):
        return t.reshape(nb, S, H, -1)

    o = FAMILY._delta(_unit(heads(q)), _unit(heads(k)), heads(v), heads(g),
                      beta.transpose(0, 2, 1), fault)
    if w is not None:
        o = o / jnp.sqrt(jnp.mean(o * o, axis=-1, keepdims=True) + EPS) * w
    return o.reshape(nb, S, -1)


def _on_o_scale(out, o, w):
    """`out`, KDA's output under the head norm, taken back to the scale of
    the reference's un-normalised `o` by o's own rms a head: compared
    there, the tolerance is on o's error, not on o's error over its rms."""
    heads = out.shape[:2] + (-1, w.shape[0])
    rms = jnp.sqrt(jnp.mean(o.reshape(heads) ** 2, axis=-1, keepdims=True)
                   + EPS)
    return (out.reshape(heads) / w * rms).reshape(out.shape)


def _matches(q, k, v, g, beta, w, ct, chunk, kernels=(True, False)):
    """Forward and the six gradients (q, k, v, g, beta, the norm's weight)
    of the chunked form under the head norm, on the kernels and on the
    `jax.numpy` chunks, against the recurrence's."""
    def loss(fn):
        return lambda *a: jnp.sum(fn(*a) * ct)

    args = (q, k, v, g, beta, w)
    every = tuple(range(6))
    with jax.default_matmul_precision("highest"):
        want = _recurrence(*args[:5])
        want_g = jax.grad(loss(_recurrence), argnums=every)(*args)
        for kernel in kernels:
            def fn(*a):
                return kda(*a, EPS, chunk, kernel=kernel, interpret=True)

            np.testing.assert_allclose(_on_o_scale(fn(*args), want, w),
                                       want, rtol=1e-5, atol=2e-6)
            got_g = jax.grad(loss(fn), argnums=every)(*args)
            for name, a, b in zip(("q", "k", "v", "g", "beta", "w"), got_g,
                                  want_g):
                scale = float(jnp.max(jnp.abs(b)))
                np.testing.assert_allclose(a, b, atol=2e-5 * scale,
                                           err_msg=(kernel, name))


def test_kda_matches_the_recurrence_and_its_gradients():
    """Three whole chunks of 32 and half of a fourth."""
    _matches(*_kda_inputs(jax.random.key(112), 112), 32)


@pytest.mark.parametrize("kernel", [True, False], ids=["kernels", "chunks"])
def test_kda_matches_the_recurrence_at_the_cells_head_width(kernel):
    """Heads of 128, as the cell's, two of them, a row of one chunk of 32
    and a half: the lanes of a head are one tile of `[B, S, H * 128]`."""
    _matches(*_kda_inputs(jax.random.key(128), 48, D=128), 32,
             kernels=(kernel,))


def test_a_decay_that_overflows_a_factored_form():
    """One chunk of 64 under decays of about -8 a step: its running sum
    falls to several hundred, where `exp(-G)` is infinite in float32, so a
    form that takes `exp(G_t - G_s)` apart as `exp(G_t) exp(-G_s)` gives
    inf or NaN; the sub-chunks never exponentiate a positive sum."""
    inputs = _kda_inputs(jax.random.key(9), 64, decay=8.0)
    assert not bool(jnp.all(jnp.isfinite(jnp.exp(
        -jnp.cumsum(inputs[3], axis=1)))))
    _matches(*inputs, 64)


def test_a_state_crosses_three_chunks():
    """Keys and values in the first chunk only, a slow decay: what the
    fourth chunk's queries read came across three chunk boundaries."""
    q, k, v, g, beta, w, _ = _kda_inputs(jax.random.key(3), 64,
                                         decay=0.01)
    k = k.at[:, 16:].set(0.0)                 # a zero key stays zero
    v = v.at[:, 16:].set(0.0)
    late = slice(48, 64)
    with jax.default_matmul_precision("highest"):
        want = _recurrence(q, k, v, g, beta)[:, late]
        assert float(jnp.abs(want).mean()) > 1e-2
        for kernel in (True, False):
            got = kda(q, k, v, g, beta, w, EPS, 16, kernel=kernel,
                      interpret=True)
            np.testing.assert_allclose(_on_o_scale(got[:, late], want, w),
                                       want, rtol=1e-5, atol=1e-6)


def test_kda_calls_are_counted_by_path_and_chunks_checked():
    q, k, v, g, beta, w, _ = _kda_inputs(jax.random.key(4), 32)
    for kernel, name in ((True, "kda.calls.kernel"),
                         (False, "kda.calls.reference")):
        got = _counted(lambda: jax.jit(lambda *a: kda(
            *a, EPS, 16, kernel=kernel, interpret=True)).lower(
                q, k, v, g, beta, w),
            "kda.calls.kernel", "kda.calls.reference")
        assert got == ([1, 0] if kernel else [0, 1])
    with pytest.raises(ValueError, match="sub-chunks"):
        kda(q, k, v, g, beta, w, EPS, 24)
    # natively the kernels take a head as whole lane tiles
    with pytest.raises(ValueError, match="kda_head_dim=16"):
        kda(q, k, v, g, beta, w, EPS, 16, kernel=True, interpret=False)


@pytest.mark.parametrize("kernel", [True, False], ids=["kernels", "chunks"])
def test_float32_inputs_give_a_bfloat16_output(kernel):
    """The mixer hands `kda` the convs' output in float32 and asks for o in
    bfloat16: o comes in `dtype`, the gradients in the inputs' types, both
    the float32 result's to bfloat16's rounding."""
    q, k, v, g, beta, w, ct = _kda_inputs(jax.random.key(5), 48)
    args = (q, k, v, g, beta, w)

    def loss(dtype):
        return lambda *a: jnp.sum(kda(*a, EPS, 16, kernel=kernel,
                                      interpret=True, dtype=dtype
                                      ).astype(jnp.float32) * ct)

    with jax.default_matmul_precision("highest"):
        want = kda(*args, EPS, 16, kernel=kernel, interpret=True)
        got = kda(*args, EPS, 16, kernel=kernel, interpret=True,
                  dtype=jnp.bfloat16)
        want_g = jax.grad(loss(None), argnums=tuple(range(6)))(*args)
        got_g = jax.grad(loss(jnp.bfloat16), argnums=tuple(range(6)))(*args)
    assert got.dtype == jnp.bfloat16 and want.dtype == jnp.float32
    np.testing.assert_allclose(got.astype(jnp.float32), want, rtol=1e-2,
                               atol=1e-2)
    for name, a, b in zip(("q", "k", "v", "g", "beta", "w"), got_g, want_g):
        assert a.dtype == b.dtype == jnp.float32, name
        scale = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(a, b, atol=2e-2 * scale, err_msg=name)


# ---- (b) the mixers -----------------------------------------------------------

def _toy():
    return toy_kimi.config(dtype="float32")


def _model_cfg(**kw):
    return TransformerConfig(**dict(_toy()["program"]["model"], **kw))


def _layer(i, sub):
    cfg = _toy()
    pre = f"layer_{i}/{sub}/"
    shapes = {k[len(f"layer_{i}/"):]: v for k, v in
              FAMILY.param_shapes(cfg).items() if k.startswith(pre)}
    flat = weights.make(5, shapes)
    return cfg, flat, weights.nest(flat)[sub]


def test_kda_mixer_matches_the_reference():
    """Convs, L2 norms, the decay a channel, beta, the recurrence, the
    gated head norm with its bias, on the kernels."""
    cfg, flat, params = _layer(0, "kda")
    z = FAMILY._sizes(cfg)
    mcfg = _model_cfg()
    u = jax.random.normal(jax.random.key(5), (2, 40, 64))
    with jax.default_matmul_precision("highest"):
        got = KimiDeltaMixer(mcfg).apply({"params": params}, u)
        want = FAMILY._kda(flat, u, z, 1e-5, mm, None)
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
        g_got = jax.grad(lambda p: jnp.sum(jnp.sin(KimiDeltaMixer(
            mcfg).apply({"params": p}, u))))(params)
        g_want = jax.grad(lambda p: jnp.sum(jnp.sin(FAMILY._kda(
            p, u, z, 1e-5, mm, None))))(flat)
    g_got = {"kda/" + k: v for k, v in weights.flatten(g_got).items()}
    assert set(g_got) == set(g_want)
    for k, v in g_want.items():
        np.testing.assert_allclose(g_got[k], v, rtol=1e-3,
                                   atol=1e-4 * float(jnp.abs(v).max()),
                                   err_msg=k)
    # every mechanism shows: each fault moves the output
    for fault in ("decay_per_head", "no_delta", "no_l2_norm", "silu_gate"):
        bent = FAMILY._kda(flat, u, z, 1e-5, mm, fault)
        assert not np.allclose(bent, want, atol=1e-3), fault


@pytest.mark.parametrize("impl", ["flash", "dense"])
def test_latent_attention_with_no_query_rank_and_no_rotation(impl):
    """`q_lora_rank` None: the query straight from the input (`query`);
    `rope` False: the shared key's 8 lanes and the query's read as they
    come, on the latent kernels and on the dense core alike."""
    cfg, flat, params = _layer(3, "attn")
    z = FAMILY._sizes(cfg)
    mcfg = _model_cfg(attention_impl=impl)
    assert mcfg.q_lora_rank is None and not mcfg.rope
    assert set(params) == {"query", "kv_a", "kv_a_norm", "kv_b", "out"}
    u = jax.random.normal(jax.random.key(6), (2, 40, 64))
    with jax.default_matmul_precision("highest"):
        got = Attention(mcfg).apply({"params": params}, u)
        want = FAMILY._attention(flat, u, z, 1e-5, cfg, mm, None)
        turned = FAMILY._attention(flat, u, z, 1e-5, cfg, mm, "rotated_key")
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    assert not np.allclose(turned, want, atol=1e-4)
    # causal: a later token moves nothing before it
    later = Attention(mcfg).apply({"params": params}, u.at[:, 30:].add(1.0))
    np.testing.assert_allclose(later[:, :30], got[:, :30], atol=1e-5)


def test_kda_layers_are_causal_and_take_no_mask():
    cfg = _model_cfg()
    u = jax.random.normal(jax.random.key(6), (1, 40, 64))
    p = KimiDeltaMixer(cfg).init(jax.random.key(7), u)
    base = KimiDeltaMixer(cfg).apply(p, u)
    later = KimiDeltaMixer(cfg).apply(p, u.at[:, 30:].add(1.0))
    np.testing.assert_allclose(base[:, :30], later[:, :30], atol=1e-6)
    assert not np.allclose(base[:, 30:], later[:, 30:])
    with pytest.raises(NotImplementedError, match="mask"):
        KimiDeltaMixer(cfg).apply(p, u, mask=jnp.ones((1, 40), bool))


# ---- (c) the experts: a chip's share of the layer ------------------------------

def _moe_cfg(held=None, offset=0, **kw):
    return TransformerConfig(**dict(dict(
        _toy()["program"]["model"], num_experts=32, moe_top_k=8,
        moe_experts_held=held, moe_expert_offset=offset), **kw))


def _moe_params(key):
    cfg = _toy()
    cfg["num_experts"] = cfg["published"]["num_experts"] = 32
    cfg["init"]["expert_bias_std"] = 0.05
    shapes = {k[len("layer_1/"):]: v for k, v in
              FAMILY.param_shapes(cfg).items() if k.startswith("layer_1/moe")}
    return weights.make(key, shapes)


def _reference_part(flat, x, held, off, fault=None):
    z = {"k": 8, "e": 32, "held": held, "off": off, "scale": 2.446}
    p = dict(flat)
    for name in ("experts_wi", "experts_up", "experts_wo"):
        k = f"moe/{name}/kernel"
        p[k] = flat[k][off:off + held]
    d = x.shape[-1]
    return FAMILY.mla._sparse_ff(p, x.reshape(-1, d), z, mm, fault).reshape(
        x.shape)


def test_the_shares_add_up_with_the_shared_expert_counted_once():
    """Four chips of eight experts each, 8 picks a token of 32; router,
    bias and shared expert on every chip: every share holds the shared
    expert's output in full, so the layer is the shares' sum less three of
    it."""
    flat = _moe_params(15)
    x = jax.random.normal(jax.random.key(16), (2, 24, 64))
    with jax.default_matmul_precision("highest"):
        uncut = _reference_part(flat, x, 32, 0)
        shared = uncut - _reference_part(flat, x, 32, 0, "no_shared_expert")
        whole = MoEMLP(_moe_cfg()).apply(
            {"params": weights.nest(flat)["moe"]}, x)
        np.testing.assert_allclose(whole, uncut, atol=3e-5)
        parts = []
        for off in range(0, 32, 8):
            p = dict(flat)
            for name in ("experts_wi", "experts_up", "experts_wo"):
                k = f"moe/{name}/kernel"
                p[k] = flat[k][off:off + 8]
            part = MoEMLP(_moe_cfg(8, off)).apply(
                {"params": weights.nest(p)["moe"]}, x)
            np.testing.assert_allclose(
                part, _reference_part(flat, x, 8, off), atol=3e-5)
            parts.append(part)
    np.testing.assert_allclose(sum(parts) - 3 * shared, uncut, atol=1.2e-4)
    assert 3 * float(jnp.abs(shared).mean()) > 1.2e-4


# ---- (d) the whole model against the plain reference ---------------------------

def test_loss_gradient_and_three_steps_match_the_plain_reference():
    """The toy of the cell, KDA + dense, then KDA, KDA, MLA, KDA with
    experts, float32 with float32 moments (AdamW of optax: the fused kernel's
    own tests hold it to the same update), on the `jax.numpy` paths (the
    kernels' own tests hold them to the same numbers), so the comparison is
    tight: what is left is the order of float32 sums."""
    from tensorflowonspark_tpu.parallel import train as train_mod

    cfg = _toy()
    cfg["program"]["optimizer"].update(name="adamw", mu_dtype="float32")
    cfg["program"]["model"]["attention_impl"] = "dense"
    spec = toy_kimi.spec()
    shapes = FAMILY.param_shapes(cfg)
    batches = traffic.first_batches(spec.traffic, cfg, 11, 3)
    ref = FAMILY.reference(cfg, lambda: weights.make(11, shapes), batches,
                           row_block=2)
    loss_fn, opt = FAMILY.build(cfg)
    params = weights.nest(weights.make(11, shapes))
    step = train_mod.make_train_step(loss_fn, opt, donate=False)
    state = train_mod.create_train_state(params, opt)
    losses = []
    for batch in batches:
        state, metrics = step(state, jnp.asarray(batch), None)
        losses.append(float(metrics["loss"]))
        if len(losses) == 1:
            got = harness.first_grad_norms(cfg["program"]["optimizer"],
                                           state.opt_state)
            pairs = 4 * 72 * 2 * 4      # rows x tokens x picks x layers
            assert float(metrics["moe.pairs.local"]
                         + metrics["moe.pairs.absent"]) == pairs
    assert losses == pytest.approx(ref["losses"], rel=5e-6)
    assert set(got) == set(ref["grad_norms"])
    scale = float(np.median(list(ref["grad_norms"].values())))
    for k, want in ref["grad_norms"].items():
        assert got[k] == pytest.approx(want, rel=2e-3, abs=2e-3 * scale), k
    assert not any(got[k] for k in got if k.endswith("expert_bias"))
    start = weights.make(11, shapes)
    moved = {k: float(jnp.linalg.norm((v - start[k]).ravel()))
             for k, v in weights.flatten(state.params).items()}
    scale = float(np.median(list(ref["update_norms"].values())))
    for k, want in ref["update_norms"].items():
        assert moved[k] == pytest.approx(want, rel=5e-3, abs=5e-3 * scale), k


# ---- (e) what is refused, and what a step program was built of -------------------

def test_decode_refuses_a_kda_layer():
    base = dict(vocab_size=64, d_model=32, n_heads=2, n_layers=2, d_ff=64,
                kda_heads=2, kda_head_dim=16)
    kinds = dict(layer_types=("kda", "full_attention"))
    for extra in (kinds, {}):
        with pytest.raises(NotImplementedError, match="kda_heads"):
            TransformerConfig(**base, decode=True, **extra)
        TransformerConfig(**base, **extra)       # training: fine


def test_kda_layers_want_their_widths_and_a_whole_sequence():
    base = dict(vocab_size=64, d_model=32, n_heads=2, n_layers=2, d_ff=64,
                layer_types=("kda", "full_attention"))
    with pytest.raises(ValueError, match="kda_heads"):
        TransformerConfig(**base)
    for axis in ("ring_attention_axis", "ulysses_axis"):
        with pytest.raises(NotImplementedError, match="kda"):
            TransformerConfig(**base, kda_heads=2, kda_head_dim=16,
                              **{axis: "tp"})
    latent = dict(kv_lora_rank=8, qk_nope_head_dim=8, qk_rope_head_dim=4,
                  v_head_dim=8)
    TransformerConfig(**base, kda_heads=2, kda_head_dim=16, **latent)
    with pytest.raises(NotImplementedError, match="latent"):
        TransformerConfig(**dict(base, layer_types=("conv", "full_attention")),
                          **latent)


def test_mixers_and_kernels_are_counted_once_a_traced_call():
    cfg = _model_cfg()
    model = Transformer(cfg)
    tokens = jnp.zeros((1, 40), jnp.int32)
    params = jax.eval_shape(lambda: model.init(jax.random.key(1), tokens))
    assert "pos_embed" not in params["params"]
    got = _counted(lambda: jax.jit(lambda p: model.apply(
        p, tokens, mutable=["intermediates"])).lower(params),
        "mixer.calls.kda", "mixer.calls.latent", "mixer.calls.attention",
        "kda.calls.kernel", "kda.calls.reference")
    assert got == [4, 1, 0, 4, 0]       # `flash`: the kernels
    dense = Transformer(dataclasses.replace(cfg, attention_impl="dense"))
    got = _counted(lambda: jax.jit(lambda p: dense.apply(
        p, tokens, mutable=["intermediates"])).lower(params),
        "kda.calls.kernel", "kda.calls.reference")
    assert got == [0, 4]
