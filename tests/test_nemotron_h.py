"""Nemotron-H's hybrid layers: Mamba-2 mixers on the chunked SSD scan,
layers that hold one part each, relu-squared experts beside a shared expert
twice their width, attention with no positions.  The program
(`models/transformer.py`, `ops/ssd.py`) against the benchmark's plain
reference (`benchmark/families/nemotron_h.py`, which imports nothing of the
program: the state space as the recurrence itself), at toy size on the CPU,
float32 unless said; the Pallas kernels run in the interpreter."""
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
import toy_nemotron  # noqa: E402
import harness  # noqa: E402
import traffic  # noqa: E402
import weights  # noqa: E402

from tensorflowonspark_tpu import trace  # noqa: E402
from tensorflowonspark_tpu.models import transformer as tfm  # noqa: E402
from tensorflowonspark_tpu.models.transformer import (  # noqa: E402
    Block, Mamba2Mixer, MoEMLP, Transformer, TransformerConfig)
from tensorflowonspark_tpu.ops.ssd import ssd  # noqa: E402

FAMILY = harness.load_module("families", "nemotron_h")
HI = jax.lax.Precision.HIGHEST


def mm(a, b):
    return jnp.matmul(a, b, precision=HI)


def _counted(fn, *names):
    before = trace.counters().snapshot()
    fn()
    now = trace.counters().snapshot()
    return [now.get(n, 0) - before.get(n, 0) for n in names]


# ---- (a) the SSD scan against the recurrence --------------------------------

def _ssd_inputs(key, S, H=4, G=2, P=8, N=16, slow=False):
    k = jax.random.split(key, 5)
    x = jax.random.normal(k[0], (2, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(k[1], (2, S, H)) - 3.0)
    A = -jnp.arange(1.0, H + 1) * (0.01 if slow else 0.3)
    B = 0.5 * jax.random.normal(k[2], (2, S, G, N))
    C = 0.5 * jax.random.normal(k[3], (2, S, G, N))
    return x, dt, A, B, C, jax.random.normal(k[4], (2, S, H, P))


def _recurrence(x, dt, A, B, C, chunk, fault=None):
    return FAMILY._recurrence(x, dt, A, B, C,
                              {"g": B.shape[2], "chunk": chunk}, fault)


@pytest.mark.parametrize("kernel", [True, False])
@pytest.mark.parametrize("S,chunk", [
    (300, 128),     # two whole chunks and a short third
    (56, 16),       # three whole chunks and half of a fourth
])
def test_ssd_matches_the_recurrence_and_its_gradients(kernel, S, chunk):
    x, dt, A, B, C, w = _ssd_inputs(jax.random.key(S), S)
    with jax.default_matmul_precision("highest"):
        want = _recurrence(x, dt, A, B, C, chunk)
        got = ssd(x, dt, A, B, C, chunk, kernel=kernel, interpret=True)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

        def loss(fn):
            return lambda *a: jnp.sum(fn(*a) * w)

        want_g = jax.grad(loss(lambda *a: _recurrence(*a, chunk)),
                          argnums=(0, 1, 2, 3, 4))(x, dt, A, B, C)
        got_g = jax.grad(loss(lambda *a: ssd(*a, chunk, kernel=kernel,
                                              interpret=True)),
                         argnums=(0, 1, 2, 3, 4))(x, dt, A, B, C)
    for name, g, gw in zip(("x", "dt", "A", "B", "C"), got_g, want_g):
        scale = float(jnp.max(jnp.abs(gw)))
        np.testing.assert_allclose(g, gw, atol=2e-5 * scale, err_msg=name)


@pytest.mark.parametrize("kernel", [True, False])
def test_a_state_crosses_three_chunks(kernel):
    """Input in the first chunk only, a slow decay: what the fourth chunk
    reads came across three chunk boundaries, and a scan that did not carry
    it would read zero there."""
    x, dt, A, B, C, _ = _ssd_inputs(jax.random.key(3), 64, slow=True)
    x = x.at[:, 16:].set(0.0)
    with jax.default_matmul_precision("highest"):
        want = _recurrence(x, dt, A, B, C, 16)
        got = ssd(x, dt, A, B, C, 16, kernel=kernel, interpret=True)
        cut = _recurrence(x, dt, A, B, C, 16, fault="state_per_chunk")
    late = slice(48, 64)
    assert float(jnp.abs(want[:, late]).min()) >= 0.0
    assert float(jnp.abs(want[:, late]).mean()) > 1e-2
    np.testing.assert_allclose(got[:, late], want[:, late], rtol=1e-5,
                               atol=1e-6)
    assert float(jnp.abs(cut[:, late]).max()) == 0.0


def test_ssd_calls_are_counted_by_path():
    x, dt, A, B, C, _ = _ssd_inputs(jax.random.key(4), 32)
    for kernel, name in ((True, "ssd.calls.kernel"),
                         (False, "ssd.calls.reference")):
        got = _counted(lambda: jax.jit(lambda *a: ssd(
            *a, 16, kernel=kernel, interpret=True)).lower(x, dt, A, B, C),
            "ssd.calls.kernel", "ssd.calls.reference")
        assert got == ([1, 0] if kernel else [0, 1])
    with pytest.raises(ValueError, match="groups"):
        ssd(x[:, :, :3], dt[:, :, :3], A[:3], B, C, 16)


# ---- (b) the mixer ------------------------------------------------------------

def _toy():
    return toy_nemotron.config(dtype="float32")


def _mixer_cfg(**kw):
    return TransformerConfig(**dict(_toy()["program"]["model"], **kw))


@pytest.mark.parametrize("impl", ["flash", "dense"])
def test_mamba_mixer_matches_the_reference(impl):
    """Conv with its bias, the step's softplus, A = -exp(A_log), the scan,
    the skip term, the gate and the norm by groups of heads x head_dim /
    groups lanes, on the kernels (`flash`) and on the jax.numpy scan, over
    rows of two whole chunks of 128 and a short third."""
    cfg = _toy()
    z = FAMILY._sizes(cfg)
    mcfg = _mixer_cfg(attention_impl=impl)
    u = jax.random.normal(jax.random.key(5), (2, 264, 64))
    shapes = {k[len("layer_0/"):]: v for k, v in
              FAMILY.param_shapes(cfg).items() if k.startswith("layer_0/")}
    flat = weights.make(5, shapes)
    params = weights.nest(flat)["mamba"]
    ref_p = {k: v for k, v in flat.items() if k.startswith("mamba/")}
    with jax.default_matmul_precision("highest"):
        got = Mamba2Mixer(mcfg).apply({"params": params}, u)
        want = FAMILY._mamba(ref_p, u, z, 1e-5, mm, None)
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
        g_got = jax.grad(lambda p: jnp.sum(jnp.sin(Mamba2Mixer(mcfg).apply(
            {"params": p}, u))))(params)
        g_want = jax.grad(lambda p: jnp.sum(jnp.sin(FAMILY._mamba(
            p, u, z, 1e-5, mm, None))))(ref_p)
    g_got = {"mamba/" + k: v for k, v in weights.flatten(g_got).items()}
    assert set(g_got) == set(g_want)
    for k, v in g_want.items():
        np.testing.assert_allclose(g_got[k], v, rtol=1e-3,
                                   atol=1e-4 * float(jnp.abs(v).max()),
                                   err_msg=k)
    # every mechanism shows: each fault moves the output
    for fault in ("no_decay", "state_per_chunk", "no_d_skip", "no_gate",
                  "norm_all_lanes", "group_map", "dt_no_softplus"):
        bent = FAMILY._mamba(ref_p, u, z, 1e-5, mm, fault)
        assert not np.allclose(bent, want, atol=1e-3), fault


def test_mamba_layers_are_causal_and_take_no_mask():
    cfg = _mixer_cfg()
    u = jax.random.normal(jax.random.key(6), (1, 40, 64))
    p = Mamba2Mixer(cfg).init(jax.random.key(7), u)
    base = Mamba2Mixer(cfg).apply(p, u)
    later = Mamba2Mixer(cfg).apply(p, u.at[:, 30:].add(1.0))
    np.testing.assert_array_equal(base[:, :30], later[:, :30])
    assert not np.allclose(base[:, 30:], later[:, 30:])
    with pytest.raises(NotImplementedError, match="mask"):
        Mamba2Mixer(cfg).apply(p, u, mask=jnp.ones((1, 40), bool))


def test_the_conv_filter_is_shared_and_takes_a_bias():
    g = jax.random.normal(jax.random.key(8), (2, 9, 5))
    taps = jax.random.normal(jax.random.key(9), (5, 4))
    bias = jnp.arange(5.0)
    want = np.zeros(g.shape)
    for t in range(9):
        for j in range(4):
            if t - (3 - j) >= 0:
                want[:, t] += np.asarray(g[:, t - (3 - j)] * taps[:, j])
    np.testing.assert_allclose(tfm.causal_conv(g, taps), want, atol=1e-5)
    np.testing.assert_allclose(tfm.causal_conv(g, taps, bias), want + bias,
                               atol=1e-5)


# ---- (c) one part a layer, no positions, relu squared, the shared expert ------

def test_a_layer_holds_one_part_under_one_norm_and_no_positions():
    cfg = _mixer_cfg()
    model = Transformer(cfg)
    tokens = jnp.zeros((1, 16), jnp.int32)
    params = model.init(jax.random.key(1), tokens)["params"]
    assert "pos_embed" not in params
    kinds = {"M": "mamba", "E": "moe", "*": "attn"}
    for i, part in enumerate(cfg.layer_pattern):
        assert set(params[f"layer_{i}"]) == {"norm", kinds[part]}, i
    x = jax.random.normal(jax.random.key(2), (1, 16, 64))
    for part, module in (("*", tfm.Attention(cfg, name="attn")),
                         ("M", Mamba2Mixer(cfg, name="mamba"))):
        p = Block(cfg, part=part).init(jax.random.key(3), x)["params"]
        got = Block(cfg, part=part).apply({"params": p}, x)
        h = tfm._make_ln(cfg, "norm").apply({"params": p["norm"]}, x)
        np.testing.assert_allclose(got, x + module.apply(
            {"params": p[module.name]}, h), atol=1e-5)
    # the same model with a learned table adds one
    with_table = Transformer(dataclasses.replace(cfg, learned_positions=True))
    assert "pos_embed" in with_table.init(jax.random.key(1), tokens)["params"]


def test_relu_squared():
    x = jnp.linspace(-2.0, 2.0, 9)
    np.testing.assert_allclose(tfm._activation(x, "relu2"),
                               np.maximum(np.asarray(x), 0.0) ** 2)
    with pytest.raises(ValueError, match="relu2"):
        tfm._activation(x, "relu3")


def _moe_cfg(held=None, offset=0, **kw):
    return TransformerConfig(**dict(dict(
        _toy()["program"]["model"], num_experts=32, moe_top_k=6,
        moe_experts_held=held, moe_expert_offset=offset), **kw))


def _moe_params(key):
    """One sparse part's leaves for all 32 experts, as the family draws
    them (the bias wide enough to move picks)."""
    cfg = _toy()
    cfg["n_routed_experts"] = cfg["published"]["n_routed_experts"] = 32
    cfg["init"]["expert_bias_std"] = 0.05
    shapes = {k[len("layer_1/"):]: v for k, v in
              FAMILY.param_shapes(cfg).items() if k.startswith("layer_1/moe")}
    return weights.make(key, shapes)


def _reference_part(flat, x, held, off, fault=None):
    z = {"k": 6, "e": 32, "held": held, "off": off, "scale": 2.5}
    p = dict(flat)
    for name in ("experts_wi", "experts_wo"):
        k = f"moe/{name}/kernel"
        p[k] = flat[k][off:off + held]
    d = x.shape[-1]
    return FAMILY._sparse_ff(p, x.reshape(-1, d), z, mm, fault).reshape(
        x.shape)


def test_shared_expert_is_two_experts_wide_and_the_experts_are_relu_squared():
    flat = _moe_params(11)
    x = jax.random.normal(jax.random.key(12), (2, 24, 64))
    assert flat["moe/shared/wi/kernel"].shape == (64, 64)
    cfg = _moe_cfg()
    with jax.default_matmul_precision("highest"):
        got = MoEMLP(cfg).apply({"params": weights.nest(flat)["moe"]}, x)
        want = _reference_part(flat, x, 32, 0)
    np.testing.assert_allclose(got, want, atol=2e-5)
    for fault in ("no_shared_expert", "relu_not_relu2", "bias_in_weights"):
        assert not np.allclose(got, _reference_part(flat, x, 32, 0, fault),
                               atol=1e-3), fault


@pytest.mark.parametrize("dtype,atol", [
    # float32: sums of the same products in another order
    ("float32", 3e-5),
    # bfloat16: outputs near 1 rounded to 8 bits, each share once and the
    # uncut part once
    ("bfloat16", 5e-2)])
def test_the_sixteen_shares_add_up_with_the_shared_expert_counted_once(
        dtype, atol):
    """Sixteen chips of two experts each, 6 picks a token of 32; router,
    bias and shared expert on every chip: every share holds the shared
    expert's output in full, so the part is the shares' sum less fifteen of
    it."""
    flat = _moe_params(15)
    x = jax.random.normal(jax.random.key(16), (2, 24, 64))
    with jax.default_matmul_precision("highest"):
        uncut = _reference_part(flat, x, 32, 0)
        shared = uncut - _reference_part(flat, x, 32, 0, "no_shared_expert")
        parts = []
        for off in range(0, 32, 2):
            p = dict(flat)
            for name in ("experts_wi", "experts_wo"):
                k = f"moe/{name}/kernel"
                p[k] = flat[k][off:off + 2]
            part = MoEMLP(_moe_cfg(2, off, dtype=dtype)).apply(
                {"params": weights.nest(p)["moe"]}, x).astype(jnp.float32)
            np.testing.assert_allclose(
                part, _reference_part(flat, x, 2, off), atol=atol)
            parts.append(part)
    np.testing.assert_allclose(sum(parts) - 15 * shared, uncut,
                               atol=16 * atol)
    # the shared expert counted sixteen times would stand out: fifteen of it
    assert 15 * float(jnp.abs(shared).mean()) > 16 * atol


# ---- (d) the whole model against the plain reference ---------------------------

def test_loss_gradient_and_three_steps_match_the_plain_reference():
    """The toy of the cell, `M E M E M * E M E`, float32 with float32
    moments and the kernels in the interpreter, so the comparison is tight:
    what is left is the order of float32 sums, and near-ties at the last
    pick."""
    from tensorflowonspark_tpu.parallel import train as train_mod

    cfg = _toy()
    cfg["program"]["optimizer"]["mu_dtype"] = "float32"
    spec = toy_nemotron.spec()
    shapes = FAMILY.param_shapes(cfg)
    batches = traffic.first_batches(spec.traffic, cfg, 11, 3)
    ref = FAMILY.reference(cfg, lambda: weights.make(11, shapes), batches,
                           row_block=2)
    loss_fn, opt = FAMILY.build(cfg)
    params = weights.nest(weights.make(11, shapes))
    (loss, stats), grads = jax.jit(jax.value_and_grad(loss_fn, has_aux=True))(
        params, jnp.asarray(batches[0]), None)
    pairs = 4 * 56 * 2 * 4          # rows x tokens x picks x sparse parts
    assert float(stats["moe.pairs.local"] + stats["moe.pairs.absent"]) == pairs
    assert 0 < float(stats["moe.picks.moved"]) < 0.5 * pairs
    assert float(loss) == pytest.approx(ref["losses"][0], rel=2e-6)
    got = {k: float(jnp.linalg.norm(v.ravel()))
           for k, v in weights.flatten(grads).items()}
    assert set(got) == set(ref["grad_norms"])
    scale = float(np.median(list(ref["grad_norms"].values())))
    for k, want in ref["grad_norms"].items():
        assert got[k] == pytest.approx(want, rel=2e-3, abs=2e-3 * scale), k
    assert not any(got[k] for k in got if k.endswith("expert_bias"))
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
        assert moved[k] == pytest.approx(want, rel=5e-3, abs=5e-3 * scale), k


# ---- (e) the cell's tree ---------------------------------------------------------

def test_the_cells_tree_is_the_familys_and_the_cuts_count():
    cfg = traffic.load("configs", "nemotron-labs-twotower-30b-a3b")
    model = Transformer(TransformerConfig(**cfg["program"]["model"]))
    theirs = weights.flatten(jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"]))
    ours = FAMILY.param_shapes(cfg)
    assert {k: v.shape for k, v in theirs.items()} == \
        {k: s for k, (s, _) in ours.items()}

    def held(prefix):
        return sum(math.prod(v.shape) for k, v in theirs.items()
                   if k.startswith(prefix))

    mamba = (2688 * 10304 + 6144 * 4 + 6144 + 3 * 64 + 4096 + 4096 * 2688
             + 2688)
    sparse = (344064 + 128 + 2 * 2688 * 3712 + 8 * 2 * 2688 * 1856 + 2688)
    attn = 2688 * 4096 * 2 + 2688 * 256 * 2 + 2688
    assert (mamba, sparse, attn) == (38744896, 100125440, 23399040)
    for i, part in enumerate("MEMEM*EME"):
        assert held(f"layer_{i}/") == {"M": mamba, "E": sparse,
                                       "*": attn}[part], i
    assert held("token_embed/") + held("lm_head/") + held("ln_f/") == \
        88083072
    assert sum(math.prod(v.shape) for v in theirs.values()) == 666963456
    assert FAMILY.step_work(cfg, 2)["n_params"] == 666963456
    assert theirs["layer_0/mamba/in_proj/kernel"].shape == (2688, 10304)
    assert theirs["layer_0/mamba/conv_taps"].shape == (6144, 4)
    assert theirs["layer_1/moe/experts_wi/kernel"].shape == (8, 2688, 1856)
    assert theirs["layer_1/moe/shared/wi/kernel"].shape == (2688, 3712)
    assert theirs["layer_5/attn/key/kernel"].shape == (2688, 256)
    # and the constants the family gives the mixers
    a_log = ours["layer_0/mamba/A_log"][1][1]
    np.testing.assert_allclose(np.exp(a_log), np.arange(1, 65), rtol=1e-6)
    dt = np.log1p(np.exp(ours["layer_0/mamba/dt_bias"][1][1]))
    assert dt.min() == pytest.approx(1e-3 * (1e2 ** (0.5 / 64)), rel=1e-4)
    assert dt.max() == pytest.approx(1e-1 / (1e2 ** (0.5 / 64)), rel=1e-4)


# ---- (f) what is refused, and what a step program was built of -------------------

@pytest.mark.parametrize("extra,names", [
    (dict(layer_pattern="MM", ssm_heads=2, ssm_head_dim=8, ssm_state=8),
     "layer_pattern"),
    (dict(learned_positions=False), "learned_positions"),
])
def test_decode_with_each_new_field_raises(extra, names):
    base = dict(vocab_size=64, d_model=32, n_heads=2, n_layers=2, d_ff=64)
    with pytest.raises(NotImplementedError, match=names):
        TransformerConfig(**base, decode=True, **extra)
    TransformerConfig(**base, **extra)           # training: fine


def test_the_pattern_is_checked_and_mamba_refuses_a_split_sequence():
    base = dict(vocab_size=64, d_model=32, n_heads=2, n_layers=2, d_ff=64,
                ssm_heads=2, ssm_head_dim=8, ssm_state=8)
    for bad in ("M", "MX", "MMM"):
        with pytest.raises(ValueError, match="layer_pattern"):
            TransformerConfig(**base, layer_pattern=bad)
    with pytest.raises(ValueError, match="layer_pattern"):
        TransformerConfig(**base, layer_pattern="M*",
                          layer_types=("conv", "full_attention"))
    with pytest.raises(ValueError, match="num_experts"):
        TransformerConfig(**base, layer_pattern="ME")
    with pytest.raises(ValueError, match="ssm_heads"):
        TransformerConfig(**dict(base, ssm_heads=0), layer_pattern="M*")
    for axis in ("ring_attention_axis", "ulysses_axis"):
        with pytest.raises(NotImplementedError, match="Mamba"):
            TransformerConfig(**base, layer_pattern="M*", **{axis: "tp"})


def test_mixers_and_scans_are_counted_once_a_traced_call():
    cfg = _mixer_cfg()
    model = Transformer(cfg)
    tokens = jnp.zeros((1, 40), jnp.int32)
    params = model.init(jax.random.key(1), tokens)
    got = _counted(lambda: jax.jit(lambda p: model.apply(
        p, tokens, mutable=["intermediates"])).lower(params),
        "mixer.calls.mamba", "mixer.calls.attention", "ssd.calls.kernel",
        "ssd.calls.reference")
    assert got == [4, 1, 4, 0]          # `flash`: the kernels
