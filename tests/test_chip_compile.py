"""The kernels compiled by the chip's own compiler, at flagship widths.

Interpret-mode parity tests (test_ops, test_fused_optim, ...) prove the
kernel BODIES; they cannot see what Mosaic refuses — block shapes that
break the (8, 128) tiling rule, VMEM overflow.  The TPU compiler is
installed with jaxlib and compiles for a chip that is described, not
attached (`jax.experimental.topologies`), so these run on the CPU tier
at no chip time.  Nothing executes: a pass here is a compile, never a
chip run.

The train-path kernels must lower (each asserts `tpu_custom_call` in the
compiled text — an interpret-mode lowering would compile too, and prove
nothing).  The two paged serving kernels are recorded as they stand:
refused, strict xfail with the compiler's words (ROADMAP.md Speed S0).

One process may load libtpu, and keeps it until exit: the topology is
described inside a fixture of THIS file (never at import, so every xdist
worker collects the same tests and only the one handed this file loads
the library), and every compile runs in the test's own process.
"""
import functools
import math
import os
import re
import sys

import pytest

import jax
import jax.numpy as jnp

from tensorflowonspark_tpu import quantize
from tensorflowonspark_tpu.ops import (flash_attention, paged_attention,
                                       paged_prefill, quant_matmul)
from tensorflowonspark_tpu.ops.flash_attention import flash_attention_latent
from tensorflowonspark_tpu.ops.fused_optim import adamw_fused

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

LM = chip_smoke.FLAGSHIP_LM_V2
B, S = chip_smoke.FLAGSHIP_BATCH, LM["max_seq_len"]
D, H, N_KV = LM["d_model"], LM["n_heads"], LM["n_kv_heads"]
DH, D_FF, VOCAB = D // H, LM["d_ff"], LM["vocab_size"]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def chip(topo):
    """`chip(shape, dtype)` -> a ShapeDtypeStruct placed on one described
    v5e chip.  A compile for a described device is written to the
    persistent cache but cannot be read back without the chip (the next
    run would warn and recompile), so the cache is off for this module."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    one = SingleDeviceSharding(topo.devices[0])
    yield lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one)
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *args):
    """The chip compiler's verdict on `fn(*args)`: compiled HLO text."""
    return jax.jit(fn).lower(*args).compile().as_text()


KERNELS = ("flash_fwd", "flash_dq", "flash_dkv", "mla_fwd", "mla_dq",
           "mla_dkv", "adamw_fused", "moe_tgmm", "moe_gmm")


def _kernel_calls(text):
    """The `tpu_custom_call` instructions of a compiled text, each by the
    one of `KERNELS` it is named after.  An instruction takes its name from
    the `pallas_call`'s `name=` with the transformations it went through
    around it (`%transpose_jvp_flash_dq__.3`), and a device trace's event
    is called by the whole instruction; one named after none of them is
    returned as it is called."""
    return [next((k for k in KERNELS if k in name), name)
            for name in re.findall(
                r'%([\w.]+) = [^\n]*custom_call_target="tpu_custom_call"',
                text)]


def _kernels(text):
    return set(_kernel_calls(text))


# (batch, sequence, query heads, key/value heads, head size): the private
# flagship's head of 128, and GPT-2 large as `gpt2-large.fed_b8` runs it,
# one resident block of 1024 a head walked in sub-tiles at a head of 64
FLASH_SHAPES = {"flagship": (B, S, H, N_KV, DH),
                "gpt2-large": (8, 1024, 20, 20, 64),
                # an odd head count at 64: the transposed staging
                "odd-heads": (2, 1024, 3, 3, 64),
                # the attention layer of `lfm2-8b-a1b.fed_s8k_b2`: a head
                # of 64 under narrow key/value heads, staged transposed
                "lfm2-8b-a1b": (2, 8192, 32, 8, 64)}


def _qkv(chip, shape="flagship"):
    b, s, h, n_kv, dh = FLASH_SHAPES[shape]
    return (chip((b, s, h, dh), jnp.bfloat16),
            chip((b, s, n_kv, dh), jnp.bfloat16),
            chip((b, s, n_kv, dh), jnp.bfloat16))


@pytest.mark.parametrize("shape", list(FLASH_SHAPES))
def test_flash_forward_lowers(chip, shape):
    fn = functools.partial(flash_attention, causal=True, interpret=False)
    text = _compile(fn, *_qkv(chip, shape))
    assert "tpu_custom_call" in text
    assert _kernels(text) == {"flash_fwd"}


@pytest.mark.parametrize("shape", list(FLASH_SHAPES))
def test_flash_backward_lowers(chip, shape):
    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, interpret=False)
        return jnp.sum(out.astype(jnp.float32))

    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), *_qkv(chip, shape))
    # forward + dq + dk/dv kernels
    assert text.count("tpu_custom_call") >= 3
    assert _kernels(text) == {"flash_fwd", "flash_dq", "flash_dkv"}


def test_flash_with_a_window_lowers(chip):
    """A sliding layer of the sparse-expert cell: 8192 tokens, 32 query
    heads on 4 key/value heads of 128, a window of 1024 (the clamped block
    index maps are what the interpreter cannot judge)."""
    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, window=1024,
                              interpret=False)
        return jnp.sum(out.astype(jnp.float32))

    qkv = (chip((1, 8192, 32, 128), jnp.bfloat16),
           chip((1, 8192, 4, 128), jnp.bfloat16),
           chip((1, 8192, 4, 128), jnp.bfloat16))
    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), *qkv)
    assert _kernels(text) == {"flash_fwd", "flash_dq", "flash_dkv"}


def _latent(chip):
    """The latent mixer of `joyai-llm-flash.fed_s8k_b2`: 32 heads, queries
    of 128 + 64 rotated lanes, ONE rotated key of 64 a token, values of
    128."""
    return (chip((2, 8192, 32, 192), jnp.bfloat16),
            chip((2, 8192, 32, 128), jnp.bfloat16),
            chip((2, 8192, 64), jnp.bfloat16),
            chip((2, 8192, 32, 128), jnp.bfloat16))


def test_latent_flash_forward_lowers(chip):
    fn = functools.partial(flash_attention_latent, interpret=False)
    text = _compile(fn, *_latent(chip))
    assert _kernels(text) == {"mla_fwd"}


def test_latent_flash_backward_lowers_with_one_rotary_key_a_token(chip):
    def loss(q, kn, kr, v):
        out = flash_attention_latent(q, kn, kr, v, interpret=False)
        return jnp.sum(out.astype(jnp.float32))

    text = _compile(jax.grad(loss, argnums=(0, 1, 2, 3)), *_latent(chip))
    assert _kernels(text) == {"mla_fwd", "mla_dq", "mla_dkv"}
    # the rotary key is never copied a head, nor a value padded to 192
    assert not re.search(r"bf16\[(2,8192,32|2,32,8192|64,8192),(192|64)\]"
                         r"[^\n]* (broadcast|pad)\(", text)
    # its gradient comes out of the kernel summed over the heads
    assert re.search(r"bf16\[2,8192,64\]", text)


# (batch, sequence, query heads, key/value heads, head size, model width,
# window): the attention sublayer of `gpt2-large.fed_b8`, and two more
# shapes of two and four heads a lane block, longer than one resident block
SUBLAYERS = {"gpt2-large": (8, 1024, 20, 20, 64, 1280, None),
             "heads-of-64-s4k": (2, 4096, 16, 16, 64, 1024, None),
             "heads-of-32-window": (2, 4096, 32, 32, 32, 1024, 1024)}


@pytest.mark.parametrize("shape", list(SUBLAYERS))
def test_attention_sublayer_stages_no_copy_of_its_heads(chip, shape):
    """Projection -> `flash_attention` -> output projection, forward and
    gradient: the kernels index q, k, v, dO and write the output, dq, dk,
    dv as the projections' own `[B, S, H*D]`, so the compiled sublayer
    holds no copy or transpose the size of any of them (each was one a
    kernel call, a head of 64 padded to 128 lanes, when the kernels took
    `[B, H, S, D]`)."""
    b, s, h, h_kv, dh, d, window = SUBLAYERS[shape]

    def loss(x, wq, wk, wv, wo):
        q = (x @ wq).reshape(b, s, h, dh)
        k = (x @ wk).reshape(b, s, h_kv, dh)
        v = (x @ wv).reshape(b, s, h_kv, dh)
        out = flash_attention(q, k, v, causal=True, window=window,
                              interpret=False)
        y = out.reshape(b, s, h * dh) @ wo
        return jnp.sum(y.astype(jnp.float32) ** 2)

    text = _compile(jax.grad(loss, argnums=(0, 1, 2, 3, 4)),
                    chip((b, s, d), jnp.bfloat16),
                    chip((d, h * dh), jnp.bfloat16),
                    chip((d, h_kv * dh), jnp.bfloat16),
                    chip((d, h_kv * dh), jnp.bfloat16),
                    chip((h * dh, d), jnp.bfloat16))
    assert _kernels(text) == {"flash_fwd", "flash_dq", "flash_dkv"}
    sizes = {b * s * h * dh, b * s * h_kv * dh}
    staged = [
        line.strip()[:120]
        for line in text[text.index("\nENTRY"):].splitlines()
        for m in [re.match(r"\s*(?:ROOT )?%[\w.-]+ = bf16\[([\d,]+)\]\S* "
                           r"(?:copy|transpose)\(", line)]
        if m and math.prod(map(int, m.group(1).split(","))) in sizes]
    assert not staged, staged


# Two dense layers under `remat=True` at the widths of two cells (a small
# vocabulary: the head is not what is looked at), the batch and sequence of
# the cell, and the forward kernel's name
REMAT = {
    "gpt2-large": (dict(
        vocab_size=1024, d_model=1280, n_heads=20, n_layers=2, d_ff=5120,
        max_seq_len=1024, use_bias=True, ln_eps=1e-5), (8, 1024),
        "flash_fwd"),
    "joyai-llm-flash": (dict(
        vocab_size=1024, d_model=2048, n_heads=32, n_layers=2, d_ff=7168,
        max_seq_len=8192, rope=True, rope_theta=32e6, kv_lora_rank=512,
        q_lora_rank=1536, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, rope_interleave=True, activation="silu",
        norm_type="rmsnorm", mlp_style="gated"), (2, 8192), "mla_fwd"),
}


@pytest.mark.parametrize("cell", list(REMAT))
def test_a_rematerialised_block_runs_the_forward_kernel_once(
        chip, cell, monkeypatch):
    """The gradient of a two-layer `remat=True` model: the block's policy
    keeps the kernels' output and row statistics (`remat_block`), so the
    compiled step holds ONE forward kernel a layer, beside one dq and one
    dk/dv, where a block rematerialised whole held two."""
    import tensorflowonspark_tpu.ops as ops
    from tensorflowonspark_tpu.models.transformer import (
        Transformer, TransformerConfig, lm_loss)

    monkeypatch.setattr(ops, "default_interpret", lambda: False)
    model_kw, (b, s), forward = REMAT[cell]
    cfg = TransformerConfig(dtype="bfloat16", attention_impl="flash",
                            remat=True, **model_kw)
    model = Transformer(cfg)
    tokens = chip((b, s), jnp.int32)
    one = tokens.sharding
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
        jax.eval_shape(lambda: model.init(
            jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"]))

    def loss(p, t):
        return lm_loss(model.apply({"params": p}, t), t)

    text = _compile(jax.grad(loss), params, tokens)
    calls = _kernel_calls(text)
    assert calls.count(forward) == cfg.n_layers, calls
    assert len(calls) == 3 * cfg.n_layers, calls       # + one dq, one dk/dv
    # ... and no row statistics a lane outlive a backward kernel call: the
    # saved ones are `f32[B, H, S]`
    assert re.search(rf"f32\[{b},{cfg.n_heads},{s}\]", text)


# (rows for the worst routing, held experts, model width, expert width):
# `mellum2-12b-a2.5b.fed_s8k_b2` (2 x 8192 tokens x 8 picks) and
# `lfm2-8b-a1b.fed_s8k_b2` (2 x 8192 tokens x 4 picks)
EXPERTS = {"mellum2-12b-a2.5b": (131072, 16, 2304, 896),
           "lfm2-8b-a1b": (65536, 8, 2048, 1792)}


@pytest.mark.parametrize("cell", list(EXPERTS))
def test_grouped_matmul_lowers(chip, cell):
    """The expert products of a sparse cell, forward and both gradients."""
    from tensorflowonspark_tpu.ops.grouped_matmul import grouped_matmul

    rows, held, d, f = EXPERTS[cell]

    def loss(rows, w_in, w_out, sizes):
        h = grouped_matmul(rows, w_in, sizes, interpret=False)
        out = grouped_matmul(h, w_out, sizes, interpret=False)
        return jnp.sum(out.astype(jnp.float32))

    text = _compile(jax.grad(loss, argnums=(0, 1, 2)),
                    chip((rows, d), jnp.bfloat16),
                    chip((held, d, f), jnp.bfloat16),
                    chip((held, f, d), jnp.bfloat16),
                    chip((held,), jnp.int32))
    # the first product, the rows' gradient twice, the weights' twice (the
    # sum's own forward is dead code)
    assert text.count("tpu_custom_call") >= 5
    assert _kernels(text) == {"moe_gmm", "moe_tgmm"}


def test_short_conv_mixer_compiles_to_fusions_and_no_convolution(chip):
    """The conv layer's mixer at `lfm2-8b-a1b.fed_s8k_b2`'s shapes, forward
    and gradient: three matmuls a direction and elementwise fusions; no
    kernel, and the taps are not handed to the convolution unit as a
    depthwise filter of 2048 groups."""
    from tensorflowonspark_tpu.models.transformer import (
        ShortConv, TransformerConfig)

    cfg = TransformerConfig(d_model=2048, n_heads=32, conv_kernel=3)
    mixer = ShortConv(cfg)
    x = chip((2, 8192, 2048), jnp.bfloat16)
    one = x.sharding
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
        jax.eval_shape(lambda: mixer.init(
            jax.random.key(0), jnp.zeros((1, 8, 2048)))["params"]))

    def loss(p, x_):
        return jnp.sum(mixer.apply({"params": p}, x_).astype(jnp.float32)
                       ** 2)

    text = _compile(jax.grad(loss, argnums=(0, 1)), params, x)
    assert "tpu_custom_call" not in text
    assert "feature_group_count=2048" not in text


def _state_beside(opt, params):
    """The shapes of `opt.init(params)`, placed on the one chip that
    `params` (ShapeDtypeStructs of `chip`) are placed on."""
    one = next(iter(params.values())).sharding
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one),
        jax.eval_shape(opt.init, params))


def test_adamw_fused_apply_lowers(chip):
    """The flagship's three leaf classes: a wide MLP kernel, the
    embedding table, and a norm scale vector (pads to one sublane
    tile)."""
    opt = adamw_fused(3e-4, mu_dtype=jnp.bfloat16, clip_norm=1.0,
                      weight_decay=0.1, interpret=False)
    shapes = {"mlp": (D, D_FF), "embed": (VOCAB, D), "scale": (D,)}
    params = {k: chip(s, jnp.float32) for k, s in shapes.items()}
    text = _compile(opt.apply, params, _state_beside(opt, params), params)
    assert text.count("tpu_custom_call") >= len(shapes)
    assert _kernels(text) == {"adamw_fused"}


def _relayouts(text, shapes):
    """The `reshape` and `copy` instructions of a compiled text's ENTRY
    whose result holds as many elements as one of `shapes`: on this chip
    each is a pass over the leaf (a `bitcast` is the view that costs
    nothing).  Results as the text prints them, `f32[51200,128]`."""
    counts = {math.prod(s) for s in shapes}
    found = []
    for result, dims, kind in re.findall(
            r"= (\w+\[([\d,]*)\])\S* (reshape|copy)\(",
            text[text.index("ENTRY"):]):
        if math.prod(int(d) for d in dims.split(",") if d) in counts:
            found.append((kind, result))
    return found


def _donated_apply(opt, shardings=None):
    """`apply` as `make_train_step` runs it: parameters and optimizer
    state donated together, parameters first in and out, so that every
    donated buffer is the buffer of the output the kernel aliases it to."""
    def apply(grads, params_and_state):
        params, state = params_and_state
        return opt.apply(grads, state, params, shardings=shardings)
    return jax.jit(apply, donate_argnums=(1,))


# a wide MLP kernel, the embedding table (its rows no multiple of a
# block's: GPT-2 large's), the experts a chip of `lfm2-8b-a1b` holds
DIRECT_LEAVES = {"mlp": (D, D_FF), "embed": (50257, 1280),
                 "experts": (8, 2048, 1792)}


def test_adamw_fused_apply_moves_no_leaf(chip):
    """The kernel blocks a leaf in the leaf's own layout and updates the
    donated state in place: no `reshape` and no `copy` of a direct leaf is
    left in the compiled ENTRY (the `[n, 128]` packing was 47 ms of the 462
    ms GPT-2 step on the chip, and never showed on the CPU).  The packed
    leaves, a scale vector among them, may keep theirs."""
    opt = adamw_fused(3e-4, mu_dtype=jnp.bfloat16, clip_norm=1.0,
                      weight_decay=0.1, interpret=False)
    shapes = dict(DIRECT_LEAVES, scale=(D,))
    params = {k: chip(s, jnp.float32) for k, s in shapes.items()}
    text = _donated_apply(opt).lower(
        params, (params, _state_beside(opt, params))).compile().as_text()
    assert _kernels(text) == {"adamw_fused"}
    assert _relayouts(text, DIRECT_LEAVES.values()) == []
    # what the reader has to see, as the parent's ENTRY held it
    packing = ("ENTRY %main {\n  %reshape.7 = f32[51200,128]{1,0:T(8,128)} "
               "reshape(%copy.3)\n")
    assert _relayouts(packing, [(1280, 5120)]) == [
        ("reshape", "f32[51200,128]")]


def test_adamw_fused_apply_lowers_under_a_mesh(topo, chip):
    """Over a mesh each leaf's kernel must run under `shard_map` by its
    param's spec: handed to GSPMD, the chip's compiler refuses it ("Mosaic
    kernels cannot be automatically partitioned") — which the interpreter
    on a virtual CPU mesh never showed, and only `chip_smoke.py --chips 4`
    on four chips did.  One leaf sharded over an axis, one replicated."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("dp", "fsdp"))
    specs = {"mlp": P("fsdp", None), "scale": P()}
    shapes = {"mlp": (D, D_FF), "scale": (D,)}
    shardings = {k: NamedSharding(mesh, spec) for k, spec in specs.items()}
    params = {k: jax.ShapeDtypeStruct(shapes[k], jnp.float32,
                                      sharding=shardings[k])
              for k in shapes}
    opt = adamw_fused(3e-4, mu_dtype=jnp.bfloat16, clip_norm=1.0,
                      weight_decay=0.1, interpret=False)
    state = jax.eval_shape(opt.init, params)
    state = state._replace(
        count=jax.ShapeDtypeStruct((), state.count.dtype,
                                   sharding=NamedSharding(mesh, P())),
        **{m: {k: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                       sharding=shardings[k])
               for k, x in getattr(state, m).items()}
           for m in ("mu", "nu")})

    text = _donated_apply(opt, shardings).lower(
        params, (params, state)).compile().as_text()
    assert text.count("tpu_custom_call") >= len(shapes)
    # the shape test sees the LOCAL shard `[D/2, D_FF]`: blocked as it lies
    assert _relayouts(text, [(D // 2, D_FF), (D, D_FF)]) == []


@pytest.mark.parametrize("rows", [8, 16])
def test_int8_quant_matmul_lowers(chip, rows):
    w = {"q": chip((D, D_FF), jnp.int8),
         "scale": chip((1, D_FF), jnp.float32)}
    assert quantize.is_quantized_leaf(w)
    fn = functools.partial(quant_matmul, interpret=False)
    text = _compile(fn, chip((rows, D), jnp.bfloat16), w)
    assert "tpu_custom_call" in text


# --- the paged serving kernels: refused today ----------------------------
#
# Both block the pool / chunk `(1, page, 1, Dh)` over a `[.., .., n_kv,
# Dh]` operand: a second-minor block of 1 over n_kv=8 is neither a
# multiple of 8 nor the whole dim.  strict: the PR that redoes the
# blocking turns these into plain passing tests, and cannot forget to.

# The serving shapes these two record: steady-state paged decode over
# long rows, and one chunk of chunked prefill.
_DEC = dict(n_slots=16, page_size=64, max_seq=4096)
_PRE = dict(n_slots=4, page_size=64, max_seq=4096, chunk=256)


def _pool(chip, n_slots, page, max_seq):
    max_pages = max_seq // page
    kv_pages = n_slots * max_pages + 1            # + the sink page
    return (chip((kv_pages, page, N_KV, DH), jnp.bfloat16),
            chip((kv_pages, page, N_KV, DH), jnp.bfloat16),
            chip((n_slots, max_pages), jnp.int32),
            chip((n_slots,), jnp.int32))


@pytest.mark.xfail(
    strict=True, raises=ValueError,
    reason="The Pallas TPU lowering currently requires that the last two "
           "dimensions of your block shape are divisible by 8 and 128 "
           "respectively, or be equal to the respective dimensions of the "
           "overall array. Block spec for args[3] in pallas_call "
           "_decode_kernel at ops/paged_attention.py:85 has block shape "
           "(1, 64, 1, 128), array shape (1025, 64, 8, 128)")
def test_paged_attention_decode_lowers(chip):
    pk, pv, table, lengths = _pool(chip, _DEC["n_slots"],
                                   _DEC["page_size"], _DEC["max_seq"])
    fn = functools.partial(paged_attention, interpret=False)
    text = _compile(fn, chip((_DEC["n_slots"], 1, H, DH), jnp.bfloat16),
                    pk, pv, table, lengths)
    assert "tpu_custom_call" in text


@pytest.mark.xfail(
    strict=True, raises=ValueError,
    reason="The Pallas TPU lowering currently requires that the last two "
           "dimensions of your block shape are divisible by 8 and 128 "
           "respectively, or be equal to the respective dimensions of the "
           "overall array. Block spec for args[3] in pallas_call "
           "_prefill_read_kernel at ops/paged_prefill.py:235 has block "
           "shape (1, 256, 1, 128), array shape (4, 256, 8, 128)")
def test_paged_prefill_lowers(chip):
    n, chunk = _PRE["n_slots"], _PRE["chunk"]
    pk, pv, table, starts = _pool(chip, n, _PRE["page_size"],
                                  _PRE["max_seq"])
    fn = functools.partial(paged_prefill, interpret=False)

    def run(q, k, v, pk, pv, table, starts):
        out, pools = fn(q, k, v, pk, pv, table, starts)
        return out, pools[0], pools[1]

    text = _compile(run, chip((n, chunk, H, DH), jnp.bfloat16),
                    chip((n, chunk, N_KV, DH), jnp.bfloat16),
                    chip((n, chunk, N_KV, DH), jnp.bfloat16),
                    pk, pv, table, starts)
    assert "tpu_custom_call" in text
