"""Family `kimi_linear`: a hybrid decoder whose mixers are Kimi Delta
Attention (KDA: a gated delta rule with a decay a key channel) in three
layers of four and latent attention (MLA) with no positions and no query
low-rank in the fourth, each followed by a feed-forward part: a leading
dense SwiGLU, then sparse layers (a shared expert beside sigmoid top-k
routed SwiGLU experts under a selection bias, the routed sum scaled); an
untied head; one chip's share of an expert-parallel deployment.

Read from the configuration file's published keys (`hidden_size`,
`num_attention_heads`, `kv_lora_rank`, `qk_nope_head_dim`,
`qk_rope_head_dim`, `v_head_dim`, `intermediate_size`,
`moe_intermediate_size`, `num_experts_per_token`, `num_shared_experts`,
`routed_scaling_factor`, `first_k_dense_replace`, `rms_norm_eps`,
`linear_attn_config`) and from its cut: `num_hidden_layers` (the layers
held, from `deployment.this_chip.first_layer` on), `num_experts` (the
experts held, out of `published.num_experts`, which stays the router's
width), `vocab_size` (the rows of the table and of the head held).

- `param_shapes`: the parameters and their initialisers, under the paths of
  the program's own tree (`layer_0/kda/f_b/kernel`, `layer_3/attn/query`);
- `build`: the program under test: `models/transformer.Transformer`,
  `ops/xent.fused_unembed_xent` over the head, `optim.make_optimizer`, the
  routing counters of `moe_stats`;
- `step_work`: operations and bytes one step REQUIRES, from shapes alone;
- `reference`: the plain float32 `jax.numpy` forward, backward and AdamW,
  which imports nothing of the program: KDA as the recurrence itself, a
  scan over positions checkpointed a chunk at a time (not the chunked
  algorithm), MLA a head at a time over the explicit `[k_nope | k_r]` key,
  every held expert computed densely for every token and masked by the
  picks, the shared expert once.
"""
import math

import harness  # the benchmark's own: finds a family's file by name

# the latent family's `route`, `_sparse_ff`, `rope_tables` and
# `_rotate_pairs`, and through it the sparse family's `_rms_norm`,
# `visible_pairs` and the dense family's `_matmul` (f32 | bf16 | fp8) and
# AdamW constants
mla = harness.load_module("families", "mla_moe")
moe = mla.moe
lm = moe.lm

# what `reference(fault=...)` can plant (tests and `tests/faults.py`;
# `program.fault`, which no file sets, plants one in a whole run)
FAULTS = ("decay_per_head", "no_delta", "no_l2_norm", "silu_gate",
          "rotated_key", "no_shared_expert")

REF_CHUNK = 64      # positions the reference's recurrence checkpoints at once


def _sizes(cfg):
    lin = cfg["linear_attn_config"]
    n = cfg["num_hidden_layers"]
    first = cfg["deployment"]["this_chip"]["first_layer"]
    kinds = ["kda" if i + 1 in lin["kda_layers"] else "mla"
             for i in range(first, first + n)]
    return {"d": cfg["hidden_size"], "h": cfg["num_attention_heads"],
            "kvr": cfg["kv_lora_rank"], "dn": cfg["qk_nope_head_dim"],
            "dr": cfg["qk_rope_head_dim"], "dv": cfg["v_head_dim"],
            "ff": cfg["intermediate_size"],
            "f": cfg["moe_intermediate_size"], "v": cfg["vocab_size"],
            "n": n, "kinds": kinds,
            "dense": max(cfg["first_k_dense_replace"] - first, 0),
            "held": cfg["num_experts"],
            "e": cfg["published"]["num_experts"],
            "k": cfg["num_experts_per_token"],
            "shared": cfg["num_shared_experts"],
            "scale": cfg["routed_scaling_factor"],
            "off": cfg["deployment"]["this_chip"]["expert_offset"],
            "kh": lin["num_heads"], "kp": lin["head_dim"],
            "inner": lin["num_heads"] * lin["head_dim"],
            "taps": lin["short_conv_kernel_size"]}


def dt_bias(cfg):
    """The inverse softplus of a step a key channel, spread log-evenly over
    [dt_min, dt_max] (channel i at the (i + 0.5) / channels point), floored
    at dt_floor (the file's `assumed.a_log_dt_bias`): a float32 array,
    which `weights.make` takes as a constant's value."""
    import numpy as np

    z, init = _sizes(cfg), cfg["init"]
    n = z["inner"]
    lo, hi = math.log(init["dt_min"]), math.log(init["dt_max"])
    dt = np.maximum(np.exp(lo + (np.arange(n) + 0.5) / n * (hi - lo)),
                    init["dt_floor"])
    return (dt + np.log(-np.expm1(-dt))).astype(np.float32)


def a_log(cfg):
    """log(A), A a head on the grid over [1, 16] (head h at the (h + 0.5)
    / heads point)."""
    import numpy as np

    heads = _sizes(cfg)["kh"]
    return np.log(1.0 + 15.0 * (np.arange(heads) + 0.5) / heads).astype(
        np.float32)


def _layer_shapes(z, i):
    d, inner, kp = z["d"], z["inner"], z["kp"]
    out = {"ln1/scale": (d,), "ln2/scale": (d,)}
    if z["kinds"][i] == "kda":
        for n in "qkv":
            out[f"kda/{n}_proj/kernel"] = (d, inner)
            out[f"kda/{n}_conv"] = (inner, z["taps"])
        out.update({"kda/b_proj/kernel": (d, z["kh"]),
                    "kda/f_a/kernel": (d, kp), "kda/f_b/kernel": (kp, inner),
                    "kda/dt_bias": (inner,), "kda/A_log": (z["kh"],),
                    "kda/g_a/kernel": (d, kp), "kda/g_b/kernel": (kp, inner),
                    "kda/g_b/bias": (inner,), "kda/out_norm": (kp,),
                    "kda/o_proj/kernel": (inner, d)})
    else:
        h = z["h"]
        out.update({"attn/query/kernel": (d, h * (z["dn"] + z["dr"])),
                    "attn/kv_a/kernel": (d, z["kvr"] + z["dr"]),
                    "attn/kv_a_norm/scale": (z["kvr"],),
                    "attn/kv_b/kernel": (z["kvr"], h * (z["dn"] + z["dv"])),
                    "attn/out/kernel": (h * z["dv"], d)})
    if i < z["dense"]:
        out.update({"mlp/wi_gate/kernel": (d, z["ff"]),
                    "mlp/wi_up/kernel": (d, z["ff"]),
                    "mlp/wo/kernel": (z["ff"], d)})
        return out
    f, fs = z["f"], z["shared"] * z["f"]
    out.update({"moe/router/kernel": (d, z["e"]),
                "moe/expert_bias": (z["e"],),
                "moe/experts_wi/kernel": (z["held"], d, f),
                "moe/experts_up/kernel": (z["held"], d, f),
                "moe/experts_wo/kernel": (z["held"], f, d),
                "moe/shared/wi_gate/kernel": (d, fs),
                "moe/shared/wi_up/kernel": (d, fs),
                "moe/shared/wo/kernel": (fs, d)})
    return out


def param_shapes(cfg):
    z, init = _sizes(cfg), cfg["init"]
    conv = ("normal", init["conv_std"])
    inits = {"kernel": ("normal", init["kernel_std"]),
             "expert_bias": ("normal", init["expert_bias_std"]),
             "bias": ("normal", init["gate_bias_std"]),
             "q_conv": conv, "k_conv": conv, "v_conv": conv,
             "dt_bias": ("const", dt_bias(cfg)),
             "A_log": ("const", a_log(cfg)),
             "out_norm": ("const", 1.0), "scale": ("const", 1.0)}
    out = {"token_embed/embedding": ((z["v"], z["d"]), (
               "normal", init["embedding_std"])),
           "ln_f/scale": ((z["d"],), inits["scale"]),
           "lm_head/kernel": ((z["d"], z["v"]), inits["kernel"])}
    for i in range(z["n"]):
        for name, shape in _layer_shapes(z, i).items():
            out[f"layer_{i}/{name}"] = (shape, inits[name.rsplit("/", 1)[-1]])
    return out


# ------------------------------------------------------------ program ----

def build(cfg):
    """`(loss_fn, optimizer)` of the program under test.  The loss takes
    the hidden states and fuses the untied head into the cross entropy.
    Where the file expects kernels and the backend is the chip, a program
    whose KDA mixers did not take the KDA kernels, or whose latent mixer
    did not take the latent flash kernels, is refused when the step is
    traced."""
    import jax
    import jax.numpy as jnp

    from tensorflowonspark_tpu import trace
    from tensorflowonspark_tpu.models.transformer import (
        MOE_COUNTERS, Transformer, TransformerConfig, moe_stats)
    from tensorflowonspark_tpu.ops.xent import fused_unembed_xent
    from tensorflowonspark_tpu.optim import make_optimizer

    mcfg = TransformerConfig(**cfg["program"]["model"])
    model = Transformer(mcfg)
    on_chip = (cfg["program"].get("expect_kernels")
               and jax.default_backend() == "tpu")

    def loss_fn(p, batch, rng):
        hidden, sown = model.apply({"params": p}, batch[:, :-1],
                                   return_hidden=True,
                                   mutable=["intermediates"])
        if on_chip:
            seen = trace.counters().snapshot()
            for name, what in (("kda.calls.kernel", "KDA kernel"),
                               ("flash.calls.latent", "latent flash kernel")):
                if not seen.get(name):
                    raise RuntimeError(f"the mixers took no {what}: they "
                                       "fell to the jax.numpy path")
        kernel = p["lm_head"]["kernel"].astype(jnp.dtype(mcfg.dtype))
        loss = fused_unembed_xent(hidden, kernel, batch[:, 1:],
                                  cfg["program"]["xent_chunk"])
        return loss, moe_stats(sown["intermediates"])

    loss_fn.counters = MOE_COUNTERS     # the step object counts them

    o = dict(cfg["program"]["optimizer"])
    opt, _ = make_optimizer(o.pop("name"), **o)
    return loss_fn, opt


# --------------------------------------------------------------- work ----

KDA_CHUNK = 128     # `ops/kda.kda`'s chunk, the program's


def kda_work(cfg, batch):
    """`{"flops", "bytes"}` of the KDA kernels of one step (`ops/kda.py`),
    forward and backward, from shapes: the chunked algorithm's products
    over whole chunks of C, a triangle of C^2 / 2 pairs where a product is
    triangular, and no recomputation.  A chunk of a head, forward: the two
    pair matrices, the triangular solve over 2D lanes, `Aqk u` (five
    triangles of D), `W S`, `q S` and the state's update (three C x D x D);
    backward: twice each of those.  The bytes the kernels read and write:
    q, k, beta k, beta v, the output and the gradients in bfloat16, the
    decay a key channel and its gradient in float32, the chunk states in
    float32 written once and read once."""
    z = _sizes(cfg)
    seq = cfg["program"]["seq_len"]
    C, D, H = KDA_CHUNK, z["kp"], z["kh"]
    n_kda = z["kinds"].count("kda")
    chunks = batch * -(-seq // C) * H
    tri = C * C // 2
    fwd = 3 * C * D * D + 5 * tri * D
    act, tokens = 2, chunks * C
    seqs = tokens * D
    states = chunks * D * D * 4
    fwd_bytes = 4 * seqs * act + seqs * 4 + seqs * act + states
    bwd_bytes = (5 * seqs * act + seqs * 4 + states
                 + 4 * seqs * act + seqs * 4)
    return {"flops": n_kda * chunks * 2 * 3 * fwd,
            "bytes": n_kda * (fwd_bytes + bwd_bytes)}


def step_work(cfg, batch):
    """What one step of `batch` rows requires, from shapes: no embedding
    gather, no recomputation, forward and backward three times the
    forward's multiply-adds for the products; the KDA recurrence as
    `kda_work` counts it.  Latent attention counts the causal pairs at
    `(nope + r) + v` multiply-adds a head (scores over 192, values over
    128); the experts count the EXPECTED local pairs, `T x k x held / E` a
    sparse layer (uniform routing: `moe_local_pairs_pct.joy` says how near
    the run came).  The convolutions' taps, the gates and the norms are
    left out."""
    z = _sizes(cfg)
    d, h, f, inner, kp = z["d"], z["h"], z["f"], z["inner"], z["kp"]
    dq, dv = z["dn"] + z["dr"], z["dv"]
    seq = cfg["program"]["seq_len"]
    tokens = batch * seq
    n_kda, n_mla = z["kinds"].count("kda"), z["kinds"].count("mla")
    n_sparse = z["n"] - z["dense"]
    pairs = n_mla * moe.visible_pairs(seq) * batch
    local = tokens * z["k"] * z["held"] // z["e"]        # a sparse layer
    kda = 3 * d * inner + d * z["kh"] + 2 * (d * kp + kp * inner) \
        + inner * d
    mla_proj = (d * h * dq + d * (z["kvr"] + z["dr"])
                + z["kvr"] * h * (z["dn"] + dv) + h * dv * d)
    proj = (n_kda * kda + n_mla * mla_proj + z["dense"] * 3 * d * z["ff"]
            + n_sparse * (d * z["e"] + 3 * d * z["shared"] * f)
            + d * z["v"])
    attn = 6 * pairs * h * (dq + dv)        # 3 x (QK^T + PV), 2 a mult-add
    gmm = n_sparse * local * 3 * 6 * d * f  # gate, up, down; fwd + 2 bwd
    rec = kda_work(cfg, batch)
    n_params = sum(math.prod(shape) for shape, _ in
                   param_shapes(cfg).values())
    act = 2                                 # bytes of an activation (bf16)
    return {
        "flops": 6 * proj * tokens + attn + gmm + rec["flops"],
        "n_params": n_params,
        "visible_pairs": pairs, "local_pairs": n_sparse * local,
        # as `mla_moe.step_work`: three tensors of the query's width, nine
        # of a head's 128, three of the one shared key a token
        "flash": {"flops": attn,
                  "bytes": n_mla * tokens * (3 * h * dq + 9 * h * dv
                                             + 3 * z["dr"]) * act},
        "moe_gmm": {"flops": gmm,
                    "bytes": n_sparse * 3 * 3 * (z["held"] * d * f
                                                 + local * (d + f)) * act},
        "kda": rec,
        "adamw": {"bytes": n_params * lm._adamw_bytes(cfg)},
    }


# ---------------------------------------------------------- reference ----

def _conv(x, w):
    """Causal depthwise filter of `w` [C, taps] over [B, S, C], the last
    tap on the position itself, no bias."""
    import jax.numpy as jnp

    s, taps = x.shape[1], w.shape[1]
    out = jnp.zeros_like(x)
    for j in range(taps):
        back = taps - 1 - j
        out = out + jnp.concatenate(
            [jnp.zeros_like(x[:, :back]), x[:, :s - back]], axis=1) * w[:, j]
    return out


def _delta(q, k, v, g, beta, fault):
    """The gated delta rule itself, position by position: `q`, `k`, `v`,
    `g` [r, S, H, P], `beta` [r, S, H] -> o [r, S, H, P] with
    `S_t = Diag(exp(g_t)) S_{t-1}`, `S_t += beta_t k_t (v_t - S_t^T k_t)^T`,
    `o_t = S_t^T q_t / sqrt(P)`.  A scan over positions inside a scan over
    chunks of REF_CHUNK, each chunk checkpointed: the backward pass keeps a
    state a chunk and computes a chunk's steps again.  Products are summed
    elementwise: no matmul, so float32 on any backend."""
    import jax
    import jax.numpy as jnp

    r, s, heads, P = q.shape
    L = REF_CHUNK
    pad = -s % L
    if pad:
        q, k, v, g, beta = (
            jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
            for t in (q, k, v, g, beta))

    def step(st, t):
        qt, kt, vt, gt, bt = t               # [r, H, P] x 4, [r, H]
        st = jnp.exp(gt)[..., None] * st
        if fault == "no_delta":              # the write without correction
            new = vt
        else:
            new = vt - jnp.sum(kt[..., :, None] * st, axis=-2)
        st = st + (bt[..., None] * kt)[..., :, None] * new[..., None, :]
        return st, jnp.sum(qt[..., :, None] * st, axis=-2) / math.sqrt(P)

    @jax.checkpoint
    def chunk(st, t):
        return jax.lax.scan(step, st, t)

    def by_chunk(t):                         # [r, S, ...] -> [n, L, r, ...]
        t = jnp.moveaxis(t, 1, 0)
        return t.reshape((-1, L) + t.shape[1:])

    st0 = jnp.zeros((r, heads, P, P), jnp.float32)
    _, o = jax.lax.scan(chunk, st0, tuple(by_chunk(t)
                                          for t in (q, k, v, g, beta)))
    return jnp.moveaxis(o.reshape((-1,) + o.shape[2:]), 0, 1)[:, :s]


def _kda(p, u, z, eps, mm, fault):
    """[B, S, d] -> [B, S, d]: the KDA mixer, as the file's `assumed.kda`
    writes it."""
    import jax
    import jax.numpy as jnp

    b, s, _ = u.shape
    heads, P = z["kh"], z["kp"]

    def per_head(t):
        return t.reshape(b, s, heads, P)

    def unit(t):
        if fault == "no_l2_norm":
            return t
        return t / jnp.sqrt(jnp.sum(t * t, axis=-1, keepdims=True) + 1e-6)

    q, k, v = (per_head(jax.nn.silu(_conv(mm(u, p[f"kda/{n}_proj/kernel"]),
                                          p[f"kda/{n}_conv"])))
               for n in "qkv")
    g = -jnp.exp(p["kda/A_log"])[:, None] * jax.nn.softplus(per_head(
        mm(mm(u, p["kda/f_a/kernel"]), p["kda/f_b/kernel"]))
        + p["kda/dt_bias"].reshape(heads, P))
    if fault == "decay_per_head":            # one decay for all channels
        g = jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)
    beta = jax.nn.sigmoid(mm(u, p["kda/b_proj/kernel"]))
    o = _delta(unit(q), unit(k), v, g, beta, fault)
    gate = mm(mm(u, p["kda/g_a/kernel"]), p["kda/g_b/kernel"]) \
        + p["kda/g_b/bias"]
    gate = jax.nn.silu(gate) if fault == "silu_gate" \
        else jax.nn.sigmoid(gate)
    o = o / jnp.sqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps) \
        * p["kda/out_norm"]
    return mm(o.reshape(b, s, heads * P) * gate, p["kda/o_proj/kernel"])


def _attention(p, u, z, eps, cfg, mm, fault):
    """[B, S, d] -> [B, S, d]: MLA with no query low-rank and no positions,
    an RMSNorm on the key/value latent, one shared key of `dr` lanes a
    token for all heads; causal softmax a head at a time over the explicit
    `[k_nope | k_r]` key, so the [S, S] scores of one head are all that is
    live (and recomputed in the backward pass)."""
    import jax
    import jax.numpy as jnp

    b, s, _ = u.shape
    h, dn, dr, dv, rank = z["h"], z["dn"], z["dr"], z["dv"], z["kvr"]
    q = mm(u, p["attn/query/kernel"]).reshape(b, s, h, dn + dr)
    kv_a = mm(u, p["attn/kv_a/kernel"])
    ckv = moe._rms_norm(kv_a[..., :rank], p["attn/kv_a_norm/scale"], eps)
    kv = mm(ckv, p["attn/kv_b/kernel"]).reshape(b, s, h, dn + dv)
    qn, qr, kn, v = q[..., :dn], q[..., dn:], kv[..., :dn], kv[..., dn:]
    kr = jnp.broadcast_to(kv_a[:, :, None, rank:], (b, s, h, dr))
    if fault == "rotated_key":               # rotary where there is none
        cos, sin = mla.rope_tables(cfg, s)
        qr, kr = mla._rotate_pairs(qr, cos, sin), mla._rotate_pairs(
            kr, cos, sin)
    i = jnp.arange(s)
    seen = i[:, None] >= i[None, :]

    @jax.checkpoint
    def head(args):
        qh, kh, vh = args
        logits = mm(qh, kh.T) / math.sqrt(dn + dr)
        probs = jax.nn.softmax(jnp.where(seen, logits, -1e30), axis=-1)
        return mm(probs, vh)

    def heads_of(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, s, x.shape[-1])

    o = jax.lax.map(head, (heads_of(jnp.concatenate([qn, qr], -1)),
                           heads_of(jnp.concatenate([kn, kr], -1)),
                           heads_of(v)))
    o = o.reshape(b, h, s, dv).transpose(0, 2, 1, 3).reshape(b, s, h * dv)
    return mm(o, p["attn/out/kernel"])


def _block(p, x, z, eps, cfg, mm, fault=None):
    """One pre-norm layer on [B, S, d] float32; what the leaves of `p` are
    says which mixer and which feed-forward it has."""
    import jax

    b, s, d = x.shape
    u = moe._rms_norm(x, p["ln1/scale"], eps)
    x = x + (_kda(p, u, z, eps, mm, fault) if "kda/o_proj/kernel" in p
             else _attention(p, u, z, eps, cfg, mm, fault))
    hn = moe._rms_norm(x, p["ln2/scale"], eps)
    if "mlp/wo/kernel" in p:
        return x + mm(jax.nn.silu(mm(hn, p["mlp/wi_gate/kernel"]))
                      * mm(hn, p["mlp/wi_up/kernel"]), p["mlp/wo/kernel"])
    return x + mla._sparse_ff(p, hn.reshape(b * s, d), z, mm,
                              fault).reshape(b, s, d)


def _head_loss(p, x, targets, denom, eps, mm):
    """Sum of the rows' next-token cross entropies over `denom`."""
    import jax
    import jax.numpy as jnp

    logits = mm(moe._rms_norm(x, p["ln_f/scale"], eps), p["lm_head/kernel"])
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.sum(logz - gold) / denom


def reference(cfg, make_weights, batches, precision="f32", devices=None,
              row_block=1, rows=None, keep_grads=False, fault=None):
    """Follow `len(batches)` AdamW steps in plain float32, as
    `moe_lm.reference` does: layer by layer, in blocks of `row_block` rows,
    each layer updated as soon as its gradient is whole; the head when the
    loss's pass has given it, the table last.  Returns `{"losses",
    "grad_norms", "update_norms"}`.  `precision`: the matmuls' (`f32`;
    `bf16` a look; `fp8` the control); the router's logits stay float32 in
    each, and the recurrence has no matmul.  `rows` plants a fault: only
    the first `rows` rows of each batch; `fault` one of `FAULTS`."""
    import functools

    import jax
    import jax.numpy as jnp

    if keep_grads:
        raise ValueError("no full gradient tree ever exists here")
    fault = fault or cfg["program"].get("fault")
    if fault not in (None,) + FAULTS:
        raise ValueError(f"fault {fault!r} not in {FAULTS}")
    dev = (devices or [jax.devices()[0]])[0]
    z = _sizes(cfg)
    n_layer, eps = z["n"], cfg["rms_norm_eps"]
    o = cfg["program"]["optimizer"]
    lr, b1, b2 = o["learning_rate"], o.get("b1", 0.9), o.get("b2", 0.999)
    mm = lm._matmul(precision)
    seq = batches[0].shape[1] - 1

    def split(flat):
        groups = {"table": {"token_embed/embedding":
                            flat["token_embed/embedding"]},
                  "head": {k: flat[k] for k in ("ln_f/scale",
                                                "lm_head/kernel")}}
        for i in range(n_layer):
            pre = f"layer_{i}/"
            groups[i] = {k[len(pre):]: v for k, v in flat.items()
                         if k.startswith(pre)}
        return {g: jax.device_put(t, dev) for g, t in groups.items()}

    def paths(g, tree):
        pre = f"layer_{g}/" if isinstance(g, int) else ""
        return {k: pre + k for k in tree}

    block = functools.partial(_block, z=z, eps=eps, cfg=cfg, mm=mm,
                              fault=fault)
    block_f = jax.jit(block)            # one program a layer's leaf set

    @jax.jit
    def block_b(p, x, dy):
        return jax.vjp(block, p, x)[1](dy)

    @jax.jit
    def embed_b(g, tokens, dx):
        return {"token_embed/embedding":
                g["token_embed/embedding"].at[tokens].add(dx)}

    @functools.partial(jax.jit, static_argnums=(3,))
    def head_vg(p, x, targets, denom):
        return jax.value_and_grad(
            lambda p_, x_: _head_loss(p_, x_, targets, denom, eps, mm),
            argnums=(0, 1))(p, x)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def add(a, b):
        return jax.tree_util.tree_map(jnp.add, a, b)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def adam(p, mu, nu, g, t):
        c1, c2 = 1.0 - b1 ** t, 1.0 - b2 ** t
        mu = jax.tree_util.tree_map(
            lambda m, g_: b1 * m + (1.0 - b1) * g_, mu, g)
        nu = jax.tree_util.tree_map(
            lambda n, g_: b2 * n + (1.0 - b2) * g_ * g_, nu, g)
        p = jax.tree_util.tree_map(
            lambda p_, m, n: p_ - lr * (m / c1) / (
                jnp.sqrt(n / c2) + lm.ADAM_EPS), p, mu, nu)
        return p, mu, nu

    @jax.jit
    def norms(tree, other=None):
        return {k: jnp.sqrt(jnp.sum(jnp.square(
            v - (0.0 if other is None else other[k]))))
            for k, v in tree.items()}

    params = split(make_weights())
    mu = {g: jax.tree_util.tree_map(jnp.zeros_like, t)
          for g, t in params.items()}
    nu = {g: jax.tree_util.tree_map(jnp.zeros_like, t)
          for g, t in params.items()}
    losses, grad_norms = [], {}

    def update(g, grads, t):
        if t == 1:
            got = norms(grads)
            grad_norms.update({paths(g, grads)[k]: v for k, v in got.items()})
        params[g], mu[g], nu[g] = adam(params[g], mu[g], nu[g], grads,
                                       jnp.float32(t))

    for t, batch in enumerate(batches, 1):
        batch = batch[:rows] if rows else batch
        n = batch.shape[0]
        blocks = [slice(i, min(i + row_block, n))
                  for i in range(0, n, row_block)]
        tokens = [jax.device_put(batch[b, :-1], dev) for b in blocks]
        targets = [jax.device_put(batch[b, 1:], dev) for b in blocks]
        # forward, layer by layer, keeping every layer's input
        xs = [[params["table"]["token_embed/embedding"][tok]
               for tok in tokens]]
        for i in range(n_layer):
            xs.append([block_f(params[i], x) for x in xs[i]])
        loss, g_head, dxs = 0.0, None, []
        for x, tgt in zip(xs.pop(), targets):
            part, (gp, dx) = head_vg(params["head"], x, tgt, n * seq)
            loss = loss + part
            g_head = gp if g_head is None else add(g_head, gp)
            dxs.append(dx)
        update("head", g_head, t)
        del g_head
        # backward, each layer updated as soon as its gradient is whole
        for i in reversed(range(n_layer)):
            g_layer = None
            for r, x in enumerate(xs.pop()):
                gp, dxs[r] = block_b(params[i], x, dxs[r])
                g_layer = gp if g_layer is None else add(g_layer, gp)
            update(i, g_layer, t)
            del g_layer
        g_table = {"token_embed/embedding": jnp.zeros_like(
            params["table"]["token_embed/embedding"])}
        for tok, dx in zip(tokens, dxs):
            g_table = embed_b(g_table, tok, dx)
        update("table", g_table, t)
        del g_table, dxs
        losses.append(float(loss))

    del mu, nu
    start = split(make_weights())
    update_norms = {}
    for g in list(params):
        got = norms(params[g], start[g])
        update_norms.update({paths(g, got)[k]: v for k, v in got.items()})
        start[g] = params[g] = None
    return {"losses": losses,
            "grad_norms": {k: float(v) for k, v in grad_norms.items()},
            "update_norms": {k: float(v) for k, v in update_norms.items()}}
