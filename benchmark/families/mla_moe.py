"""Family `mla_moe`: a decoder of latent-attention (MLA) blocks, a leading
dense SwiGLU and then sparse layers (a shared expert beside sigmoid top-k
routed experts under a selection bias, the routed sum scaled), and a
multi-token-prediction module behind the last block that shares the table
and the head; one chip's share of an expert-parallel deployment.

Read from the configuration file's published keys (`hidden_size`,
`num_attention_heads`, `q_lora_rank`, `kv_lora_rank`, `qk_nope_head_dim`,
`qk_rope_head_dim`, `v_head_dim`, `rope_theta`, `rope_interleave`,
`intermediate_size`, `moe_intermediate_size`, `num_experts_per_tok`,
`n_shared_experts`, `routed_scaling_factor`, `first_k_dense_replace`,
`num_nextn_predict_layers`, `rms_norm_eps`) and from its cut:
`num_hidden_layers` (the layers held), `n_routed_experts` (the experts held,
out of `published.n_routed_experts`, which stays the router's width),
`vocab_size` (the rows of the table and of the head held); `mtp_loss_weight`
is assumed (the file says why).

- `param_shapes`: the parameters and their initialisers, under the paths of
  the program's own tree (`layer_2/attn/kv_b/kernel`, `mtp_0_proj/kernel`);
- `build`: the program under test: `models/transformer.Transformer`, its
  `next_token_losses` (two `ops/xent.fused_unembed_xent` over the one head),
  `optim.make_optimizer`, the counters of `moe_stats` and of the loss;
- `step_work`: operations and bytes one step REQUIRES, from shapes alone;
- `reference`: the plain float32 `jax.numpy` forward, backward and AdamW,
  which imports nothing of the program: attention a head at a time over
  the explicit `[k_nope | k_rope]` key, every held expert computed densely
  for every token and masked by the picks, the shared expert once, both
  losses.
"""
import math

import harness  # the benchmark's own: finds a family's file by name

# the sparse family's `_rms_norm`, `visible_pairs`, and through it the dense
# family's `_matmul` (f32 | bf16 | fp8) and AdamW constants
moe = harness.load_module("families", "moe_lm")
lm = moe.lm

# what `reference(fault=...)` can plant (tests and the builder's readings;
# `program.fault`, which no file sets, plants one in a whole run)
FAULTS = ("rope_key_per_head", "rope_all_lanes", "scale_from_value_width",
          "no_routed_scale", "no_shared_expert", "no_second_loss",
          "second_loss_on_next1", "bias_in_weights", "zero_expert")


def _sizes(cfg):
    return {"d": cfg["hidden_size"], "h": cfg["num_attention_heads"],
            "qr": cfg["q_lora_rank"], "kvr": cfg["kv_lora_rank"],
            "dn": cfg["qk_nope_head_dim"], "dr": cfg["qk_rope_head_dim"],
            "dv": cfg["v_head_dim"], "ff": cfg["intermediate_size"],
            "f": cfg["moe_intermediate_size"], "v": cfg["vocab_size"],
            "n": cfg["num_hidden_layers"],
            "dense": cfg["first_k_dense_replace"],
            "held": cfg["n_routed_experts"],
            "e": cfg["published"]["n_routed_experts"],
            "k": cfg["num_experts_per_tok"],
            "shared": cfg["n_shared_experts"],
            "scale": cfg["routed_scaling_factor"],
            "mtp": cfg["num_nextn_predict_layers"],
            "lam": cfg["mtp_loss_weight"],
            "off": cfg["deployment"]["this_chip"]["expert_offset"]}


def _block_shapes(z, sparse):
    d, h, f = z["d"], z["h"], z["f"]
    out = {"ln1/scale": (d,), "ln2/scale": (d,),
           "attn/q_a/kernel": (d, z["qr"]), "attn/q_a_norm/scale": (z["qr"],),
           "attn/q_b/kernel": (z["qr"], h * (z["dn"] + z["dr"])),
           "attn/kv_a/kernel": (d, z["kvr"] + z["dr"]),
           "attn/kv_a_norm/scale": (z["kvr"],),
           "attn/kv_b/kernel": (z["kvr"], h * (z["dn"] + z["dv"])),
           "attn/out/kernel": (h * z["dv"], d)}
    if not sparse:
        out.update({"mlp/wi_gate/kernel": (d, z["ff"]),
                    "mlp/wi_up/kernel": (d, z["ff"]),
                    "mlp/wo/kernel": (z["ff"], d)})
        return out
    out.update({"moe/router/kernel": (d, z["e"]),
                "moe/expert_bias": (z["e"],),
                "moe/experts_wi/kernel": (z["held"], d, f),
                "moe/experts_up/kernel": (z["held"], d, f),
                "moe/experts_wo/kernel": (z["held"], f, d),
                "moe/shared/wi_gate/kernel": (d, z["shared"] * f),
                "moe/shared/wi_up/kernel": (d, z["shared"] * f),
                "moe/shared/wo/kernel": (z["shared"] * f, d)})
    return out


def param_shapes(cfg):
    z, init = _sizes(cfg), cfg["init"]
    inits = {"kernel": ("normal", init["kernel_std"]),
             "expert_bias": ("normal", init["expert_bias_std"]),
             "scale": ("const", 1.0)}
    d = z["d"]
    out = {"token_embed/embedding": ((z["v"], d), (
               "normal", init["embedding_std"])),
           "ln_f/scale": ((d,), inits["scale"]),
           "lm_head/kernel": ((d, z["v"]), inits["kernel"])}
    blocks = {f"layer_{i}": i >= z["dense"] for i in range(z["n"])}
    for m in range(z["mtp"]):
        blocks[f"mtp_{m}_block"] = True
        out.update({f"mtp_{m}_hnorm/scale": ((d,), inits["scale"]),
                    f"mtp_{m}_enorm/scale": ((d,), inits["scale"]),
                    f"mtp_{m}_proj/kernel": ((2 * d, d), inits["kernel"]),
                    f"mtp_{m}_ln_f/scale": ((d,), inits["scale"])})
    for name, sparse in blocks.items():
        for leaf, shape in _block_shapes(z, sparse).items():
            out[f"{name}/{leaf}"] = (shape, inits[leaf.rsplit("/", 1)[-1]])
    return out


# ------------------------------------------------------------ program ----

def build(cfg):
    """`(loss_fn, optimizer)` of the program under test.  The loss is the
    package's own `next_token_losses`: both heads' passes fused into their
    cross entropies over the one `lm_head` kernel.  Where the file expects
    kernels and the backend is the chip, a program whose latent mixers did
    not take the latent flash kernels is refused when the step is traced."""
    import jax
    import jax.numpy as jnp

    from tensorflowonspark_tpu import trace
    from tensorflowonspark_tpu.models.transformer import (
        LOSS_COUNTERS, MOE_COUNTERS, Transformer, TransformerConfig,
        moe_stats, next_token_losses)
    from tensorflowonspark_tpu.optim import make_optimizer

    mcfg = TransformerConfig(**cfg["program"]["model"])
    model = Transformer(mcfg)
    on_chip = (cfg["program"].get("expect_kernels")
               and jax.default_backend() == "tpu")

    def loss_fn(p, batch, rng):
        (hidden, ahead), sown = model.apply(
            {"params": p}, batch[:, :-1], return_hidden=True,
            mutable=["intermediates"])
        if on_chip and not trace.counters().snapshot().get(
                "flash.calls.latent"):
            raise RuntimeError("the latent mixers took no latent flash "
                               "kernel: they fell to the dense path")
        kernel = p["lm_head"]["kernel"].astype(jnp.dtype(mcfg.dtype))
        loss, terms = next_token_losses(
            hidden, ahead, kernel, batch, mcfg.mtp_loss_weight,
            cfg["program"]["xent_chunk"])
        return loss, {**moe_stats(sown["intermediates"]), **terms}

    loss_fn.counters = MOE_COUNTERS + LOSS_COUNTERS   # the step counts them

    o = dict(cfg["program"]["optimizer"])
    opt, _ = make_optimizer(o.pop("name"), **o)
    return loss_fn, opt


# --------------------------------------------------------------- work ----

def step_work(cfg, batch):
    """What one step of `batch` rows requires, from shapes: no embedding
    gather, no recomputation, forward and backward three times the
    forward's multiply-adds.  Latent attention counts the causal pairs at
    `(nope + rope) + v` multiply-adds a head (scores over 192, values over
    128); the experts count the EXPECTED local pairs, `T x k x held / E` a
    sparse layer (uniform routing: `moe_local_pairs_pct.joy` says how near
    the run came); the prediction module counts as a block, its projection
    and a second pass over the head.  The flash kernels' bytes count the
    rotary key ONCE a token: a kernel that reads a copy a head is not
    credited with the copies."""
    z = _sizes(cfg)
    d, h, f = z["d"], z["h"], z["f"]
    dq, dn, dr, dv = z["dn"] + z["dr"], z["dn"], z["dr"], z["dv"]
    seq = cfg["program"]["seq_len"]
    tokens = batch * seq
    n_mla = z["n"] + z["mtp"]
    n_sparse = z["n"] - z["dense"] + z["mtp"]
    pairs = n_mla * moe.visible_pairs(seq) * batch
    local = tokens * z["k"] * z["held"] // z["e"]        # a sparse layer
    mla = (d * z["qr"] + z["qr"] * h * dq + d * (z["kvr"] + dr)
           + z["kvr"] * h * (dn + dv) + h * dv * d)
    proj = (n_mla * mla + z["dense"] * 3 * d * z["ff"]
            + n_sparse * (d * z["e"] + 3 * d * z["shared"] * f)
            + (1 + z["mtp"]) * d * z["v"] + z["mtp"] * 2 * d * d)
    attn = 6 * pairs * h * (dq + dv)        # 3 x (QK^T + PV), 2 a mult-add
    gmm = n_sparse * local * 3 * 6 * d * f  # gate, up, down; fwd + 2 bwd
    n_params = sum(math.prod(shape) for shape, _ in
                   param_shapes(cfg).values())
    act = 2                                 # bytes of an activation (bf16)
    return {
        "flops": 6 * proj * tokens + attn + gmm,
        "n_params": n_params,
        "visible_pairs": pairs, "local_pairs": n_sparse * local,
        # forward reads q, k_nope, k_rope, v and writes o; backward reads
        # those, o and do and writes dq, dk_nope, dk_rope, dv: three
        # tensors of the query's width, nine of a head's 128 (k_nope, v, o
        # twice, do, dk_nope, dv), three of the one rotary key a token
        "flash": {"flops": attn,
                  "bytes": n_mla * tokens * (3 * h * dq + 9 * h * dv
                                             + 3 * dr) * act},
        "moe_gmm": {"flops": gmm,
                    "bytes": n_sparse * 3 * 3 * (z["held"] * d * f
                                                 + local * (d + f)) * act},
        "adamw": {"bytes": n_params * lm._adamw_bytes(cfg)},
    }


# ---------------------------------------------------------- reference ----

def rope_tables(cfg, seq):
    """`(cos, sin)` [seq, rope lanes / 2] of plain rotary over the rotary
    lanes, in float64 on the host (`rope_scaling` is null: no mscale)."""
    import numpy as np

    if cfg.get("rope_scaling") is not None:
        raise ValueError("rope_scaling is not null")
    dr = cfg["qk_rope_head_dim"]
    inv = float(cfg["rope_theta"]) ** (
        -2.0 * np.arange(dr // 2, dtype=np.float64) / dr)
    angles = np.arange(seq, dtype=np.float64)[:, None] * inv[None, :]
    return np.cos(angles).astype(np.float32), np.sin(angles).astype(np.float32)


def _rotate_pairs(x, cos, sin):
    """Rotary in place over the last axis of [B, S, ..., D], pair m being
    lanes (2m, 2m + 1) (`rope_interleave`); `cos`, `sin` [S, D / 2]."""
    import jax.numpy as jnp

    shape = x.shape
    x = x.reshape(shape[:-1] + (shape[-1] // 2, 2))
    a, b = x[..., 0], x[..., 1]
    cos = cos.reshape((1, shape[1]) + (1,) * (len(shape) - 3) + (-1,))
    sin = sin.reshape(cos.shape)
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(shape)


def _attention(p, u, z, eps, cos, sin, mm, fault=None):
    """[B, S, d] -> [B, S, d]: the two low-rank paths with an RMSNorm on
    each latent, rotary on the rotary lanes only, one rotary key a token
    for all heads; causal softmax a head at a time over the explicit
    `[k_nope | k_rope]` key, so the [S, S] scores of one head are all that
    is live (and recomputed in the backward pass)."""
    import jax
    import jax.numpy as jnp

    b, s, _ = u.shape
    h, dn, dr, dv, rank = z["h"], z["dn"], z["dr"], z["dv"], z["kvr"]
    cq = moe._rms_norm(mm(u, p["attn/q_a/kernel"]), p["attn/q_a_norm/scale"],
                       eps)
    q = mm(cq, p["attn/q_b/kernel"]).reshape(b, s, h, dn + dr)
    kv_a = mm(u, p["attn/kv_a/kernel"])
    ckv = moe._rms_norm(kv_a[..., :rank], p["attn/kv_a_norm/scale"], eps)
    kv = mm(ckv, p["attn/kv_b/kernel"]).reshape(b, s, h, dn + dv)
    qn, qr, kn, v = q[..., :dn], q[..., dn:], kv[..., :dn], kv[..., dn:]
    kr = jnp.broadcast_to(kv_a[:, :, None, rank:], (b, s, h, dr))
    if fault == "rope_key_per_head":       # head a reads the key a lanes on
        kr = jnp.stack([jnp.roll(kr[:, :, a], a, axis=-1) for a in range(h)],
                       axis=2)
    qr, kr = _rotate_pairs(qr, cos, sin), _rotate_pairs(kr, cos, sin)
    if fault == "rope_all_lanes":          # the other lanes rotated as well
        reps = dn // dr
        wide = (jnp.tile(cos, (1, reps)), jnp.tile(sin, (1, reps)))
        qn, kn = _rotate_pairs(qn, *wide), _rotate_pairs(kn, *wide)
    width = dv if fault == "scale_from_value_width" else dn + dr
    i = jnp.arange(s)
    seen = i[:, None] >= i[None, :]

    @jax.checkpoint
    def head(args):
        qh, kh, vh = args                   # [S, dn + dr] x 2, [S, dv]
        logits = mm(qh, kh.T) / math.sqrt(width)
        probs = jax.nn.softmax(jnp.where(seen, logits, -1e30), axis=-1)
        return mm(probs, vh)

    def per_head(x):                        # -> [B * heads, S, width]
        return x.transpose(0, 2, 1, 3).reshape(b * h, s, x.shape[-1])

    o = jax.lax.map(head, (per_head(jnp.concatenate([qn, qr], -1)),
                           per_head(jnp.concatenate([kn, kr], -1)),
                           per_head(v)))
    o = o.reshape(b, h, s, dv).transpose(0, 2, 1, 3).reshape(b, s, h * dv)
    return mm(o, p["attn/out/kernel"])


def route(p, hn, z, fault=None):
    """`(weights [T, held], picks [T, k])`: sigmoid of the float32 logits,
    the k largest of score + bias, weighted by the scores WITHOUT the bias
    over their sum + 1e-20, times the scaling factor; of those the columns
    of the experts held here (the absent ones' are left out)."""
    import jax
    import jax.numpy as jnp

    scores = jax.nn.sigmoid(jnp.matmul(
        hn, p["moe/router/kernel"], precision=jax.lax.Precision.HIGHEST))
    biased = scores + p["moe/expert_bias"]
    _, picks = jax.lax.top_k(biased, z["k"])
    top = jnp.take_along_axis(
        biased if fault == "bias_in_weights" else scores, picks, axis=-1)
    top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
    if fault != "no_routed_scale":
        top = top * z["scale"]
    full = jnp.sum(jax.nn.one_hot(picks, z["e"], dtype=top.dtype)
                   * top[..., None], axis=-2)          # [T, E]
    return full[:, z["off"]:z["off"] + z["held"]], picks


def _sparse_ff(p, hn, z, mm, fault=None):
    """Every held expert for every token, masked by the picks, and the
    shared expert once."""
    import jax
    import jax.numpy as jnp

    weights, _ = route(p, hn, z, fault)
    if fault == "zero_expert":
        weights = weights.at[:, 1].set(0.0)

    @jax.checkpoint
    def one(y, args):
        wg, wu, wd, w = args
        return y + w[:, None] * mm(jax.nn.silu(mm(hn, wg)) * mm(hn, wu),
                                   wd), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(hn), (
        p["moe/experts_wi/kernel"], p["moe/experts_up/kernel"],
        p["moe/experts_wo/kernel"], weights.T))
    if fault == "no_shared_expert":
        return y
    return y + mm(jax.nn.silu(mm(hn, p["moe/shared/wi_gate/kernel"]))
                  * mm(hn, p["moe/shared/wi_up/kernel"]),
                  p["moe/shared/wo/kernel"])


def _block(p, x, z, eps, cos, sin, mm, fault=None):
    """One pre-norm block on [B, S, d] float32; what the leaves of `p` are
    says which feed-forward it has."""
    import jax

    b, s, d = x.shape
    x = x + _attention(p, moe._rms_norm(x, p["ln1/scale"], eps), z, eps,
                       cos, sin, mm, fault)
    hn = moe._rms_norm(x, p["ln2/scale"], eps)
    if "mlp/wo/kernel" in p:
        return x + mm(jax.nn.silu(mm(hn, p["mlp/wi_gate/kernel"]))
                      * mm(hn, p["mlp/wi_up/kernel"]), p["mlp/wo/kernel"])
    return x + _sparse_ff(p, hn.reshape(b * s, d), z, mm,
                          fault).reshape(b, s, d)


def _xent_sum(logits, targets, valid):
    import jax
    import jax.numpy as jnp

    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.sum((logz - gold) * valid)


def _head_loss(p, x, targets, denom, eps, mm):
    """Sum of the rows' next-token cross entropies over `denom`."""
    import jax.numpy as jnp

    logits = mm(moe._rms_norm(x, p["ln_f/scale"], eps), p["lm_head/kernel"])
    return _xent_sum(logits, targets, jnp.ones(targets.shape)) / denom


def _ahead_loss(pm, kernel, x, e_next, targets, valid, denom, block, eps, mm):
    """The prediction module's weighted term: `x` the last block's output
    (before the last norm), `e_next` the embedding of the token one ahead,
    `targets` the token two ahead where `valid`; the head is the main
    model's `kernel`."""
    import jax.numpy as jnp

    m = mm(jnp.concatenate(
        [moe._rms_norm(x, pm["mtp_0_hnorm/scale"], eps),
         moe._rms_norm(e_next, pm["mtp_0_enorm/scale"], eps)], axis=-1),
        pm["mtp_0_proj/kernel"])
    pre = "mtp_0_block/"
    m = block({k[len(pre):]: v for k, v in pm.items() if k.startswith(pre)},
              m)
    logits = mm(moe._rms_norm(m, pm["mtp_0_ln_f/scale"], eps), kernel)
    return _xent_sum(logits, targets, valid) / denom


def reference(cfg, make_weights, batches, precision="f32", devices=None,
              row_block=1, rows=None, keep_grads=False, fault=None):
    """Follow `len(batches)` AdamW steps in plain float32, as
    `moe_lm.reference` does: layer by layer, in blocks of `row_block` rows,
    each group of leaves updated as soon as its gradient is whole (the head
    when both losses' passes have added theirs, the table last, when the
    module's and the first layer's gradients have been added).
    Returns `{"losses", "grad_norms", "update_norms"}`.  `precision`: the
    matmuls' (`f32`; `bf16` a look; `fp8` the control); the router's logits
    stay float32 in each.  `rows` plants a fault: only the first `rows` rows
    of each batch; `fault` one of `FAULTS`."""
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
    if z["mtp"] != 1:
        raise ValueError("the reference follows one prediction module")
    n_layer, eps = z["n"], cfg["rms_norm_eps"]
    lam = 0.0 if fault == "no_second_loss" else z["lam"]
    o = cfg["program"]["optimizer"]
    lr, b1, b2 = o["learning_rate"], o.get("b1", 0.9), o.get("b2", 0.999)
    mm = lm._matmul(precision)
    seq = batches[0].shape[1] - 1
    cos, sin = jax.device_put(rope_tables(cfg, seq), dev)

    def split(flat):
        groups = {"embed": {"token_embed/embedding":
                            flat["token_embed/embedding"]},
                  "head": {k: flat[k] for k in ("ln_f/scale",
                                                "lm_head/kernel")},
                  "mtp": {k: v for k, v in flat.items()
                          if k.startswith("mtp_")}}
        for i in range(n_layer):
            pre = f"layer_{i}/"
            groups[i] = {k[len(pre):]: v for k, v in flat.items()
                         if k.startswith(pre)}
        return {g: jax.device_put(t, dev) for g, t in groups.items()}

    def paths(g, tree):
        pre = f"layer_{g}/" if isinstance(g, int) else ""
        return {k: pre + k for k in tree}

    block = functools.partial(_block, z=z, eps=eps, cos=cos, sin=sin, mm=mm,
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

    @functools.partial(jax.jit, static_argnums=(6,))
    def ahead_vg(pm, kernel, x, e_next, targets, valid, denom):
        """Value and gradients (module, head kernel, x, e_next)."""
        return jax.value_and_grad(
            lambda *a: lam * _ahead_loss(*a, targets, valid, denom, block,
                                         eps, mm),
            argnums=(0, 1, 2, 3))(pm, kernel, x, e_next)

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
        # the module's targets: two ahead, the row's last position without
        # one (planted: one ahead, every position)
        if fault == "second_loss_on_next1":
            ahead = [(tgt, jnp.ones(tgt.shape)) for tgt in targets]
            denom2 = n * seq
        else:
            ahead = [(jnp.pad(tgt[:, 1:], ((0, 0), (0, 1))),
                      jnp.pad(jnp.ones(tgt[:, 1:].shape), ((0, 0), (0, 1))))
                     for tgt in targets]
            denom2 = n * (seq - 1)
        table = params["embed"]["token_embed/embedding"]
        # forward, layer by layer, keeping every layer's input
        xs = [[table[tok] for tok in tokens]]
        for i in range(n_layer):
            xs.append([block_f(params[i], x) for x in xs[i]])
        # the embedding of the token one ahead (zeros behind the last)
        e_next = [jnp.pad(e[:, 1:], ((0, 0), (0, 1), (0, 0))) for e in xs[0]]
        loss, g_head, g_mtp, g_embed, dxs = 0.0, None, None, None, []
        for r, (x, tgt) in enumerate(zip(xs.pop(), targets)):
            part, (gp, dx) = head_vg(params["head"], x, tgt, n * seq)
            part2, (gm, gk, dx2, de) = ahead_vg(
                params["mtp"], params["head"]["lm_head/kernel"], x,
                e_next[r], *ahead[r], denom2)
            loss = loss + part + part2
            gp = dict(gp, **{"lm_head/kernel": gp["lm_head/kernel"] + gk})
            g_head = gp if g_head is None else add(g_head, gp)
            g_mtp = gm if g_mtp is None else add(g_mtp, gm)
            dxs.append(dx + dx2)
            # e_next[t] is the embedding of token t + 1
            if g_embed is None:
                g_embed = {"token_embed/embedding": jnp.zeros_like(table)}
            g_embed = embed_b(g_embed, tokens[r][:, 1:], de[:, :-1])
        del e_next, table
        update("head", g_head, t)
        update("mtp", g_mtp, t)
        del g_head, g_mtp
        # backward, each layer updated as soon as its gradient is whole
        for i in reversed(range(n_layer)):
            g_layer = None
            for r, x in enumerate(xs.pop()):
                gp, dxs[r] = block_b(params[i], x, dxs[r])
                g_layer = gp if g_layer is None else add(g_layer, gp)
            update(i, g_layer, t)
            del g_layer
        for tok, dx in zip(tokens, dxs):
            g_embed = embed_b(g_embed, tok, dx)
        update("embed", g_embed, t)
        del g_embed, dxs
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
