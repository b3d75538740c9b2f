"""Family `lfm2_moe`: a decoder whose layers mix the sequence with a gated
short convolution or with full attention under query/key norms, and feed
forward through a dense SwiGLU (the leading layers) or through sparse
experts routed by sigmoid scores with a selection bias; one table for the
embedding and the head; one chip's share of an expert-parallel deployment.

Read from the configuration file's published keys (`hidden_size`,
`num_attention_heads`, `num_key_value_heads`, `intermediate_size`,
`moe_intermediate_size`, `num_experts_per_tok`, `conv_L_cache`, `norm_eps`,
`rope_theta`, `routed_scaling_factor`, `layer_types`) and from its cut:
`num_hidden_layers` (the layers held, from `deployment.this_chip.first_layer`
on), `num_dense_layers` (how many of them are dense), `num_experts` (the
experts held, out of `published.num_experts`, which stays the router's
width), `vocab_size` (the rows of the table held).

- `param_shapes`: the parameters and their initialisers, under the paths of
  the program's own tree (`layer_2/conv/in_proj/kernel`);
- `build`: the program under test: `models/transformer.Transformer`,
  `ops/xent.fused_unembed_xent` over the table transposed,
  `optim.make_optimizer`, the routing counters of `moe_stats`;
- `step_work`: operations and bytes one step REQUIRES, from shapes alone;
- `reference`: the plain float32 `jax.numpy` forward, backward and AdamW,
  which imports nothing of the program: the convolution as the sum over
  taps of shifted arrays, every held expert computed densely for every
  token and masked by the picks, attention a head at a time.
"""
import math

import harness  # the benchmark's own: finds a family's file by name

# the sparse family's `_rms_norm`, `_rotate`, `visible_pairs`, and through
# it the dense family's `_matmul` (f32 | bf16 | fp8) and AdamW constants
moe = harness.load_module("families", "moe_lm")
lm = moe.lm

CONV = "conv"
# what `reference(fault=...)` can plant (tests and the builder's readings;
# `program.fault`, which no file sets, plants one in a whole run)
FAULTS = ("bias_out_of_choice", "bias_in_weights", "tap_zeroed",
          "no_qk_norm", "zero_expert")


def _sizes(cfg):
    n = cfg["num_hidden_layers"]
    first = cfg["deployment"]["this_chip"]["first_layer"]
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return {"d": d, "h": h, "kv": cfg["num_key_value_heads"], "hd": d // h,
            "ff": cfg["intermediate_size"],
            "f": cfg["moe_intermediate_size"], "v": cfg["vocab_size"],
            "n": n, "dense": cfg["num_dense_layers"],
            "held": cfg["num_experts"],
            "e": cfg["published"]["num_experts"],
            "k": cfg["num_experts_per_tok"], "taps": cfg["conv_L_cache"],
            "scale": cfg["routed_scaling_factor"],
            "off": cfg["deployment"]["this_chip"]["expert_offset"],
            "kinds": cfg["layer_types"][first:first + n]}


def _layer_shapes(z, i):
    d, qd, kvd = z["d"], z["h"] * z["hd"], z["kv"] * z["hd"]
    out = {"ln1/scale": (d,), "ln2/scale": (d,)}
    if z["kinds"][i] == CONV:
        out.update({"conv/in_proj/kernel": (d, 3 * d),
                    "conv/taps": (d, z["taps"]),
                    "conv/out_proj/kernel": (d, d)})
    else:
        out.update({"attn/query/kernel": (d, qd), "attn/key/kernel": (d, kvd),
                    "attn/value/kernel": (d, kvd), "attn/out/kernel": (qd, d),
                    "attn/q_norm/scale": (z["hd"],),
                    "attn/k_norm/scale": (z["hd"],)})
    if i < z["dense"]:
        out.update({"mlp/wi_gate/kernel": (d, z["ff"]),
                    "mlp/wi_up/kernel": (d, z["ff"]),
                    "mlp/wo/kernel": (z["ff"], d)})
    else:
        out.update({"moe/router/kernel": (d, z["e"]),
                    "moe/expert_bias": (z["e"],),
                    "moe/experts_wi/kernel": (z["held"], d, z["f"]),
                    "moe/experts_up/kernel": (z["held"], d, z["f"]),
                    "moe/experts_wo/kernel": (z["held"], z["f"], d)})
    return out


def param_shapes(cfg):
    z, init = _sizes(cfg), cfg["init"]
    inits = {"kernel": ("normal", init["kernel_std"]),
             "taps": ("normal", init["taps_std"]),
             "expert_bias": ("normal", init["expert_bias_std"]),
             "scale": ("const", 1.0)}
    out = {"token_embed/embedding": ((z["v"], z["d"]), (
               "normal", init["embedding_std"])),
           "ln_f/scale": ((z["d"],), inits["scale"])}
    for i in range(z["n"]):
        for name, shape in _layer_shapes(z, i).items():
            out[f"layer_{i}/{name}"] = (shape, inits[name.rsplit("/", 1)[-1]])
    return out


# ------------------------------------------------------------ program ----

def build(cfg):
    """`(loss_fn, optimizer)` of the program under test.  The loss takes
    the hidden states and fuses the head, the embedding's table transposed,
    into the cross entropy (`[T, V]` float32 logits are 1.1 GB at 16,384
    tokens)."""
    import jax.numpy as jnp

    from tensorflowonspark_tpu.models.transformer import (
        MOE_COUNTERS, Transformer, TransformerConfig, moe_stats)
    from tensorflowonspark_tpu.ops.xent import fused_unembed_xent
    from tensorflowonspark_tpu.optim import make_optimizer

    if cfg["routed_scaling_factor"] != 1:
        raise ValueError("the program has no field for a routed scaling "
                         "factor other than the published 1")
    mcfg = TransformerConfig(**cfg["program"]["model"])
    model = Transformer(mcfg)

    def loss_fn(p, batch, rng):
        hidden, sown = model.apply({"params": p}, batch[:, :-1],
                                   return_hidden=True,
                                   mutable=["intermediates"])
        table = p["token_embed"]["embedding"].astype(jnp.dtype(mcfg.dtype))
        loss = fused_unembed_xent(hidden, table.T, batch[:, 1:],
                                  cfg["program"]["xent_chunk"])
        return loss, moe_stats(sown["intermediates"])

    loss_fn.counters = MOE_COUNTERS     # the step object counts them

    o = dict(cfg["program"]["optimizer"])
    opt, _ = make_optimizer(o.pop("name"), **o)
    return loss_fn, opt


# --------------------------------------------------------------- work ----

def step_work(cfg, batch):
    """What one step of `batch` rows requires, from shapes: no embedding
    gather, no recomputation, forward and backward three times the
    forward's multiply-adds.  Attention counts the causal pairs; the
    experts count the EXPECTED local pairs, `T x k x held / E` a sparse
    layer (uniform routing: `moe_local_pairs_pct.lfm` says how near the run
    came).  The convolution's taps and gates are left out: 24 operations a
    channel and token, 3e9 a step beside 21e12."""
    z = _sizes(cfg)
    d, qd, kvd = z["d"], z["h"] * z["hd"], z["kv"] * z["hd"]
    seq = cfg["program"]["seq_len"]
    tokens = batch * seq
    n_conv = sum(kind == CONV for kind in z["kinds"])
    n_attn, n_sparse = z["n"] - n_conv, z["n"] - z["dense"]
    pairs = n_attn * moe.visible_pairs(seq) * batch
    local = tokens * z["k"] * z["held"] // z["e"]        # a sparse layer
    proj = (n_conv * (d * 3 * d + d * d) + n_attn * (2 * d * qd + 2 * d * kvd)
            + z["dense"] * 3 * d * z["ff"] + n_sparse * d * z["e"]
            + d * z["v"])
    attn = 12 * pairs * qd                  # 3 x (QK^T + PV), 2 a mult-add
    gmm = n_sparse * local * 3 * 6 * d * z["f"]   # gate, up, down; fwd + 2 bwd
    n_params = sum(math.prod(shape) for shape, _ in
                   param_shapes(cfg).values())
    act = 2                                 # bytes of an activation (bf16)
    return {
        "flops": 6 * proj * tokens + attn + gmm,
        "n_params": n_params,
        "visible_pairs": pairs, "local_pairs": n_sparse * local,
        # as `moe_lm.step_work`: six tensors of the query's width and six
        # of the narrow key/value width, forward and backward
        "flash": {"flops": attn,
                  "bytes": n_attn * 6 * tokens * (qd + kvd) * act},
        "moe_gmm": {"flops": gmm,
                    "bytes": n_sparse * 3 * 3 * (z["held"] * d * z["f"]
                                                 + local * (d + z["f"])) * act},
        "adamw": {"bytes": n_params * lm._adamw_bytes(cfg)},
    }


# ---------------------------------------------------------- reference ----

def rope_tables(cfg, seq):
    """`(cos, sin)` [seq, head_dim / 2] of plain rotary, in float64 on the
    host."""
    import numpy as np

    hd = cfg["hidden_size"] // cfg["num_attention_heads"]
    inv = float(cfg["rope_theta"]) ** (
        -2.0 * np.arange(hd // 2, dtype=np.float64) / hd)
    angles = np.arange(seq, dtype=np.float64)[:, None] * inv[None, :]
    return np.cos(angles).astype(np.float32), np.sin(angles).astype(np.float32)


def _short_conv(p, u, z, mm, fault):
    """[B, S, d] -> [B, S, d]: `g = b * z`, `s_t = sum_j w_j g_{t-(L-1)+j}`
    with zeros left of the row's start, `(c * s) W_out`."""
    import jax.numpy as jnp

    b, c, gate = jnp.split(mm(u, p["conv/in_proj/kernel"]), 3, axis=-1)
    g, w, n = b * gate, p["conv/taps"], z["taps"]
    seq = g.shape[1]
    s = jnp.zeros_like(g)
    for j in range(1 if fault == "tap_zeroed" else 0, n):
        back = n - 1 - j
        shifted = jnp.concatenate(
            [jnp.zeros_like(g[:, :back]), g[:, :seq - back]], axis=1)
        s = s + shifted * w[:, j]
    return mm(c * s, p["conv/out_proj/kernel"])


def _attention(p, u, z, eps, cos, sin, mm, fault):
    """[B, S, d] -> [B, S, d]: query/key RMSNorm a head, rotation, causal
    softmax a head at a time, so the [S, S] scores of one head are all that
    is live (and recomputed in the backward pass)."""
    import jax
    import jax.numpy as jnp

    b, s, _ = u.shape
    heads, kv, hd = z["h"], z["kv"], z["hd"]
    q = mm(u, p["attn/query/kernel"]).reshape(b, s, heads, hd)
    k = mm(u, p["attn/key/kernel"]).reshape(b, s, kv, hd)
    v = mm(u, p["attn/value/kernel"]).reshape(b, s, kv, hd)
    if fault != "no_qk_norm":
        q = moe._rms_norm(q, p["attn/q_norm/scale"], eps)
        k = moe._rms_norm(k, p["attn/k_norm/scale"], eps)
    q, k = moe._rotate(q, cos, sin), moe._rotate(k, cos, sin)
    i = jnp.arange(s)
    seen = i[:, None] >= i[None, :]

    @jax.checkpoint
    def head(args):
        qh, kh, vh = args                              # [S, hd]
        logits = mm(qh, kh.T) / math.sqrt(hd)
        probs = jax.nn.softmax(jnp.where(seen, logits, -1e30), axis=-1)
        return mm(probs, vh)

    def per_head(x, n):                                # -> [B * heads, S, hd]
        x = jnp.repeat(x.transpose(0, 2, 1, 3), heads // n, axis=1)
        return x.reshape(b * heads, s, hd)

    o = jax.lax.map(head, (per_head(q, heads), per_head(k, kv),
                           per_head(v, kv)))
    o = o.reshape(b, heads, s, hd).transpose(0, 2, 1, 3).reshape(
        b, s, heads * hd)
    return mm(o, p["attn/out/kernel"])


def route(p, hn, z, fault=None):
    """`(weights [T, held], picks [T, k])`: sigmoid of the float32 logits,
    the k largest of score + bias, weighted by the scores WITHOUT the bias
    over their sum + 1e-6, times the scaling factor; of those the columns
    of the experts held here (the absent ones' are left out)."""
    import jax
    import jax.numpy as jnp

    scores = jax.nn.sigmoid(jnp.matmul(
        hn, p["moe/router/kernel"], precision=jax.lax.Precision.HIGHEST))
    biased = scores + p["moe/expert_bias"]
    _, picks = jax.lax.top_k(
        scores if fault == "bias_out_of_choice" else biased, z["k"])
    top = jnp.take_along_axis(
        biased if fault == "bias_in_weights" else scores, picks, axis=-1)
    top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-6) * z["scale"]
    full = jnp.sum(jax.nn.one_hot(picks, z["e"], dtype=top.dtype)
                   * top[..., None], axis=-2)          # [T, E]
    return full[:, z["off"]:z["off"] + z["held"]], picks


def _experts(p, hn, z, mm, fault):
    """Every held expert for every token, masked by the picks."""
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
    return y


def _block(p, x, z, eps, cos, sin, mm, fault=None):
    """One pre-norm block on [B, S, d] float32; what the leaves of `p` are
    says which mixer and which feed-forward it has."""
    import jax

    b, s, d = x.shape
    u = moe._rms_norm(x, p["ln1/scale"], eps)
    x = x + (_short_conv(p, u, z, mm, fault) if "conv/taps" in p
             else _attention(p, u, z, eps, cos, sin, mm, fault))
    hn = moe._rms_norm(x, p["ln2/scale"], eps)
    if "mlp/wo/kernel" in p:
        return x + mm(jax.nn.silu(mm(hn, p["mlp/wi_gate/kernel"]))
                      * mm(hn, p["mlp/wi_up/kernel"]), p["mlp/wo/kernel"])
    return x + _experts(p, hn.reshape(b * s, d), z, mm, fault).reshape(b, s, d)


def _head_loss(p, x, targets, denom, eps, mm):
    """Sum of the rows' cross entropies over `denom`, the logits read off
    the embedding's own table."""
    import jax
    import jax.numpy as jnp

    logits = mm(moe._rms_norm(x, p["ln_f/scale"], eps),
                p["token_embed/embedding"].T)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.sum(logz - gold) / denom


def reference(cfg, make_weights, batches, precision="f32", devices=None,
              row_block=1, rows=None, keep_grads=False, fault=None):
    """Follow `len(batches)` AdamW steps in plain float32, as
    `moe_lm.reference` does: layer by layer, in blocks of `row_block` rows,
    each layer updated as soon as its gradient is whole; the table last,
    when the head's and the embedding's gradients have been added.
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
    n_layer, eps = z["n"], cfg["norm_eps"]
    o = cfg["program"]["optimizer"]
    lr, b1, b2 = o["learning_rate"], o.get("b1", 0.9), o.get("b2", 0.999)
    mm = lm._matmul(precision)
    seq = batches[0].shape[1] - 1
    cos, sin = jax.device_put(rope_tables(cfg, seq), dev)

    def split(flat):
        groups = {"table": {k: flat[k] for k in ("token_embed/embedding",
                                                 "ln_f/scale")}}
        for i in range(n_layer):
            pre = f"layer_{i}/"
            groups[i] = {k[len(pre):]: v for k, v in flat.items()
                         if k.startswith(pre)}
        return {g: jax.device_put(t, dev) for g, t in groups.items()}

    def paths(g, tree):
        pre = "" if g == "table" else f"layer_{g}/"
        return {k: pre + k for k in tree}

    block = functools.partial(_block, z=z, eps=eps, cos=cos, sin=sin, mm=mm,
                              fault=fault)
    block_f = jax.jit(block)            # one program a layer's leaf set

    @jax.jit
    def block_b(p, x, dy):
        return jax.vjp(block, p, x)[1](dy)

    @jax.jit
    def embed_b(g, tokens, dx):
        return dict(g, **{"token_embed/embedding":
                          g["token_embed/embedding"].at[tokens].add(dx)})

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
        denom = n * seq
        # forward, layer by layer, keeping every layer's input
        xs = [[params["table"]["token_embed/embedding"][tok]
               for tok in tokens]]
        for i in range(n_layer):
            xs.append([block_f(params[i], x) for x in xs[i]])
        loss, g_table, dxs = 0.0, None, []
        for x, tgt in zip(xs.pop(), targets):
            part, (gp, dx) = head_vg(params["table"], x, tgt, denom)
            loss = loss + part
            g_table = gp if g_table is None else add(g_table, gp)
            dxs.append(dx)
        # backward, each layer updated as soon as its gradient is whole
        for i in reversed(range(n_layer)):
            g_layer = None
            for r, x in enumerate(xs.pop()):
                gp, dxs[r] = block_b(params[i], x, dxs[r])
                g_layer = gp if g_layer is None else add(g_layer, gp)
            update(i, g_layer, t)
            del g_layer
        for tok, dx in zip(tokens, dxs):       # the table's other use
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
