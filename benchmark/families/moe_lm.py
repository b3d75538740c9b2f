"""Family `moe_lm`: a decoder of sparse-expert blocks with window and full
attention by layer kind, trained on token rows; one chip's share of an
expert-parallel deployment.

Read from the configuration file's published keys (`hidden_size`,
`num_attention_heads`, `num_key_value_heads`, `head_dim`, `layer_types`,
`sliding_window`, `rope_parameters`, `moe_intermediate_size`,
`num_experts_per_tok`, `rms_norm_eps`) and from its cut: `num_hidden_layers`
(the layers held), `num_experts` (the experts held, out of
`published.num_experts`, which stays the router's width), `vocab_size` (the
rows of the vocabulary held) and `deployment.this_chip` (which ones).

- `param_shapes`: the parameters and their initialisers, under the paths of
  the program's own tree (`layer_3/moe/experts_wi/kernel`);
- `build`: the program under test: `models/transformer.Transformer` with
  dropless routing over the held experts, `ops/xent.fused_unembed_xent`,
  `optim.make_optimizer`, the routing counters of `moe_stats`;
- `step_work`: operations and bytes one step REQUIRES, from shapes alone;
- `reference`: the plain float32 `jax.numpy` forward, backward and AdamW,
  which imports nothing of the program: every held expert computed densely
  for every token and masked by the picks, attention a head at a time.
"""
import math

import harness  # the benchmark's own: finds a family's file by name

# the dense family's `_matmul` (f32 | bf16 | fp8) and AdamW constants
lm = harness.load_module("families", "transformer_lm")

SLIDING = "sliding_attention"


def _sizes(cfg):
    return {"d": cfg["hidden_size"], "h": cfg["num_attention_heads"],
            "kv": cfg["num_key_value_heads"], "hd": cfg["head_dim"],
            "f": cfg["moe_intermediate_size"], "v": cfg["vocab_size"],
            "n": cfg["num_hidden_layers"], "held": cfg["num_experts"],
            "e": cfg["published"]["num_experts"],
            "k": cfg["num_experts_per_tok"],
            "off": cfg["deployment"]["this_chip"]["expert_offset"],
            "kinds": cfg["layer_types"][:cfg["num_hidden_layers"]],
            "window": cfg["sliding_window"]}


def param_shapes(cfg):
    z = _sizes(cfg)
    d, qd, kvd = z["d"], z["h"] * z["hd"], z["kv"] * z["hd"]
    std, one = ("normal", cfg["init"]["kernel_std"]), ("const", 1.0)
    out = {"token_embed/embedding": ((z["v"], d), (
               "normal", cfg["init"]["embedding_std"])),
           "ln_f/scale": ((d,), one),
           "lm_head/kernel": ((d, z["v"]), std)}
    block = {"ln1/scale": (d,), "ln2/scale": (d,),
             "attn/query/kernel": (d, qd), "attn/key/kernel": (d, kvd),
             "attn/value/kernel": (d, kvd), "attn/out/kernel": (qd, d),
             "moe/router/kernel": (d, z["e"]),
             "moe/experts_wi/kernel": (z["held"], d, z["f"]),
             "moe/experts_up/kernel": (z["held"], d, z["f"]),
             "moe/experts_wo/kernel": (z["held"], z["f"], d)}
    for i in range(z["n"]):
        for name, shape in block.items():
            out[f"layer_{i}/{name}"] = (
                shape, std if name.endswith("kernel") else one)
    return out


# ------------------------------------------------------------ program ----

def build(cfg):
    """`(loss_fn, optimizer)` of the program under test.  The loss takes
    the hidden states and fuses the head into the cross entropy: plain
    `lm_loss` would hold `[T, V]` float32 logits and a one-hot of the same
    size, 1.6 GB each at 16,384 tokens.  `program.zero_expert` (tests only:
    no file sets it) zeroes one held expert's output in every layer."""
    import jax.numpy as jnp

    from tensorflowonspark_tpu.models.transformer import (
        MOE_COUNTERS, Transformer, TransformerConfig, moe_stats)
    from tensorflowonspark_tpu.ops.xent import fused_unembed_xent
    from tensorflowonspark_tpu.optim import make_optimizer

    mcfg = TransformerConfig(**cfg["program"]["model"])
    model = Transformer(mcfg)
    dead = cfg["program"].get("zero_expert")

    def loss_fn(p, batch, rng):
        if dead is not None:
            p = dict(p)
            for i in range(mcfg.n_layers):
                w = p[f"layer_{i}"]["moe"]["experts_wo"]["kernel"]
                p[f"layer_{i}"] = dict(p[f"layer_{i}"], moe=dict(
                    p[f"layer_{i}"]["moe"],
                    experts_wo={"kernel": w.at[dead].set(0.0)}))
        hidden, sown = model.apply({"params": p}, batch[:, :-1],
                                   return_hidden=True,
                                   mutable=["intermediates"])
        loss = fused_unembed_xent(
            hidden, p["lm_head"]["kernel"].astype(jnp.dtype(mcfg.dtype)),
            batch[:, 1:], cfg["program"]["xent_chunk"])
        return loss, moe_stats(sown["intermediates"])

    loss_fn.counters = MOE_COUNTERS     # the step object counts them

    o = dict(cfg["program"]["optimizer"])
    opt, _ = make_optimizer(o.pop("name"), **o)
    return loss_fn, opt


# --------------------------------------------------------------- work ----

def visible_pairs(seq, window=None):
    """(query, key) pairs a causal layer sees in one row of `seq` tokens:
    key j for query i iff j <= i, and with a window also i - j < window."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def step_work(cfg, batch):
    """What one step of `batch` rows requires, from shapes: no embedding
    gather, no recomputation, forward and backward three times the
    forward's multiply-adds.  Attention counts the visible pairs of each
    layer kind; the experts count the EXPECTED local pairs, `T x k x
    held / E` (uniform routing: `moe_local_pairs_pct.moe` says how near the
    run came)."""
    z = _sizes(cfg)
    d, qd, kvd, f = z["d"], z["h"] * z["hd"], z["kv"] * z["hd"], z["f"]
    seq = cfg["program"]["seq_len"]
    tokens = batch * seq
    pairs = sum(visible_pairs(seq, z["window"] if kind == SLIDING else None)
                for kind in z["kinds"]) * batch
    local = tokens * z["k"] * z["held"] // z["e"]        # a layer
    proj = z["n"] * (2 * d * qd + 2 * d * kvd + d * z["e"]) + d * z["v"]
    attn = 12 * pairs * qd                  # 3 x (QK^T + PV), 2 a mult-add
    gmm = z["n"] * local * 3 * 6 * d * f    # gate, up, down; fwd + 2 bwd
    n_params = sum(math.prod(shape) for shape, _ in
                   param_shapes(cfg).values())
    act = 2                                 # bytes of an activation (bf16)
    return {
        "flops": 6 * proj * tokens + attn + gmm,
        "n_params": n_params,
        "visible_pairs": pairs, "local_pairs": z["n"] * local,
        # flash forward reads q, k, v and writes o; backward reads q, k, v,
        # o, do and writes dq, dk, dv: six tensors of the query's width and
        # six of the narrow key/value width (GQA: never repeated)
        "flash": {"flops": attn,
                  "bytes": z["n"] * 6 * tokens * (qd + kvd) * act},
        # each of the three products, forward and twice backward: the
        # weights read (forward, gradient of the rows) or written (their
        # own gradient) once, the rows of both sides once
        "moe_gmm": {"flops": gmm,
                    "bytes": z["n"] * 3 * 3 * (z["held"] * d * f
                                               + local * (d + f)) * act},
        "adamw": {"bytes": n_params * lm._adamw_bytes(cfg)},
    }


# ---------------------------------------------------------- reference ----

def rope_tables(cfg, kind, seq):
    """`(cos, sin)` [seq, head_dim / 2] of a layer kind, in float64 on the
    host: plain rotary on the window layers, YaRN with its attention factor
    on the full ones (`rope_parameters`)."""
    import numpy as np

    r = cfg["rope_parameters"][kind]
    hd = cfg["head_dim"]
    m = np.arange(hd // 2, dtype=np.float64)
    inv = float(r["rope_theta"]) ** (-2.0 * m / hd)
    factor = 1.0
    if r["rope_type"] == "yarn":
        def pair_of(turns):
            return hd * math.log(r["original_max_position_embeddings"] / (
                2 * math.pi * turns)) / (2 * math.log(r["rope_theta"]))

        low = max(math.floor(pair_of(r["beta_fast"])), 0)
        high = min(math.ceil(pair_of(r["beta_slow"])), hd - 1)
        ramp = np.clip((m - low) / (high - low), 0.0, 1.0)
        inv = (1.0 - ramp) * inv + ramp * inv / r["factor"]
        factor = r["attention_factor"]
    elif r["rope_type"] != "default":
        raise ValueError(f"rope_type {r['rope_type']!r}")
    angles = np.arange(seq, dtype=np.float64)[:, None] * inv[None, :]
    return ((np.cos(angles) * factor).astype(np.float32),
            (np.sin(angles) * factor).astype(np.float32))


def _rms_norm(x, scale, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * scale


def _rotate(x, cos, sin):
    """Split-half pairing over the last axis of [..., S, heads, hd]."""
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    cos, sin = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(p, h, z, cos, sin, window, mm):
    """[B, S, d] -> [B, S, d]: a head at a time, so the [S, S] scores of one
    head are all that is live (and recomputed in the backward pass)."""
    import jax
    import jax.numpy as jnp

    b, s, _ = h.shape
    heads, kv, hd = z["h"], z["kv"], z["hd"]
    q = _rotate(mm(h, p["attn/query/kernel"]).reshape(b, s, heads, hd),
                cos, sin)
    k = _rotate(mm(h, p["attn/key/kernel"]).reshape(b, s, kv, hd), cos, sin)
    v = mm(h, p["attn/value/kernel"]).reshape(b, s, kv, hd)
    i = jnp.arange(s)
    seen = i[:, None] >= i[None, :]
    if window is not None:
        seen = seen & (i[:, None] - i[None, :] < window)

    @jax.checkpoint
    def head(args):
        qh, kh, vh = args                              # [S, hd]
        logits = mm(qh, kh.T) / math.sqrt(hd)
        probs = jax.nn.softmax(jnp.where(seen, logits, -1e30), axis=-1)
        return mm(probs, vh)

    def per_head(x, n):                                # -> [B * heads, S, hd]
        x = x.transpose(0, 2, 1, 3)
        x = jnp.repeat(x, heads // n, axis=1)          # head i reads i // 8
        return x.reshape(b * heads, s, hd)

    o = jax.lax.map(head, (per_head(q, heads), per_head(k, kv),
                           per_head(v, kv)))
    o = o.reshape(b, heads, s, hd).transpose(0, 2, 1, 3).reshape(
        b, s, heads * hd)
    return mm(o, p["attn/out/kernel"])


def route(p, hn, z):
    """`(weights [T, held], picks [T, k])`: softmax over all the experts in
    float32, the k largest, their weights over their sum; of those the
    columns of the experts held here (the absent ones' are left out)."""
    import jax
    import jax.numpy as jnp

    logits = jnp.matmul(hn, p["moe/router/kernel"],
                        precision=jax.lax.Precision.HIGHEST)
    top, picks = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), z["k"])
    top = top / jnp.sum(top, axis=-1, keepdims=True)
    full = jnp.sum(jax.nn.one_hot(picks, z["e"], dtype=top.dtype)
                   * top[..., None], axis=-2)          # [T, E]
    return full[:, z["off"]:z["off"] + z["held"]], picks


def _experts(p, hn, z, mm):
    """Every held expert for every token, masked by the picks: no sort, no
    capacity."""
    import jax
    import jax.numpy as jnp

    weights, _ = route(p, hn, z)

    @jax.checkpoint
    def one(y, args):
        wg, wu, wd, w = args
        return y + w[:, None] * mm(jax.nn.silu(mm(hn, wg)) * mm(hn, wu),
                                   wd), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(hn), (
        p["moe/experts_wi/kernel"], p["moe/experts_up/kernel"],
        p["moe/experts_wo/kernel"], weights.T))
    return y


def _block(p, x, z, eps, cos, sin, window, mm, picks=False):
    """One pre-norm block on [B, S, d] float32 (`picks`: the router's
    choices [T, k] beside it, for a look at how the program routes)."""
    b, s, d = x.shape
    x = x + _attention(p, _rms_norm(x, p["ln1/scale"], eps), z, cos, sin,
                       window, mm)
    hn = _rms_norm(x, p["ln2/scale"], eps).reshape(b * s, d)
    y = x + _experts(p, hn, z, mm).reshape(b, s, d)
    return (y, route(p, hn, z)[1]) if picks else y


def _head_loss(p, x, targets, denom, eps, mm):
    """Sum of the rows' cross entropies over `denom` (the whole batch's
    token count, so row blocks add up to the batch mean)."""
    import jax
    import jax.numpy as jnp

    logits = mm(_rms_norm(x, p["ln_f/scale"], eps), p["lm_head/kernel"])
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.sum(logz - gold) / denom


def reference(cfg, make_weights, batches, precision="f32", devices=None,
              row_block=1, rows=None, keep_grads=False):
    """Follow `len(batches)` AdamW steps in plain float32, as
    `transformer_lm.reference` does: layer by layer, in blocks of
    `row_block` rows, each layer updated as soon as its gradient is whole.
    Returns `{"losses", "grad_norms", "update_norms"}`.  `precision`: the
    matmuls' (`f32`; `bf16` a look; `fp8` the control); the router's
    logits stay float32 in each, as the configuration states them.
    `rows` plants a fault: only the first `rows` rows of each batch."""
    import functools

    import jax
    import jax.numpy as jnp

    if keep_grads:
        raise ValueError("no full gradient tree ever exists here")
    dev = (devices or [jax.devices()[0]])[0]
    z = _sizes(cfg)
    n_layer, eps = z["n"], cfg["rms_norm_eps"]
    o = cfg["program"]["optimizer"]
    lr, b1, b2 = o["learning_rate"], o.get("b1", 0.9), o.get("b2", 0.999)
    mm = lm._matmul(precision)
    seq = batches[0].shape[1] - 1
    tables = {kind: jax.device_put(rope_tables(cfg, kind, seq), dev)
              for kind in set(z["kinds"])}

    def split(flat):
        groups = {"embed": {"token_embed/embedding":
                            flat["token_embed/embedding"]},
                  "head": {k: flat[k] for k in ("ln_f/scale",
                                                "lm_head/kernel")}}
        for i in range(n_layer):
            pre = f"layer_{i}/"
            groups[i] = {k[len(pre):]: v for k, v in flat.items()
                         if k.startswith(pre)}
        return {g: jax.device_put(t, dev) for g, t in groups.items()}

    def paths(g, tree):
        pre = "" if g in ("embed", "head") else f"layer_{g}/"
        return {k: pre + k for k in tree}

    def block_of(kind):
        return functools.partial(
            _block, z=z, eps=eps, mm=mm,
            window=z["window"] if kind == SLIDING else None)

    block_f = {kind: jax.jit(block_of(kind)) for kind in tables}
    block_b = {kind: jax.jit(lambda p, x, cos, sin, dy, f=block_of(kind):
                             jax.vjp(lambda p_, x_: f(p_, x_, cos=cos,
                                                      sin=sin), p, x)[1](dy))
               for kind in tables}

    @jax.jit
    def embed_b(p, tokens, dx):
        return {"token_embed/embedding": jnp.zeros_like(
            p["token_embed/embedding"]).at[tokens].add(dx)}

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
        xs = [[params["embed"]["token_embed/embedding"][tok]
               for tok in tokens]]
        for i, kind in enumerate(z["kinds"]):
            xs.append([block_f[kind](params[i], x, cos=tables[kind][0],
                                     sin=tables[kind][1]) for x in xs[i]])
        loss, g_head, dxs = 0.0, None, []
        for x, tgt in zip(xs.pop(), targets):
            part, (gp, dx) = head_vg(params["head"], x, tgt, denom)
            loss = loss + part
            g_head = gp if g_head is None else add(g_head, gp)
            dxs.append(dx)
        update("head", g_head, t)
        del g_head
        # backward, each layer updated as soon as its gradient is whole
        for i in reversed(range(n_layer)):
            kind, g_layer = z["kinds"][i], None
            for r, x in enumerate(xs.pop()):
                gp, dxs[r] = block_b[kind](params[i], x, *tables[kind],
                                           dxs[r])
                g_layer = gp if g_layer is None else add(g_layer, gp)
            update(i, g_layer, t)
            del g_layer
        g_embed = None
        for tok, dx in zip(tokens, dxs):
            gp = embed_b(params["embed"], tok, dx)
            g_embed = gp if g_embed is None else add(g_embed, gp)
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
