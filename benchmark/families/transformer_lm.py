"""Family `transformer_lm`: a GPT-2-shaped decoder trained on token rows.

Four things live here, all read from the configuration file's published
keys (`n_embd`, `n_layer`, `n_head`, `n_inner`, `n_positions`, `vocab_size`,
`layer_norm_epsilon`):

- `param_shapes`: the parameters and their initialisers, under the paths of
  the program's own tree (`layer_3/attn/query/kernel`);
- `build`: the program under test: `models/transformer.Transformer`,
  `lm_loss`, `optim.make_optimizer`;
- `step_work`: operations and bytes one step REQUIRES, from shapes alone;
- `reference`: the plain float32 `jax.numpy` forward, backward and AdamW,
  which imports nothing of the program.
"""
import math

ADAM_EPS = 1e-8      # the program's default (ops/fused_optim.adamw_fused)


def param_shapes(cfg):
    d, ff, v = cfg["n_embd"], cfg["n_inner"], cfg["vocab_size"]
    std = ("normal", 0.02)
    one, zero = ("const", 1.0), ("const", 0.0)
    out = {"token_embed/embedding": ((v, d), std),
           "pos_embed/embedding": ((cfg["n_positions"], d), std),
           "ln_f/scale": ((d,), one), "ln_f/bias": ((d,), zero),
           "lm_head/kernel": ((d, v), std)}
    for i in range(cfg["n_layer"]):
        for name, shape in _block_shapes(d, ff).items():
            init = std if name.endswith("kernel") else \
                one if name.endswith("scale") else zero
            out[f"layer_{i}/{name}"] = (shape, init)
    return out


def _block_shapes(d, ff):
    out = {}
    for ln in ("ln1", "ln2"):
        out[f"{ln}/scale"] = (d,)
        out[f"{ln}/bias"] = (d,)
    for proj in ("query", "key", "value", "out"):
        out[f"attn/{proj}/kernel"] = (d, d)
        out[f"attn/{proj}/bias"] = (d,)
    out["mlp/wi/kernel"], out["mlp/wi/bias"] = (d, ff), (ff,)
    out["mlp/wo/kernel"], out["mlp/wo/bias"] = (ff, d), (d,)
    return out


# ------------------------------------------------------------ program ----

def build(cfg):
    """`(loss_fn, optimizer)` of the program under test."""
    from tensorflowonspark_tpu.models.transformer import (
        Transformer, TransformerConfig, lm_loss)
    from tensorflowonspark_tpu.optim import make_optimizer

    model = Transformer(TransformerConfig(**cfg["program"]["model"]))

    def loss_fn(p, batch, rng):
        return lm_loss(model.apply({"params": p}, batch[:, :-1]),
                       batch[:, 1:])

    o = dict(cfg["program"]["optimizer"])
    opt, _ = make_optimizer(o.pop("name"), **o)
    return loss_fn, opt


# --------------------------------------------------------------- work ----

def step_work(cfg, batch):
    """What one step of `batch` rows requires, from shapes: no embedding
    gather, no recomputation.  Forward and backward are three times the
    forward's matmuls; causal attention does half of the square."""
    d, ff, v = cfg["n_embd"], cfg["n_inner"], cfg["vocab_size"]
    n_layer, s = cfg["n_layer"], cfg["n_positions"]
    tokens = batch * s
    matmul_params = n_layer * (4 * d * d + 2 * d * ff) + d * v
    attn = n_layer * batch * 6 * s * s * d        # 3 x (QK^T + PV) / 2
    n_params = sum(math.prod(shape) for shape, _ in
                   param_shapes(cfg).values())
    return {
        "flops": 6 * matmul_params * tokens + attn,
        "matmul_params": matmul_params,
        "n_params": n_params,
        # flash forward reads q,k,v and writes o; backward reads q,k,v,o,do
        # and writes dq,dk,dv: 12 tensors of [B,S,d] in the activation type
        "flash": {"flops": attn, "bytes": n_layer * 12 * tokens * d * 2},
        # AdamW reads param, grad, mu, nu and writes param, mu, nu
        # (no metric reads it yet: the kernels' own time leaves out what
        # XLA prefetches for them, PERF.md section 3)
        "adamw": {"bytes": n_params * _adamw_bytes(cfg)},
    }


def _adamw_bytes(cfg):
    mu = 2 if cfg["program"]["optimizer"].get("mu_dtype") == "bfloat16" else 4
    return (4 + 4 + mu + 4) + (4 + mu + 4)


# ---------------------------------------------------------- reference ----

def _matmul(precision):
    """`mm(a, b)` over the last axis of `a` and the second-last of `b`.
    `f32`: float32 at `highest`.  `bf16`: operands rounded to bfloat16,
    float32 sums (a look, see below).  `fp8`: the control: both operands
    rounded to the four significant bits of fp8 (e4m3)."""
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    if precision == "f32":
        return lambda a, b: jnp.matmul(a, b, precision=hi)
    if precision == "bf16":
        # the precision the configuration states, in plain code: a look
        # that tells rounding from a fault (tests/control.py), never the
        # reference a run is compared with
        return lambda a, b: jnp.matmul(
            a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
            preferred_element_type=jnp.float32)
    if precision != "fp8":
        raise ValueError(f"precision {precision!r}")

    def q(x):
        # fp8 e4m3 keeps four significant bits; its range is not the
        # limit where a tensor is scaled to it, so only they are modelled.
        # Straight through: the backward pass sees the rounded values and
        # passes gradients on (rounding alone has gradient nought).
        m, e = jnp.frexp(x)
        return x + jax.lax.stop_gradient(
            jnp.ldexp(jnp.round(m * 16.0) / 16.0, e) - x)

    return lambda a, b: jnp.matmul(q(a), q(b), precision=hi)


def _layer_norm(x, scale, bias, eps):
    import jax.numpy as jnp

    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * scale + bias


def _gelu_new(x):
    import jax.numpy as jnp

    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _block(p, x, n_head, eps, mm):
    """One pre-LN GPT-2 block on `[B, S, d]` float32."""
    import jax
    import jax.numpy as jnp

    b, s, d = x.shape
    hd = d // n_head
    h = _layer_norm(x, p["ln1/scale"], p["ln1/bias"], eps)

    def heads(name):
        y = mm(h, p[f"attn/{name}/kernel"]) + p[f"attn/{name}/bias"]
        return y.reshape(b, s, n_head, hd).transpose(0, 2, 1, 3)

    q, k, v = heads("query"), heads("key"), heads("value")
    scores = mm(q, k.transpose(0, 1, 3, 2)) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -1e30), axis=-1)
    o = mm(probs, v).transpose(0, 2, 1, 3).reshape(b, s, d)
    x = x + mm(o, p["attn/out/kernel"]) + p["attn/out/bias"]
    h = _layer_norm(x, p["ln2/scale"], p["ln2/bias"], eps)
    h = _gelu_new(mm(h, p["mlp/wi/kernel"]) + p["mlp/wi/bias"])
    return x + mm(h, p["mlp/wo/kernel"]) + p["mlp/wo/bias"]


def _head_loss(p, x, targets, denom, eps, mm):
    """Sum of the rows' cross entropies over `denom` (the whole batch's
    token count, so row blocks add up to the batch mean)."""
    import jax
    import jax.numpy as jnp

    h = _layer_norm(x, p["ln_f/scale"], p["ln_f/bias"], eps)
    logits = mm(h, p["lm_head/kernel"])
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.sum(logz - gold) / denom


def reference(cfg, make_weights, batches, precision="f32", devices=None,
              row_block=4, rows=None, keep_grads=False):
    """Follow `len(batches)` AdamW steps in plain float32.

    Returns `{"losses": [...], "grad_norms": {path: norm of the first
    gradient}, "update_norms": {path: norm of the parameters' change over
    all the steps}}`.

    It has to fit beside nothing else on one 16 GB chip at 838M parameters,
    where parameters and both moments alone are 10 GB: so the backward pass
    goes layer by layer, in blocks of `row_block` rows, and each layer's
    parameters are updated as soon as its gradient is whole; no full
    gradient tree ever exists.  `devices` spreads the layers over several
    chips (the four-chip cell).  `rows` plants a fault: only the first
    `rows` rows of each batch are used, the mean taken over them.
    """
    import functools

    import jax
    import jax.numpy as jnp

    if keep_grads:
        raise ValueError("no full gradient tree ever exists here")
    devices = devices or [jax.devices()[0]]
    n_layer, n_head = cfg["n_layer"], cfg["n_head"]
    eps = cfg["layer_norm_epsilon"]
    o = cfg["program"]["optimizer"]
    lr, b1, b2 = o["learning_rate"], o.get("b1", 0.9), o.get("b2", 0.999)
    mm = _matmul(precision)

    def dev(i):
        return devices[i * len(devices) // n_layer]

    head_keys = ("ln_f/scale", "ln_f/bias", "lm_head/kernel")
    embed_keys = ("token_embed/embedding", "pos_embed/embedding")

    def split(flat):
        """Groups of leaves, each on its device: embed, layers, head."""
        groups = {"embed": ({k: flat[k] for k in embed_keys}, devices[0]),
                  "head": ({k: flat[k] for k in head_keys}, devices[-1])}
        for i in range(n_layer):
            pre = f"layer_{i}/"
            groups[i] = ({k[len(pre):]: v for k, v in flat.items()
                          if k.startswith(pre)}, dev(i))
        return {g: jax.device_put(t, d) for g, (t, d) in groups.items()}

    def paths(g, tree):
        pre = "" if g in ("embed", "head") else f"layer_{g}/"
        return {k: pre + k for k in tree}

    block = functools.partial(_block, n_head=n_head, eps=eps, mm=mm)

    @jax.jit
    def embed_f(p, tokens):
        s = tokens.shape[1]
        return p["token_embed/embedding"][tokens] \
            + p["pos_embed/embedding"][:s][None]

    @jax.jit
    def embed_b(p, tokens, dx):
        s = tokens.shape[1]
        return {"token_embed/embedding":
                jnp.zeros_like(p["token_embed/embedding"]).at[tokens].add(dx),
                "pos_embed/embedding":
                jnp.zeros_like(p["pos_embed/embedding"]).at[:s].add(
                    dx.sum(0))}

    block_f = jax.jit(block)

    @jax.jit
    def block_b(p, x, dy):
        return jax.vjp(block, p, x)[1](dy)

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

        def leaf(p_, mu_, nu_, g_):
            mu_ = b1 * mu_ + (1.0 - b1) * g_
            nu_ = b2 * nu_ + (1.0 - b2) * g_ * g_
            upd = (mu_ / c1) / (jnp.sqrt(nu_ / c2) + ADAM_EPS)
            return p_ - lr * upd, mu_, nu_

        out = jax.tree_util.tree_map(leaf, p, mu, nu, g)
        pick = lambda i: jax.tree_util.tree_map(          # noqa: E731
            lambda t3: t3[i], out, is_leaf=lambda x: isinstance(x, tuple))
        return pick(0), pick(1), pick(2)

    @jax.jit
    def norms(tree):
        return {k: jnp.sqrt(jnp.sum(jnp.square(v))) for k, v in tree.items()}

    @jax.jit
    def diff_norms(a, b):
        return {k: jnp.sqrt(jnp.sum(jnp.square(a[k] - b[k]))) for k in a}

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
        tokens = [jax.device_put(batch[b, :-1], devices[0]) for b in blocks]
        targets = [jax.device_put(batch[b, 1:], devices[-1]) for b in blocks]
        denom = n * (batch.shape[1] - 1)
        # forward, layer by layer, keeping every layer's input
        xs = [[None] * len(blocks) for _ in range(n_layer + 1)]
        for r, tok in enumerate(tokens):
            xs[0][r] = embed_f(params["embed"], tok)
        for i in range(n_layer):
            for r in range(len(blocks)):
                xs[i][r] = jax.device_put(xs[i][r], dev(i))
                xs[i + 1][r] = block_f(params[i], xs[i][r])
        # head
        loss, g_head, dxs = 0.0, None, []
        for r in range(len(blocks)):
            (part, (gp, dx)) = head_vg(
                params["head"], jax.device_put(xs[n_layer][r], devices[-1]),
                targets[r], denom)
            loss = loss + part
            g_head = gp if g_head is None else add(g_head, gp)
            dxs.append(dx)
        xs[n_layer] = None
        update("head", g_head, t)
        del g_head
        # backward, each layer updated as soon as its gradient is whole
        for i in reversed(range(n_layer)):
            g_layer = None
            for r in range(len(blocks)):
                gp, dxs[r] = block_b(params[i], xs[i][r],
                                     jax.device_put(dxs[r], dev(i)))
                g_layer = gp if g_layer is None else add(g_layer, gp)
            xs[i] = None
            update(i, g_layer, t)
            del g_layer
        g_embed = None
        for r, tok in enumerate(tokens):
            gp = embed_b(params["embed"], tok,
                         jax.device_put(dxs[r], devices[0]))
            g_embed = gp if g_embed is None else add(g_embed, gp)
        update("embed", g_embed, t)
        del g_embed, dxs
        losses.append(float(loss))

    del mu, nu
    start = split(make_weights())
    update_norms = {}
    for g in params:
        got = diff_norms(params[g], start[g])
        update_norms.update({paths(g, got)[k]: v for k, v in got.items()})
        start[g] = params[g] = None
    return {"losses": losses,
            "grad_norms": {k: float(v) for k, v in grad_norms.items()},
            "update_norms": {k: float(v) for k, v in update_norms.items()}}
