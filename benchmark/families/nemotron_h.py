"""Family `nemotron_h`: a hybrid decoder whose layers each hold ONE part,
as `hybrid_override_pattern` says: a Mamba-2 mixer (`M`), sparse experts
(`E`: sigmoid top-k routed non-gated relu-squared experts under a selection
bias, the routed sum scaled, a shared expert of its own published width)
or attention (`*`: grouped query heads, no positions); an untied head; one
chip's share of an expert-parallel deployment.

Read from the configuration file's published keys (`hidden_size`,
`num_attention_heads`, `num_key_value_heads`, `head_dim`,
`mamba_num_heads`, `mamba_head_dim`, `n_groups`, `ssm_state_size`,
`conv_kernel`, `chunk_size`, `time_step_min` / `_max` / `_floor`,
`moe_intermediate_size`, `moe_shared_expert_intermediate_size`,
`num_experts_per_tok`, `routed_scaling_factor`, `norm_eps`,
`hybrid_override_pattern`) and from its cut: `num_hidden_layers` (the
layers held, from `deployment.this_chip.first_layer` on), `n_routed_experts`
(the experts held, out of `published.n_routed_experts`, which stays the
router's width), `vocab_size` (the rows of the table and of the head held).

- `param_shapes`: the parameters and their initialisers, under the paths of
  the program's own tree (`layer_0/mamba/in_proj/kernel`);
- `build`: the program under test: `models/transformer.Transformer`,
  `ops/xent.fused_unembed_xent` over the head, `optim.make_optimizer`, the
  routing counters of `moe_stats`;
- `step_work`: operations and bytes one step REQUIRES, from shapes alone;
- `reference`: the plain float32 `jax.numpy` forward, backward and AdamW,
  which imports nothing of the program: the state space as the recurrence
  itself, a scan over positions checkpointed a chunk at a time (not the
  chunked algorithm), attention a head at a time, every held expert
  computed densely for every token and masked by the picks.
"""
import math

import harness  # the benchmark's own: finds a family's file by name

# the sparse family's `_rms_norm`, `visible_pairs`, and through it the dense
# family's `_matmul` (f32 | bf16 | fp8) and AdamW constants
moe = harness.load_module("families", "moe_lm")
lm = moe.lm

# what `reference(fault=...)` can plant (tests and `tests/faults.py`;
# `program.fault`, which no file sets, plants one in a whole run)
FAULTS = ("no_decay", "state_per_chunk", "no_d_skip", "no_gate",
          "norm_all_lanes", "group_map", "dt_no_softplus", "relu_not_relu2",
          "no_shared_expert", "bias_in_weights")


def _sizes(cfg):
    n = cfg["num_hidden_layers"]
    first = cfg["deployment"]["this_chip"]["first_layer"]
    mh, mp = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, state = cfg["n_groups"], cfg["ssm_state_size"]
    return {"d": cfg["hidden_size"], "h": cfg["num_attention_heads"],
            "kv": cfg["num_key_value_heads"], "hd": cfg["head_dim"],
            "f": cfg["moe_intermediate_size"],
            "fs": cfg["moe_shared_expert_intermediate_size"],
            "v": cfg["vocab_size"], "n": n,
            "held": cfg["n_routed_experts"],
            "e": cfg["published"]["n_routed_experts"],
            "k": cfg["num_experts_per_tok"],
            "scale": cfg["routed_scaling_factor"],
            "off": cfg["deployment"]["this_chip"]["expert_offset"],
            "parts": cfg["hybrid_override_pattern"][first:first + n],
            "mh": mh, "mp": mp, "g": g, "state": state,
            "inner": mh * mp, "width": mh * mp + 2 * g * state,
            "taps": cfg["conv_kernel"], "chunk": cfg["chunk_size"]}


def dt_bias(cfg):
    """The inverse softplus of `mamba_num_heads` steps spread log-evenly
    over [time_step_min, time_step_max], head h at the (h + 0.5) / heads
    point, floored at time_step_floor (the file's `assumed.init`): a
    float32 array, which `weights.make` takes as a constant's value."""
    import numpy as np

    heads = cfg["mamba_num_heads"]
    lo, hi = math.log(cfg["time_step_min"]), math.log(cfg["time_step_max"])
    out = []
    for h in range(heads):
        dt = max(math.exp(lo + (h + 0.5) / heads * (hi - lo)),
                 cfg["time_step_floor"])
        out.append(dt + math.log(-math.expm1(-dt)))
    return np.asarray(out, np.float32)


def _layer_shapes(z, part):
    d = z["d"]
    if part == "M":
        return {"mamba/in_proj/kernel": (d, z["inner"] + z["width"] + z["mh"]),
                "mamba/conv_taps": (z["width"], z["taps"]),
                "mamba/conv_bias": (z["width"],),
                "mamba/dt_bias": (z["mh"],), "mamba/A_log": (z["mh"],),
                "mamba/D": (z["mh"],), "mamba/norm": (z["inner"],),
                "mamba/out_proj/kernel": (z["inner"], d)}
    if part == "E":
        return {"moe/router/kernel": (d, z["e"]), "moe/expert_bias": (z["e"],),
                "moe/experts_wi/kernel": (z["held"], d, z["f"]),
                "moe/experts_wo/kernel": (z["held"], z["f"], d),
                "moe/shared/wi/kernel": (d, z["fs"]),
                "moe/shared/wo/kernel": (z["fs"], d)}
    if part == "*":
        qd, kvd = z["h"] * z["hd"], z["kv"] * z["hd"]
        return {"attn/query/kernel": (d, qd), "attn/key/kernel": (d, kvd),
                "attn/value/kernel": (d, kvd), "attn/out/kernel": (qd, d)}
    raise ValueError(f"no layer part {part!r} in this family")


def param_shapes(cfg):
    import numpy as np

    z, init = _sizes(cfg), cfg["init"]
    conv = ("normal", init["conv_std"])
    inits = {"kernel": ("normal", init["kernel_std"]),
             "expert_bias": ("normal", init["expert_bias_std"]),
             "conv_taps": conv, "conv_bias": conv,
             "dt_bias": ("const", dt_bias(cfg)),
             "A_log": ("const", np.log(np.arange(1, z["mh"] + 1,
                                                 dtype=np.float32))),
             "D": ("const", 1.0), "norm": ("const", 1.0),
             "scale": ("const", 1.0)}
    out = {"token_embed/embedding": ((z["v"], z["d"]), (
               "normal", init["embedding_std"])),
           "ln_f/scale": ((z["d"],), inits["scale"]),
           "lm_head/kernel": ((z["d"], z["v"]), inits["kernel"])}
    for i, part in enumerate(z["parts"]):
        leaves = dict(_layer_shapes(z, part), **{"norm/scale": (z["d"],)})
        for name, shape in leaves.items():
            out[f"layer_{i}/{name}"] = (shape, inits[name.rsplit("/", 1)[-1]])
    return out


# ------------------------------------------------------------ program ----

def build(cfg):
    """`(loss_fn, optimizer)` of the program under test.  The loss takes
    the hidden states and fuses the untied head into the cross entropy.
    Where the file expects kernels and the backend is the chip, a program
    whose Mamba mixers did not take the SSD kernels is refused when the step
    is traced."""
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
        if on_chip and not trace.counters().snapshot().get(
                "ssd.calls.kernel"):
            raise RuntimeError("the Mamba mixers took no SSD kernel: they "
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

def ssd_work(cfg, batch):
    """`{"flops", "bytes"}` of the SSD kernels of one step (`ops/ssd.py`),
    forward and backward, from shapes: the chunked algorithm's products
    over whole chunks of L (C B^T once a group; a head's intra-chunk
    product, the state's way in and out; the backward's transposed
    products and the carried gradient), and the bytes the kernels read and
    write: x dt, B, C (the backward also transposed), the running sums, y,
    the chunk states in float32, and the gradients."""
    z = _sizes(cfg)
    seq = cfg["program"]["seq_len"]
    L, n_m = z["chunk"], z["parts"].count("M")
    chunks = batch * -(-seq // L)
    H, G, P, N = z["mh"], z["g"], z["mp"], z["state"]
    fwd = G * 2 * L * L * N + H * (2 * L * L * P + 2 * 2 * L * N * P)
    bwd = G * 2 * 2 * L * L * N + H * (3 * 2 * L * L * P + 2 * 2 * L * L * N
                                       + 5 * 2 * L * N * P)
    act, tokens = 2, chunks * L
    heads, groups, sums = tokens * H * P * act, tokens * G * N * act, \
        tokens * H * 4
    states = chunks * H * N * P * 4
    fwd_bytes = heads + sums + 2 * groups + heads + states
    bwd_bytes = (heads + sums + 4 * groups + states + heads
                 + heads + sums + 2 * groups)
    return {"flops": n_m * chunks * (fwd + bwd),
            "bytes": n_m * (fwd_bytes + bwd_bytes)}


def step_work(cfg, batch):
    """What one step of `batch` rows requires, from shapes: no embedding
    gather, no recomputation, forward and backward three times the
    forward's multiply-adds for the products; the SSD scan as
    `ssd_work` counts it.  Attention counts the causal pairs; the experts
    count the EXPECTED local pairs, `T x k x held / E` a sparse part
    (uniform routing: `moe_local_pairs_pct.nem` says how near the run
    came).  The convolution's taps, the gates and the norms are left
    out."""
    z = _sizes(cfg)
    d, qd, kvd = z["d"], z["h"] * z["hd"], z["kv"] * z["hd"]
    seq = cfg["program"]["seq_len"]
    tokens = batch * seq
    n_m, n_e, n_a = (z["parts"].count(c) for c in "ME*")
    pairs = n_a * moe.visible_pairs(seq) * batch
    local = tokens * z["k"] * z["held"] // z["e"]        # a sparse part
    proj = (n_m * (d * (z["inner"] + z["width"] + z["mh"]) + z["inner"] * d)
            + n_a * (2 * d * qd + 2 * d * kvd)
            + n_e * (d * z["e"] + 2 * d * z["fs"]) + d * z["v"])
    attn = 12 * pairs * qd                  # 3 x (QK^T + PV), 2 a mult-add
    gmm = n_e * local * 2 * 6 * d * z["f"]  # up, down; fwd + 2 bwd
    ssd = ssd_work(cfg, batch)
    n_params = sum(math.prod(shape) for shape, _ in
                   param_shapes(cfg).values())
    act = 2                                 # bytes of an activation (bf16)
    return {
        "flops": 6 * proj * tokens + attn + gmm + ssd["flops"],
        "n_params": n_params,
        "visible_pairs": pairs, "local_pairs": n_e * local,
        # as `moe_lm.step_work`: six tensors of the query's width and six
        # of the narrow key/value width, forward and backward
        "flash": {"flops": attn,
                  "bytes": n_a * 6 * tokens * (qd + kvd) * act},
        "moe_gmm": {"flops": gmm,
                    "bytes": n_e * 2 * 3 * (z["held"] * d * z["f"]
                                            + local * (d + z["f"])) * act},
        "ssd": ssd,
        "adamw": {"bytes": n_params * lm._adamw_bytes(cfg)},
    }


# ---------------------------------------------------------- reference ----

def _silu(x):
    import jax

    return x * jax.nn.sigmoid(x)


def _recurrence(x, dt, a, b, c, z, fault):
    """The state space itself, position by position: `x` [r, S, H, P],
    `dt` [r, S, H], `a` [H], `b`, `c` [r, S, G, N] -> y [r, S, H, P] with
    `h_t = exp(dt_t a) h_{t-1} + dt_t x_t b_t^T`, `y_t = h_t c_t`, head h
    reading group h // (H / G).  A scan over positions inside a scan over
    chunks of `chunk_size`, each chunk checkpointed: the backward pass
    keeps a state a chunk and computes a chunk's steps again."""
    import jax
    import jax.numpy as jnp

    r, s, heads, _ = x.shape
    per, L = heads // z["g"], z["chunk"]
    pad = -s % L
    if pad:
        x, dt, b, c = (jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
                       for t in (x, dt, b, c))
    if fault == "group_map":                 # head h reads group h % G
        group = jnp.arange(heads) % z["g"]
    else:
        group = jnp.arange(heads) // per

    def step(h, t):
        xt, dtt, bt, ct = t                  # [r, H, P], [r, H], [r, G, N]
        keep = jnp.ones_like(dtt) if fault == "no_decay" else jnp.exp(dtt * a)
        h = keep[..., None, None] * h + (dtt[..., None] * xt)[..., None] \
            * bt[:, group, None, :]
        return h, jnp.sum(h * ct[:, group, None, :], axis=-1)

    @jax.checkpoint
    def chunk(h, t):
        if fault == "state_per_chunk":       # nothing carried across chunks
            h = jnp.zeros_like(h)
        return jax.lax.scan(step, h, t)

    def by_chunk(t):                         # [r, S, ...] -> [n, L, r, ...]
        t = jnp.moveaxis(t, 1, 0)
        return t.reshape((-1, L) + t.shape[1:])

    h0 = jnp.zeros((r, heads, x.shape[3], b.shape[3]), jnp.float32)
    _, y = jax.lax.scan(chunk, h0, tuple(by_chunk(t) for t in (x, dt, b, c)))
    return jnp.moveaxis(y.reshape((-1,) + y.shape[2:]), 0, 1)[:, :s]


def _mamba(p, u, z, eps, mm, fault):
    """[B, S, d] -> [B, S, d]: the Mamba-2 mixer, as the file's
    `assumed.mamba` writes it."""
    import jax
    import jax.numpy as jnp

    nb, s, _ = u.shape
    inner, width, heads, g = z["inner"], z["width"], z["mh"], z["g"]
    proj = mm(u, p["mamba/in_proj/kernel"])
    gate, xbc, dt = (proj[..., :inner], proj[..., inner:inner + width],
                     proj[..., inner + width:])
    w, taps = p["mamba/conv_taps"], z["taps"]
    conv = jnp.zeros_like(xbc) + p["mamba/conv_bias"]
    for j in range(taps):
        back = taps - 1 - j
        conv = conv + jnp.concatenate(
            [jnp.zeros_like(xbc[:, :back]), xbc[:, :s - back]], axis=1) \
            * w[:, j]
    xbc = _silu(conv)
    x = xbc[..., :inner].reshape(nb, s, heads, z["mp"])
    b = xbc[..., inner:inner + g * z["state"]].reshape(nb, s, g, z["state"])
    c = xbc[..., inner + g * z["state"]:].reshape(nb, s, g, z["state"])
    dt = dt + p["mamba/dt_bias"]
    # planted: the step without its softplus, clamped to time_step_limit
    # [0, inf) (unclamped, exp(dt A) > 1 overflows the state to NaN)
    dt = (jnp.maximum(dt, 0.0) if fault == "dt_no_softplus"
          else jax.nn.softplus(dt))
    y = _recurrence(x, dt, -jnp.exp(p["mamba/A_log"]), b, c, z, fault)
    if fault != "no_d_skip":
        y = y + p["mamba/D"][:, None] * x
    y = y.reshape(nb, s, inner)
    if fault != "no_gate":
        y = y * _silu(gate)
    groups = 1 if fault == "norm_all_lanes" else g
    y = y.reshape(nb, s, groups, inner // groups)
    y = y / jnp.sqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps)
    return mm(y.reshape(nb, s, inner) * p["mamba/norm"],
              p["mamba/out_proj/kernel"])


def _attention(p, u, z, mm):
    """[B, S, d] -> [B, S, d]: causal softmax a head at a time over grouped
    key/value heads, no positions, so the [S, S] scores of one head are all
    that is live (and recomputed in the backward pass)."""
    import jax
    import jax.numpy as jnp

    b, s, _ = u.shape
    heads, kv, hd = z["h"], z["kv"], z["hd"]
    q = mm(u, p["attn/query/kernel"]).reshape(b, s, heads, hd)
    k = mm(u, p["attn/key/kernel"]).reshape(b, s, kv, hd)
    v = mm(u, p["attn/value/kernel"]).reshape(b, s, kv, hd)
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
    top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20) * z["scale"]
    full = jnp.sum(jax.nn.one_hot(picks, z["e"], dtype=top.dtype)
                   * top[..., None], axis=-2)          # [T, E]
    return full[:, z["off"]:z["off"] + z["held"]], picks


def _sparse_ff(p, hn, z, mm, fault=None):
    """Every held expert for every token, masked by the picks, and the
    shared expert once; each `down(relu(up x)^2)`."""
    import jax
    import jax.numpy as jnp

    def act(h):
        h = jax.nn.relu(h)
        return h if fault == "relu_not_relu2" else h * h

    weights, _ = route(p, hn, z, fault)

    @jax.checkpoint
    def one(y, args):
        wu, wd, w = args
        return y + w[:, None] * mm(act(mm(hn, wu)), wd), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(hn), (
        p["moe/experts_wi/kernel"], p["moe/experts_wo/kernel"], weights.T))
    if fault == "no_shared_expert":
        return y
    return y + mm(act(mm(hn, p["moe/shared/wi/kernel"])),
                  p["moe/shared/wo/kernel"])


def _block(p, x, z, eps, mm, fault=None):
    """One layer on [B, S, d] float32, `x + part(RMSNorm(x))`; what the
    leaves of `p` are says which part it holds."""
    b, s, d = x.shape
    u = moe._rms_norm(x, p["norm/scale"], eps)
    if "mamba/D" in p:
        return x + _mamba(p, u, z, eps, mm, fault)
    if "attn/out/kernel" in p:
        return x + _attention(p, u, z, mm)
    return x + _sparse_ff(p, u.reshape(b * s, d), z, mm,
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
    n_layer, eps = z["n"], cfg["norm_eps"]
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

    block = functools.partial(_block, z=z, eps=eps, mm=mm, fault=fault)
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
