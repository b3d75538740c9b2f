"""Family `resnet`: bottleneck ResNet (v1.5) with GroupNorm on uint8 images.

As `transformer_lm.py`: `param_shapes`, `build` (the program:
`models/resnet.ResNet`, `image.normalize_batch`, optax SGD through
`optim.make_optimizer`), `step_work` (required operations from shapes) and
`reference` (plain float32 `jax.numpy`, importing nothing of the program).
"""
import math

MEAN = (123.675, 116.28, 103.53)     # ImageNet, on the 0..255 scale
STD = (58.395, 57.12, 57.375)


def _convs(cfg):
    """Every convolution, in order: `(path, kh, cin, cout, stride,
    input side)`, then the classifier's `(cin, cout)`."""
    side = cfg["image_size"]
    f, exp = cfg["num_filters"], cfg["bottleneck_expansion"]
    convs = [("conv_init", 7, 3, f, 2, side)]
    side //= 4                                  # stem stride 2, pool stride 2
    cin = f
    for i, n in enumerate(cfg["stage_sizes"]):
        mid = f * 2 ** i
        for j in range(n):
            stride = 2 if (i > 0 and j == 0) else 1
            pre = f"stage{i}_block{j}"
            convs.append((f"{pre}/Conv_0", 1, cin, mid, 1, side))
            convs.append((f"{pre}/Conv_1", 3, mid, mid, stride, side))
            if j == 0:
                convs.append((f"{pre}/conv_proj", 1, cin, mid * exp, stride,
                              side))
            side //= stride
            convs.append((f"{pre}/Conv_2", 1, mid, mid * exp, 1, side))
            cin = mid * exp
    return convs, (cin, cfg["num_classes"])


def _norm_of(conv_path):
    pre, _, name = conv_path.rpartition("/")
    norm = {"conv_init": "norm_init", "conv_proj": "norm_proj"}.get(
        name, name.replace("Conv_", "ChannelGroupNorm_"))
    return f"{pre}/{norm}/gn" if pre else f"{norm}/gn"


def param_shapes(cfg):
    convs, (cin, classes) = _convs(cfg)
    out = {}
    for path, k, ci, co, _, _ in convs:
        out[f"{path}/kernel"] = ((k, k, ci, co),
                                 ("normal", math.sqrt(2.0 / (k * k * ci))))
        # the last normalisation of a block starts small, as ResNets are
        # trained (zero in Goyal et al. and in the program's own init; here
        # not quite, so that every leaf has a gradient at step 1)
        last = path.endswith("/Conv_2")
        out[f"{_norm_of(path)}/scale"] = ((co,), ("const", cfg.get(
            "last_norm_scale_init", 1.0) if last else 1.0))
        out[f"{_norm_of(path)}/bias"] = ((co,), ("const", 0.0))
    out["head/kernel"] = ((cin, classes), ("normal", 0.01))
    out["head/bias"] = ((classes,), ("const", 0.0))
    return out


# ------------------------------------------------------------ program ----

def build(cfg):
    import jax
    import jax.numpy as jnp

    from tensorflowonspark_tpu import image
    from tensorflowonspark_tpu.models.resnet import ResNet
    from tensorflowonspark_tpu.optim import make_optimizer

    m = dict(cfg["program"]["model"])
    m["stage_sizes"] = tuple(m["stage_sizes"])
    model = ResNet(**m)
    classes = m["num_classes"]

    def loss_fn(p, batch, rng):
        pixels, labels = batch
        logits = model.apply({"params": p}, image.normalize_batch(
            pixels, dtype=m["dtype"]))
        onehot = jax.nn.one_hot(labels, classes, dtype=jnp.float32)
        return -jnp.mean(jnp.sum(
            jax.nn.log_softmax(logits.astype(jnp.float32)) * onehot, axis=-1))

    o = dict(cfg["program"]["optimizer"])
    opt, _ = make_optimizer(o.pop("name"), **o)
    return loss_fn, opt


# --------------------------------------------------------------- work ----

def step_work(cfg, batch):
    """Convolution and classifier operations of forward and backward (three
    times the forward's, less the stem's input gradient, which nothing
    needs), two per multiply-add, for `batch` images."""
    convs, (cin, classes) = _convs(cfg)
    macs = cin * classes
    for _, k, ci, co, stride, side in convs:
        macs += (side // stride) ** 2 * k * k * ci * co
    _, k, ci, co, stride, side = convs[0]
    stem = (side // stride) ** 2 * k * k * ci * co
    n_params = sum(math.prod(s) for s, _ in param_shapes(cfg).values())
    return {"flops": 2 * (3 * macs - stem) * batch,
            "forward_macs_per_image": macs, "n_params": n_params}


# ---------------------------------------------------------- reference ----

def _conv_fn(precision):
    import jax
    import jax.numpy as jnp

    def q(x):
        # fp8 (e4m3) keeps four significant bits; its range is not the
        # limit where a tensor is scaled to it, so only they are modelled.
        # Straight through: the backward pass sees the rounded values and
        # passes gradients on (rounding alone has gradient nought).
        m, e = jnp.frexp(x)
        return x + jax.lax.stop_gradient(
            jnp.ldexp(jnp.round(m * 16.0) / 16.0, e) - x)

    def conv(x, w, stride):
        if precision == "fp8":     # the control
            x, w = q(x), q(w)
        elif precision == "bf16":
            # the look of PERF.md section 4: the precision the
            # configuration states (bfloat16 convolutions between float32
            # normalisations), in plain code, to tell rounding from a fault
            x, w = x.astype(jnp.bfloat16), w.astype(jnp.bfloat16)
        elif precision != "f32":
            raise ValueError(f"precision {precision!r}")
        return jax.lax.conv_general_dilated(
            x, w, (stride, stride), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=jax.lax.Precision.HIGHEST).astype(jnp.float32)

    return conv


def _forward(p, pixels, cfg, conv):
    import jax
    import jax.numpy as jnp

    gs, eps = cfg["group_size"], cfg["norm_epsilon"]

    def gn(x, path):
        n, h, w, c = x.shape
        g = x.reshape(n, h, w, c // gs, gs)
        mean = jnp.mean(g, axis=(1, 2, 4), keepdims=True)
        var = jnp.mean(jnp.square(g - mean), axis=(1, 2, 4), keepdims=True)
        g = (g - mean) / jnp.sqrt(var + eps)
        return g.reshape(n, h, w, c) * p[f"{path}/scale"] + p[f"{path}/bias"]

    def cn(x, path, stride=1):
        return gn(conv(x, p[f"{path}/kernel"], stride), _norm_of(path))

    x = (pixels.astype(jnp.float32) - jnp.asarray(MEAN)) / jnp.asarray(STD)
    x = jax.nn.relu(cn(x, "conv_init", 2))
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), "SAME")
    for i, n in enumerate(cfg["stage_sizes"]):
        for j in range(n):
            stride = 2 if (i > 0 and j == 0) else 1
            pre = f"stage{i}_block{j}"
            y = jax.nn.relu(cn(x, f"{pre}/Conv_0"))
            y = jax.nn.relu(cn(y, f"{pre}/Conv_1", stride))
            y = cn(y, f"{pre}/Conv_2")
            if j == 0:
                x = cn(x, f"{pre}/conv_proj", stride)
            x = jax.nn.relu(x + y)
    x = jnp.mean(x, axis=(1, 2))
    return jnp.matmul(x, p["head/kernel"],
                      precision=jax.lax.Precision.HIGHEST) + p["head/bias"]


def reference(cfg, make_weights, batches, precision="f32", devices=None,
              row_block=32, rows=None, keep_grads=False):
    """Follow `len(batches)` SGD-momentum steps in plain float32, the
    gradient summed over blocks of `row_block` images (GroupNorm keeps the
    images independent).  Returns what `transformer_lm.reference` returns,
    and with `keep_grads` the first step's gradient itself (`first_grads`,
    on the host)."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    o = cfg["program"]["optimizer"]
    lr, momentum = o["learning_rate"], o["momentum"]
    conv = _conv_fn(precision)

    @functools.partial(jax.jit, static_argnums=(3,))
    def loss_grad(p, pixels, labels, denom):
        def loss(p_):
            logp = jax.nn.log_softmax(_forward(p_, pixels, cfg, conv))
            return -jnp.sum(jnp.take_along_axis(
                logp, labels[:, None], axis=-1)) / denom
        return jax.value_and_grad(loss)(p)

    @jax.jit
    def sgd(p, trace, g):
        trace = {k: g[k] + momentum * trace[k] for k in p}
        return {k: p[k] - lr * trace[k] for k in p}, trace

    def norms(a, b=None):
        return {k: float(jnp.sqrt(jnp.sum(jnp.square(
            a[k] - (b[k] if b else 0.0))))) for k in a}

    start = make_weights()
    params = start
    trace = {k: jnp.zeros_like(v) for k, v in params.items()}
    losses, grad_norms = [], None
    for pixels, labels in batches:
        if rows:
            pixels, labels = pixels[:rows], labels[:rows]
        n = len(labels)
        loss, grads = 0.0, None
        for i in range(0, n, row_block):
            part, g = loss_grad(params, pixels[i:i + row_block],
                                labels[i:i + row_block], n)
            loss = loss + part
            grads = g if grads is None else \
                {k: grads[k] + g[k] for k in g}
        if grad_norms is None:
            grad_norms = norms(grads)
            if keep_grads:
                first = {k: np.asarray(v) for k, v in grads.items()}
        params, trace = sgd(params, trace, grads)
        losses.append(float(loss))
    out = {"losses": losses, "grad_norms": grad_norms,
           "update_norms": norms(params, start)}
    if keep_grads:
        out["first_grads"] = first
    return out
