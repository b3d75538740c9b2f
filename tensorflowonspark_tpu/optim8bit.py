"""8-bit blockwise-quantized Adam state (bitsandbytes-style, TPU-native).

At LM scale the Adam states dominate training memory: for the 0.87B
flagship config the float32 m/v are ~7 GB resident.  Storing both
moments as int8 with per-block float32 scales cuts that to ~1.8 GB —
the headroom that decides whether the next model size fits on a chip.

Step time against f32 adamw on this chip: not measured.  The
quantize/requantize arithmetic competes with what the smaller state
saves in bandwidth, so choose adamw8bit for its MEMORY footprint; for
speed the single-pass ``adamw_fused`` is the candidate.

Quantization scheme (chosen for XLA friendliness — everything is a
reshape + absmax + multiply, no tables):

- **m (first moment):** symmetric linear int8 per block of
  ``block_size`` values: ``q = round(m / s * 127)``, ``s = absmax``.
  Momentum is noise-tolerant; linear absmax is plenty (the same
  argument as optax's mu_dtype=bfloat16, just 2x smaller).
- **v (second moment):** nonnegative with a huge dynamic range, and the
  update consumes ``1/(sqrt(v)+eps)`` — linear quantization of v would
  crush small values.  Stored instead as ``sqrt(v)`` quantized with the
  UNSIGNED mapping (``signed=False``: the full int8 range covers
  [0, max], twice the resolution of the symmetric scheme on a
  nonnegative tensor); uniform error in the sqrt domain ≈ uniform error
  in the denominator, which keeps relative update error at the percent
  level (see tests/test_optim8bit.py for the convergence check vs f32
  adam).

The transform is a drop-in `optax.GradientTransformation`; compose decay
/ clipping around it exactly like `optax.scale_by_adam`:

    opt = optim8bit.adamw8bit(3e-4, weight_decay=0.1)
    # or via the factory: optim.make_optimizer("adamw8bit", ...)

Sharding note: quantized payloads are flat [n_blocks, block] views.  By
default the flatten is plain row-major, which only lines up with a param
sharded on dim 0 (fsdp-style row sharding).  For the general fsdp x tp
case — a matrix sharded on BOTH dims — build the optimizer with
``layouts=optim8bit.layouts_for_shardings(params, shardings)``:
quantization blocks are then computed over each logical shard's OWN
elements (shard-major flatten, per-shard padding), so q/scale shard
along their block axis by the param's full spec with zero extra
communication, and the int8 state scales down per chip exactly like f32
moments would.  Pass the SAME layouts tree to
``parallel.train.make_train_step(..., example_params=..., layouts=...)``
so it emits the matching state shardings (explicit, never guessed: an
aligned payload's shape coincides with the row-major one whenever each
shard's elements are a block multiple — the common production case).  A
layout-less 8-bit state under a TP-sharded param REPLICATES with a loud
warning (parallel/train._map_state).
"""
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

DEFAULT_BLOCK = 256


class Quantized(NamedTuple):
    """Blockwise-quantized tensor: int8 payload + per-block f32 scales.
    The original shape is NOT stored — `dequantize` takes it from the
    gradient it is paired with."""
    q: jnp.ndarray       # int8 [n_blocks, block]
    scale: jnp.ndarray   # f32  [n_blocks, 1]


def _pad_len(n, block):
    return (-n) % block


def _shard_major(x, layout):
    """Reshape `x` to [n_shards, elems_per_shard], shard-major.

    `layout` gives per-dim shard counts (n_0, ..., n_{r-1}); every dim
    must divide.  Row k of the result is exactly the elements device k
    owns under a PartitionSpec whose dim-i axes have total size n_i —
    shard order matches GSPMD's (dim-major, then major-to-minor within a
    tuple spec entry), so sharding the result's dim 0 by the concatenated
    spec axes keeps every block device-local.
    """
    r = len(x.shape)
    split = []
    for d, n in zip(x.shape, layout):
        split.extend((n, d // n))
    perm = ([2 * i for i in range(r)] + [2 * i + 1 for i in range(r)])
    return x.reshape(split).transpose(perm).reshape(math.prod(layout), -1)


def _shard_major_inverse(flat, shape, layout):
    """Invert `_shard_major`: [n_shards, elems_per_shard] -> `shape`."""
    r = len(shape)
    sub = tuple(d // n for d, n in zip(shape, layout))
    perm = []
    for i in range(r):
        perm.extend((i, r + i))
    return flat.reshape(tuple(layout) + sub).transpose(perm).reshape(shape)


def quantize(x, block=DEFAULT_BLOCK, signed=True, layout=None):
    """f32/bf16 array -> Quantized, linear absmax per block.

    ``signed=True``: symmetric int8 in [-127, 127] (first moment).
    ``signed=False``: for NONNEGATIVE tensors — the full int8 range maps
    [0, max] via ``q = round(x/s*254) - 127``, halving the step size the
    symmetric scheme would waste on the never-used negative half (matters
    for nu_sqrt, which the update consumes as 1/(sqrt(v)+eps)).

    ``layout`` (per-dim shard counts, from `shard_layout`): blocks are
    computed over each logical shard's own elements — shard-major
    flatten with per-shard padding — so the payload's dim 0 shards by
    the param's full PartitionSpec with no cross-shard blocks.  The
    same `layout` must be passed to `dequantize`.
    """
    layout = _check_layout(layout, x.shape)
    if layout is None:
        flat = x.reshape(1, -1).astype(jnp.float32)
    else:
        flat = _shard_major(x.astype(jnp.float32), layout)
    pad = _pad_len(flat.shape[1], block)
    if pad:
        flat = jnp.pad(flat, ((0, 0), (0, pad)))
    blocks = flat.reshape(-1, block)
    scale = jnp.max(jnp.abs(blocks), axis=1, keepdims=True)
    safe = jnp.where(scale > 0, scale, 1.0)
    if signed:
        q = jnp.clip(jnp.round(blocks / safe * 127.0), -127, 127)
    else:
        q = jnp.clip(jnp.round(blocks / safe * 254.0) - 127.0, -127, 127)
    return Quantized(q.astype(jnp.int8), scale)


def dequantize(qt, shape, dtype=jnp.float32, signed=True, layout=None):
    if signed:
        flat = (qt.q.astype(jnp.float32) * (qt.scale / 127.0)).reshape(-1)
    else:
        flat = ((qt.q.astype(jnp.float32) + 127.0)
                * (qt.scale / 254.0)).reshape(-1)
    layout = _check_layout(layout, shape)
    if layout is None:
        return flat[:math.prod(shape)].reshape(shape).astype(dtype)
    n_shards = math.prod(layout)
    block = qt.q.shape[-1]
    if qt.q.shape[0] != expected_blocks(shape, layout, block):
        raise ValueError(
            f"payload {tuple(qt.q.shape)} was not quantized with layout "
            f"{layout} for shape {shape} (expected "
            f"{expected_blocks(shape, layout, block)} blocks)")
    flat = flat.reshape(n_shards, -1)[:, :math.prod(shape) // n_shards]
    return _shard_major_inverse(flat, shape, layout).astype(dtype)


def _check_layout(layout, shape):
    """Validate `layout` against `shape`; normalize all-ones to None."""
    if layout is None:
        return None
    if len(layout) != len(shape) or any(
            d % n for d, n in zip(shape, layout)):
        raise ValueError(f"layout {layout} does not tile shape "
                         f"{tuple(shape)}")
    return None if all(n == 1 for n in layout) else tuple(layout)


def expected_blocks(shape, layout, block):
    """Block-row count of a payload quantized with `layout` (per-shard
    padding: each shard's elements round up to whole blocks)."""
    n_shards = math.prod(layout)
    per_shard = math.prod(shape) // n_shards
    return n_shards * (-(-per_shard // block))


def shard_layout(shape, sharding):
    """Per-dim shard counts for a param under `sharding`, or None.

    Returns a tuple (n_0, ..., n_{r-1}) — the number of shards along
    each dim implied by the sharding's PartitionSpec over its mesh —
    when at least one dim is sharded and every sharded dim divides.
    None means "no aligned layout": unsharded, scalar, indivisible, or
    a plain positional sharding we cannot read a spec from.
    """
    spec = tuple(getattr(sharding, "spec", ()) or ())
    mesh = getattr(sharding, "mesh", None)
    if mesh is None or not shape:
        return None
    counts = []
    for i, d in enumerate(shape):
        entry = spec[i] if i < len(spec) else None
        names = (() if entry is None
                 else entry if isinstance(entry, tuple) else (entry,))
        n = math.prod(mesh.shape.get(a, 1) for a in names)
        if n > 1 and d % n:
            return None
        counts.append(n)
    if all(n == 1 for n in counts):
        return None
    return tuple(counts)


def layouts_for_shardings(params, shardings):
    """Pytree of `shard_layout` results matching `params`, for the
    ``layouts=`` argument of `adamw8bit` / `scale_by_adam_8bit`.

    Build the optimizer with this whenever params are sharded (fsdp
    and/or tp) so the int8 state shards with them; pass the same
    `shardings` (and `example_params`) to
    `parallel.train.make_train_step`, which recognizes the layout and
    emits matching state shardings.
    """
    return jax.tree_util.tree_map(
        lambda p, s: shard_layout(tuple(getattr(p, "shape", ())), s),
        params, shardings)


class Adam8bitState(NamedTuple):
    count: jnp.ndarray
    mu: object        # pytree of Quantized
    nu_sqrt: object   # pytree of Quantized (stores sqrt(v))


class _UpdOut(NamedTuple):
    """Per-leaf result triple of the update fn (a dedicated type so
    is_leaf can target it without colliding with tuple containers that
    may appear inside the user's parameter pytree)."""
    out: jnp.ndarray
    mu: Quantized
    nu_sqrt: Quantized


def scale_by_adam_8bit(b1=0.9, b2=0.999, eps=1e-8, block_size=DEFAULT_BLOCK,
                       layouts=None):
    """`optax.scale_by_adam` with int8 blockwise state (see module doc).

    ``layouts`` (pytree matching params; leaves are per-dim shard-count
    tuples or None — from `layouts_for_shardings`) aligns each param's
    quantization blocks to its logical shards so the state can shard by
    the param's full PartitionSpec.  Pure layout: the update math is
    identical, only block boundaries move.
    """
    import optax

    def _layout_tree(params):
        if layouts is None:
            return jax.tree_util.tree_map(lambda _: None, params)
        return layouts

    def init_fn(params):
        # mu and nu_sqrt must be INDEPENDENT buffers: sharing one zero
        # tree would donate the same buffer twice under donated train
        # steps (XLA rejects `f(donate(a), donate(a))`)
        def zeros_q(signed):
            return lambda p, lo: quantize(jnp.zeros(p.shape, jnp.float32),
                                          block_size, signed=signed,
                                          layout=lo)

        lts = _layout_tree(params)
        return Adam8bitState(
            jnp.zeros((), jnp.int32),
            jax.tree_util.tree_map(zeros_q(True), params, lts),
            jax.tree_util.tree_map(zeros_q(False), params, lts))

    def update_fn(updates, state, params=None):
        count = state.count + 1

        def upd(g, mu_q, nusq_q, lo):
            g = g.astype(jnp.float32)
            mu = dequantize(mu_q, g.shape, layout=lo)
            v = dequantize(nusq_q, g.shape, signed=False, layout=lo) ** 2
            mu = b1 * mu + (1 - b1) * g
            v = b2 * v + (1 - b2) * g * g
            mu_hat = mu / (1 - b1 ** count.astype(jnp.float32))
            v_hat = v / (1 - b2 ** count.astype(jnp.float32))
            out = mu_hat / (jnp.sqrt(v_hat) + eps)
            return _UpdOut(out, quantize(mu, block_size, layout=lo),
                           quantize(jnp.sqrt(v), block_size, signed=False,
                                    layout=lo))

        # tree_map flattens the companion trees UP TO `updates`' leaf
        # positions, so each call sees the whole Quantized subtree for
        # its parameter; `flat` then holds one _UpdOut per leaf position
        # (a dedicated type: keying is_leaf on bare tuples would misfire
        # on tuple CONTAINERS inside the parameter pytree)
        flat = jax.tree_util.tree_map(
            upd, updates, state.mu, state.nu_sqrt, _layout_tree(updates))
        is_out = lambda x: isinstance(x, _UpdOut)  # noqa: E731
        out = jax.tree_util.tree_map(lambda t: t.out, flat, is_leaf=is_out)
        mu = jax.tree_util.tree_map(lambda t: t.mu, flat, is_leaf=is_out)
        nusq = jax.tree_util.tree_map(lambda t: t.nu_sqrt, flat,
                                      is_leaf=is_out)
        return out, Adam8bitState(count, mu, nusq)

    return optax.GradientTransformation(init_fn, update_fn)


def adamw8bit(learning_rate, b1=0.9, b2=0.999, eps=1e-8, weight_decay=0.0,
              mask=None, block_size=DEFAULT_BLOCK, layouts=None):
    """AdamW with 8-bit state: scale_by_adam_8bit -> weight decay -> lr."""
    import optax

    chain = [scale_by_adam_8bit(b1, b2, eps, block_size, layouts=layouts)]
    if weight_decay:
        chain.append(optax.add_decayed_weights(weight_decay, mask))
    chain.append(optax.scale_by_learning_rate(learning_rate))
    return optax.chain(*chain)
