"""Pallas TPU grouped (ragged) matmul over the experts a chip holds.

`grouped_matmul(lhs [M, K], rhs [G, K, N], group_sizes [G])` multiplies the
rows of group `g` (rows are sorted by group: group `g` is rows
`[sum(sizes[:g]), sum(sizes[:g + 1]))`) by `rhs[g]`.  `M` is static and may
exceed `sum(group_sizes)`: a dropless MoE layer sizes its row buffer for the
worst routing and fills what the router gives.  The kernels walk a list of
(row tile, group) work items made from `group_sizes` on the device and the
grid's work dimension is the length of that list, so **rows past the last
group cost nothing and are not written**: they hold whatever the buffer
held, and the caller selects them away (`jnp.where`, never a multiply).

Three products, float32 accumulation, named for the device trace:

- `moe_gmm`: the forward, and with the weights read transposed the gradient
  of the rows (`d_lhs = d_out @ rhs[g].T`);
- `moe_tgmm`: the gradient of the weights (`d_rhs[g] = lhs_g.T @ d_out_g`),
  an empty group's written as zeros.

A row tile that holds the end of one group and the start of the next is
visited once for each; the visits are consecutive, so the output tile stays
in VMEM between them and each writes its own rows (the scheme of
`jax.experimental.pallas.ops.tpu.megablox`, cut down to what one chip
needs: no group offset, no existing output, tiles that divide the widths).
Off the chip the same kernels run in interpret mode.  Under a mesh it
raises: a Pallas call cannot be partitioned by GSPMD, and the exchange
between expert-parallel chips is not written.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE_M = 512          # rows a work item; a group of 2048 rows wastes little
TILE_KN = 2304        # the most of a width one tile takes: an expert's whole
# [2304, 896] weight is one block, so the row tiles of one group that follow
# one another find it in VMEM (a block index that stays starts no copy)
VMEM_BYTES = 64 << 20  # of the chip's 128 MiB; the default scope is 16


def _pick_tile(dim, most=TILE_KN):
    """The whole width where it is small, else the largest multiple of 128
    under `most` that divides it (the kernels mask no remainder)."""
    if dim <= most:
        return dim
    for t in range(most - most % 128, 0, -128):
        if dim % t == 0:
            return t
    return dim


def work_items(group_sizes, m, tm, visit_empty):
    """`(offsets [G+1], group_of [L], tile_of [L], n)`: the first `n` of the
    `L = m // tm + G - 1` work items are (row tile, group) pairs in row
    order, one for each tile a group's rows touch; with `visit_empty` an
    empty group gets one item (its gradient has to be written as zeros)."""
    g = group_sizes.shape[0]
    tiles_m = m // tm
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    first = starts // tm
    n_tiles = jnp.where(group_sizes > 0, (ends + tm - 1) // tm - first,
                        1 if visit_empty else 0)
    tile_ends = jnp.cumsum(n_tiles)
    item = jnp.arange(tiles_m + g - 1, dtype=jnp.int32)
    group_of = jnp.minimum(
        jnp.searchsorted(tile_ends, item, side="right"), g - 1)
    tile_of = first[group_of] + item - (tile_ends - n_tiles)[group_of]
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return (offsets.astype(jnp.int32), group_of.astype(jnp.int32),
            jnp.clip(tile_of, 0, tiles_m - 1).astype(jnp.int32),
            tile_ends[-1])


def _rows_of_group(offsets, group_of, tile_of, w, tm, width):
    """[tm, width] mask: the rows of work item `w`'s tile that belong to
    its group."""
    grp = group_of[w]
    rows = tile_of[w] * tm + jax.lax.broadcasted_iota(
        jnp.int32, (tm, width), 0)
    return jnp.logical_and(rows >= offsets[grp], rows < offsets[grp + 1])


def _gmm_kernel(offsets, group_of, tile_of, lhs, rhs, out, acc, *, tm,
                transpose_rhs):
    w, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)

    acc[...] += jax.lax.dot_general(
        lhs[...], rhs[...],
        (((1,), (1 if transpose_rhs else 0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(ki == pl.num_programs(2) - 1)
    def _():
        mine = _rows_of_group(offsets, group_of, tile_of, w, tm,
                              out.shape[1])
        out[...] = jax.lax.select(
            mine, acc[...], out[...].astype(jnp.float32)).astype(out.dtype)


def _gmm(lhs, rhs, group_sizes, transpose_rhs, interpret):
    m, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tm, tk, tn = min(TILE_M, m), _pick_tile(k), _pick_tile(n)
    offsets, group_of, tile_of, n_items = work_items(
        group_sizes, m, tm, visit_empty=False)
    rhs_block = (None, tn, tk) if transpose_rhs else (None, tk, tn)

    def rhs_index(ni, w, ki, offsets, group_of, tile_of):
        return (group_of[w], ni, ki) if transpose_rhs else \
            (group_of[w], ki, ni)

    return pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm, transpose_rhs=transpose_rhs),
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[
                pl.BlockSpec((tm, tk), lambda ni, w, ki, o, g, t:
                             (t[w], ki)),
                pl.BlockSpec(rhs_block, rhs_index)],
            out_specs=pl.BlockSpec((tm, tn), lambda ni, w, ki, o, g, t:
                                   (t[w], ni)),
            grid=(n // tn, n_items, k // tk),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_BYTES),
        interpret=interpret,
        name="moe_gmm",
    )(offsets, group_of, tile_of, lhs, rhs)


def _tgmm_kernel(offsets, group_of, tile_of, lhs, grad, out, acc, *, tm):
    w = pl.program_id(2)
    last = pl.num_programs(2) - 1
    grp = group_of[w]
    before = group_of[jnp.maximum(w - 1, 0)]
    after = group_of[jnp.minimum(w + 1, last)]

    @pl.when(jnp.logical_or(w == 0, before != grp))
    def _():
        acc[...] = jnp.zeros_like(acc)

    @pl.when(offsets[grp + 1] > offsets[grp])
    def _():
        # rows of other groups, and rows no group wrote, are selected
        # away on both sides (a product with 0 would keep a NaN)
        def mine(ref):
            rows = _rows_of_group(offsets, group_of, tile_of, w, tm,
                                  ref.shape[1])
            return jnp.where(rows, ref[...], jnp.zeros_like(ref))

        acc[...] += jax.lax.dot_general(
            mine(lhs), mine(grad), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(jnp.logical_or(w == last, after != grp))
    def _():
        out[...] = acc[...].astype(out.dtype)


def _tgmm(lhs, grad, group_sizes, interpret):
    m, k = lhs.shape
    n = grad.shape[1]
    tm, tk, tn = min(TILE_M, m), _pick_tile(k), _pick_tile(n)
    offsets, group_of, tile_of, n_items = work_items(
        group_sizes, m, tm, visit_empty=True)
    return pl.pallas_call(
        functools.partial(_tgmm_kernel, tm=tm),
        out_shape=jax.ShapeDtypeStruct((group_sizes.shape[0], k, n),
                                       lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[
                pl.BlockSpec((tm, tk), lambda ni, ki, w, o, g, t:
                             (t[w], ki)),
                pl.BlockSpec((tm, tn), lambda ni, ki, w, o, g, t:
                             (t[w], ni))],
            out_specs=pl.BlockSpec((None, tk, tn),
                                   lambda ni, ki, w, o, g, t:
                                   (g[w], ki, ni)),
            grid=(n // tn, k // tk, n_items),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_BYTES),
        interpret=interpret,
        name="moe_tgmm",
    )(offsets, group_of, tile_of, lhs, grad)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _grouped(lhs, rhs, group_sizes, interpret):
    return _gmm(lhs, rhs, group_sizes, False, interpret)


def _grouped_fwd(lhs, rhs, group_sizes, interpret):
    return _grouped(lhs, rhs, group_sizes, interpret), (lhs, rhs,
                                                        group_sizes)


def _grouped_bwd(interpret, res, g):
    lhs, rhs, group_sizes = res
    return (_gmm(g, rhs, group_sizes, True, interpret),
            _tgmm(lhs, g, group_sizes, interpret).astype(rhs.dtype), None)


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


def grouped_matmul(lhs, rhs, group_sizes, interpret=None):
    """`out[r] = lhs[r] @ rhs[g]` for the rows `r` of each group `g`:
    `lhs` [M, K] sorted by group, `rhs` [G, K, N], `group_sizes` [G] int32
    with `sum <= M`.  Returns [M, N] in `lhs`'s type; rows past the last
    group are NOT written (select them away).  Differentiable in `lhs` and
    `rhs`.  `interpret=None`: native on TPU, the interpreter elsewhere."""
    if not jax.sharding.get_abstract_mesh().empty:
        raise NotImplementedError(
            "grouped_matmul runs on one chip: a Pallas call cannot be "
            "partitioned over a mesh, and the exchange between "
            "expert-parallel chips is not written")
    m = lhs.shape[0]
    if lhs.ndim != 2 or rhs.ndim != 3 or rhs.shape[1] != lhs.shape[1] \
            or group_sizes.shape != (rhs.shape[0],):
        raise ValueError(f"lhs {lhs.shape}, rhs {rhs.shape}, group_sizes "
                         f"{group_sizes.shape}: want [M, K], [G, K, N], [G]")
    if interpret is None:
        from tensorflowonspark_tpu.ops import default_interpret
        interpret = default_interpret()
    pad = -m % TILE_M if m > TILE_M else 0      # a small M is one tile
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    out = _grouped(lhs, rhs.astype(lhs.dtype), group_sizes.astype(jnp.int32),
                   bool(interpret))
    return out[:m] if pad else out


def grouped_matmul_reference(lhs, rhs, group_sizes):
    """The same product by a loop over the groups (rows past the last
    group come out zero): what the tests compare the kernels with."""
    ends = jnp.cumsum(group_sizes)
    rows = jnp.arange(lhs.shape[0])
    out = jnp.zeros((lhs.shape[0], rhs.shape[2]), jnp.float32)
    for g in range(rhs.shape[0]):
        mine = (rows >= ends[g] - group_sizes[g]) & (rows < ends[g])
        out = out + jnp.where(mine[:, None], jnp.matmul(
            lhs.astype(jnp.float32), rhs[g].astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST), 0)
    return out
