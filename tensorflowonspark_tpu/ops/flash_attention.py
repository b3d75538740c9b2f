"""Pallas TPU flash attention (forward + blocked backward).

Blocked attention with a numerically-stable online softmax: the [S, S]
score matrix never materializes in HBM — in either direction.  The forward
grid streams K/V blocks through VMEM (innermost grid dim) while per-q-block
running max / denominator / accumulator live in VMEM scratch that persists
across the sequential k-steps of the TPU grid, and emits the per-row
logsumexp.  The backward recomputes probabilities blockwise from (q, k,
lse) — flash-style recompute — in two kernels: one accumulating dq over
streamed K/V blocks, one accumulating dk/dv over streamed Q/dO blocks.  All
matmuls run on the MXU with f32 accumulation.  What a backward holds of
the forward is q, k, v, the output, `O(B·S·H·D)`, and the row logsumexp as
`f32[B, H, S]`, one value a query: the forward rules (`_residuals`) keep
lane 0 of the 128 equal lanes the kernel writes and the backward
broadcasts it back for the dq kernel, so 128 times that is alive only
around the backward's own kernel calls, not from the forward pass to the
backward.  The two are named `flash_out` and `flash_lse`
(`jax.ad_checkpoint.checkpoint_name`): a rematerialised block whose policy
saves those names (`models.transformer.remat_block`) recomputes q, k and v
and no forward kernel.

A grid step keeps a large resident block (1024 rows by default: a grid
step costs the same whatever it holds) and computes it in strips of
sub-tiles of 128, a sub-tile only if the causal triangle, the `window`
(query i sees key j only if i - j < window) and the padding leave a pair
in it, with the mask arithmetic only on the sub-tiles a mask edge crosses.
So the causal skip also fires where S is one block (S=1024: 36 of 64
sub-tiles, 8 of them masked), and a window layer computes the sub-tiles
of its band, not the two blocks that hold it.  The schedule follows from
what a call can see (S, the blocks, causal, window) and is worked out when
the kernel is traced: every strip has static bounds, blocks of the grid
with the same schedule share one body, and a small table in scalar memory
tells a grid step which body is its own.  `subtile_counts` is the same
arithmetic as a count, and every traced call adds it to the process
counters `flash.subtiles.{computed,masked,square}` (`trace.py`).  Where a
row's keys all sit in one block the forward keeps no running state; the
dk/dv kernel holds its scores keys-by-queries, so neither product that
accumulates into dk or dv transposes a score tile.  Key blocks wholly
outside the window are never fetched: the block index maps clamp to the
window's own blocks, and a block index that does not change starts no
copy.

Where a head divides 128 (64, 32; no narrow key/value heads; `H*D` in whole
lane tiles: `_heads_a_block`) the kernels read q, k, v and dO and write the
output, dq, dk and dv in the layout the projections around attention
produce and consume, `[B, S, H*D]`: a block is `block` rows by one tile of
128 lanes, its last index the pair or four of heads that share the tile,
told apart by a lane mask that follows a grid axis, so the body is no
longer for it: a product that contracts 128 lanes of which the other head's
are zero costs the MXU what a contraction over 64 does and adds zeros.  No
`[B, H, S, D]` copy of any of them exists (each was one a kernel call, 64
lanes padded to 128); `lse` and `delta`, as the kernels write and read
them, stay `[B, H, S, 128]`.  Every other shape is staged `[B*H, S, D]` by a
transpose each way (`_heads_a_block` says why for each), and the process
counters `flash.calls.packed` / `flash.calls.transposed` say which a traced
kernel call took.  Latent attention (a query/key width unlike the value's,
one rotary key a token for all heads) has three kernels of its own on the
same schedule, at the end of the file (`flash_attention_latent`,
`flash.calls.latent`).

Composes with ring attention (parallel/ring_attention.py): ring handles the
cross-device sequence axis, this kernel the on-device blocks.

The reference framework has no kernels at all — math is delegated to TF
(SURVEY.md §1); this file is net-new TPU machinery.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tensorflowonspark_tpu import trace

NEG_INF = -1e30  # large-finite: exp(NEG_INF - m) == 0 without inf-inf NaNs
_LANES = 128  # the kernels write lse and read lse/delta lane-replicated


def _scratch(shape, dtype=jnp.float32):
    return pltpu.VMEM(shape, dtype)


# ---- the sub-tile schedule -------------------------------------------------
#
# A grid step keeps a large resident block (the step count does not grow)
# and computes it in STRIPS of sub-tiles: a run of query sub-tiles against
# the key sub-tiles they can see (dq and the forward; dk/dv take a run of
# key sub-tiles against their queries).  Which sub-tiles hold a visible
# (query, key) pair, and which of those a mask edge crosses, is interval
# arithmetic on what the call can see, done in Python when the kernel is
# traced: every strip has static bounds.  Blocks of the grid that share a
# schedule share one unrolled body; a table in scalar memory says which
# body a grid step takes.

def _ceil_div(x, t, n):
    """ceil(x / t) clipped into [0, n]."""
    return min(max(-(-x // t), 0), n)


def _floor_div1(x, t, n):
    """floor(x / t) + 1 clipped into [0, n]."""
    return min(max(x // t + 1, 0), n)


def _span(a0, ta, a_len, b_base, tb, nb, b_len, lo_d, hi_d):
    """Sub-tiles of a resident block along axis `b` that the sub-tile
    `[a0, a0 + ta)` of axis `a` meets: `(lo, lo_m, hi_m, hi)`.

    Sub-tile `i` covers `[b_base + i*tb, b_base + (i+1)*tb)`, `nb` of them;
    positions at or past `a_len` / `b_len` are padding.  A pair is visible
    if `lo_d <= a - b <= hi_d` (either bound may be None).  Sub-tiles
    `lo..hi-1` hold a visible pair and are computed; of those,
    `lo_m..hi_m-1` hold nothing else and need no mask."""
    a_end = min(a0 + ta, a_len) - 1             # last real position of a
    rel = b_len - b_base
    hi, hi_m = _ceil_div(rel, tb, nb), _floor_div1(rel - tb, tb, nb)
    lo = lo_m = 0
    some, whole = a0 < a_len, a0 + ta <= a_len
    if lo_d is not None:      # a - b >= lo_d: an upper end for b
        hi = min(hi, _floor_div1(a_end - lo_d - b_base, tb, nb))
        hi_m = min(hi_m, _floor_div1(a0 - lo_d - tb + 1 - b_base, tb, nb))
    if hi_d is not None:      # a - b <= hi_d: a lower end for b
        lo = _ceil_div(a0 - hi_d - tb + 1 - b_base, tb, nb)
        lo_m = _ceil_div(a0 + ta - 1 - hi_d - b_base, tb, nb)
        some = some and a0 - hi_d <= b_len - 1
    hi = max(hi, lo) if some else lo
    lo_m = min(max(lo_m, lo), hi)
    hi_m = min(max(hi_m, lo_m), hi) if whole else lo_m
    return lo, lo_m, hi_m, hi


def _k_span(q0, k_base, sub, nc, seq_q, seq_k, causal, window):
    """Key sub-tiles of a resident key block for query rows `[q0, q0+tq)`."""
    return _span(q0, sub[0], seq_q, k_base, sub[1], nc, seq_k,
                 0 if causal else None,
                 None if window is None else window - 1)


def _q_span(k0, q_base, sub, nr, seq_q, seq_k, causal, window):
    """Query sub-tiles of a resident query block for keys `[k0, k0+tk)`:
    the same condition read as a bound on `k - q`."""
    return _span(k0, sub[1], seq_k, q_base, sub[0], nr, seq_q,
                 None if window is None else 1 - window,
                 0 if causal else None)


def subtile_counts(seq_q, seq_k, block_q, block_k, sub, causal=True,
                   window=None):
    """`(computed, masked, square)`: how many sub-tiles one kernel call
    computes for one head, how many of those carry the mask arithmetic,
    and how many the padded square holds.  `sub` is `(tq, tk)`.  The
    kernels' strips come from the same `_span`."""
    nr, nc = block_q // sub[0], block_k // sub[1]
    nq, nk = -(-seq_q // block_q), -(-seq_k // block_k)
    computed = masked = 0
    for row in range(nq * nr):
        for kj in range(nk):
            lo, lo_m, hi_m, hi = _k_span(row * sub[0], kj * block_k, sub, nc,
                                         seq_q, seq_k, causal, window)
            computed += hi - lo
            masked += (hi - lo) - (hi_m - lo_m)
    return computed, masked, nq * nr * nk * nc


# Rows, and keys, a sub-tile.  Measured on the v5e at S=1024 (D=64, one
# block a head) and S=8192 (D=128, GQA 8:1, with and without a window),
# 128 against 256 and 512 in each kernel: 128 is quickest in all three at
# both shapes (PERF.md section 6, PR 29).
_SUBTILE = 128


def _pick_subtile(block_q, block_k):
    """`(tq, tk)` for a resident block: `_SUBTILE` where it divides the
    block, the block itself where it does not (the small blocks of
    tests, S under 128)."""
    def one(block):
        return _SUBTILE if block % _SUBTILE == 0 else block
    return one(block_q), one(block_k)


def _count_subtiles(seq_len, block_q, block_k, sub, causal, window):
    computed, masked, square = subtile_counts(
        seq_len, seq_len, block_q, block_k, sub, causal, window)
    counters = trace.counters()
    counters.inc("flash.subtiles.computed", computed)
    counters.inc("flash.subtiles.masked", masked)
    counters.inc("flash.subtiles.square", square)


def _schedule(grid, blocks, sub, seq_len, causal, window, by_keys):
    """`(kinds, patterns)` for a grid of `(nq, nk)` resident blocks:
    `patterns[kinds[qi * nk + ki]]` is the tuple of strips block (qi, ki)
    computes, `()` where it holds no visible pair.

    A strip `(i0, i1, span)` is sub-tiles `i0..i1-1` of one axis (queries
    if `by_keys`: the forward and dq walk a query strip's keys; else keys:
    dk/dv walk a key strip's queries) against the sub-tiles `span` =
    `(lo, lo_m, hi_m, hi)` of the other, as `_span` gives them.
    Neighbours with the same span make one strip: an interior block is
    one strip, the whole block."""
    (nq, nk), (block_q, block_k) = grid, blocks
    nr, nc = block_q // sub[0], block_k // sub[1]
    kinds, patterns = [], []
    for qi in range(nq):
        for ki in range(nk):
            if by_keys:
                spans = [_k_span(qi * block_q + r * sub[0], ki * block_k,
                                 sub, nc, seq_len, seq_len, causal, window)
                         for r in range(nr)]
            else:
                spans = [_q_span(ki * block_k + c * sub[1], qi * block_q,
                                 sub, nr, seq_len, seq_len, causal, window)
                         for c in range(nc)]
            strips = []
            for i, span in enumerate(spans):
                if span[3] == span[0]:
                    continue
                if strips and strips[-1][1:] == (i, span):
                    strips[-1] = (strips[-1][0], i + 1, span)
                else:
                    strips.append((i, i + 1, span))
            pattern = tuple(strips)
            if pattern not in patterns:
                patterns.append(pattern)
            kinds.append(patterns.index(pattern))
    return np.asarray(kinds, np.int32), patterns


def _for_each_strip(kinds_ref, patterns, qi, ki, nk, strip):
    """Run `strip(*s)` for the strips of block (qi, ki)'s pattern: under a
    scalar branch a pattern where the grid holds several, plainly where
    every block has the same."""
    if len(patterns) == 1:
        for s in patterns[0]:
            strip(*s)
        return
    kind = kinds_ref[qi * nk + ki]
    for pid, pattern in enumerate(patterns):
        if pattern:
            @pl.when(kind == pid)
            def _(pattern=pattern):
                for s in pattern:
                    strip(*s)


def _tile_mask(q0, k0, shape, q_axis, seq_len, causal, window):
    """Validity mask of a tile of scores whose axis `q_axis` runs over
    queries from `q0` and whose other axis over keys from `k0`: real rows,
    real keys, the causal triangle and the window."""
    q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
    k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
    mask = jnp.logical_and(q_pos < seq_len, k_pos < seq_len)
    if causal:
        mask = jnp.logical_and(mask, q_pos >= k_pos)
    if window is not None:
        mask = jnp.logical_and(mask, q_pos - k_pos < window)
    return mask


def _strip_scores(a, b, sm_scale, q0, k0, q_axis, t, span, seq_len, causal,
                  window):
    """Scaled scores `[rows of a, rows of b]` of one strip on the MXU, f32
    accumulation, masked as `_mask_strip` says.  `a` holds the strip's own
    rows (queries if `q_axis` is 0, keys if 1), `b` the other side's
    sub-tiles `lo..hi-1` of `span`."""
    s = jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale
    return _mask_strip(s, q0, k0, q_axis, t, span, seq_len, causal, window)


def _mask_strip(s, q0, k0, q_axis, t, span, seq_len, causal, window):
    """A strip's scores with the mask arithmetic only on the runs of the
    strip a mask edge crosses: along axis 1 lie the sub-tiles `lo..hi-1` of
    `span` = `(lo, lo_m, hi_m, hi)`, `t` rows each: masked, plain, masked
    runs.  `q0`, `k0`: the strip's first query and key."""
    lo, lo_m, hi_m, hi = span
    if lo_m == lo and hi_m == hi:
        return s
    parts = []
    for first, last, masked in ((lo, lo_m, True), (lo_m, hi_m, False),
                                (hi_m, hi, True)):
        if first == last:
            continue
        off = (first - lo) * t
        part = s[:, off:(last - lo) * t]
        if masked:
            mask = _tile_mask(q0 + (off if q_axis else 0),
                              k0 + (0 if q_axis else off), part.shape,
                              q_axis, seq_len, causal, window)
            part = jnp.where(mask, part, NEG_INF)
        parts.append(part)
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)


def _grid_pos(axis, n):
    """A block's index along a grid axis: 0, statically, where the axis
    holds one block."""
    return 0 if n == 1 else pl.program_id(axis)


def _when(cond, body):
    if isinstance(cond, bool):
        if cond:
            body()
    else:
        pl.when(cond)(body)


def _k_blocks_of(i, block_q, block_k, causal, window, nk):
    """Clamp a key-block index to the blocks query block `i` can see."""
    def clamp(j):
        if window is None:
            return j
        lo = jnp.maximum(i * block_q - window + 1, 0) // block_k
        hi = (i * block_q + block_q - 1) // block_k if causal else nk - 1
        return jnp.clip(j, lo, jnp.minimum(hi, nk - 1))
    return clamp


def _q_blocks_of(j, block_q, block_k, causal, window, nq):
    """Clamp a query-block index to the blocks that can see key block `j`."""
    def clamp(i):
        if window is None:
            return i
        lo = (j * block_k) // block_q if causal else 0
        hi = (j * block_k + block_k - 1 + window - 1) // block_q
        return jnp.clip(i, lo, jnp.minimum(hi, nq - 1))
    return clamp


def _own(shape, head, head_dim):
    """Which lanes of a `(rows, lanes)` tile of a block belong to its head
    `head`; None where the block is one head."""
    if shape[1] == head_dim:
        return None
    return jax.lax.broadcasted_iota(jnp.int32, shape, 1) // head_dim == head


def _own_lanes(x, head, head_dim):
    """`x` with the lanes of the block's other heads zeroed: a product that
    contracts the lanes then sees this head alone, and one that keeps them
    leaves the others' zero."""
    own = _own(x.shape, head, head_dim)
    return x if own is None else jnp.where(own, x, 0)


def _put_head(ref, rows, val, head, head_dim):
    """Write `val` into `ref[0, rows]`: into this head's lanes alone where
    the block holds several (the others keep what their own turn wrote)."""
    own = _own(val.shape, head, head_dim)
    ref[0, rows, :] = val if own is None else jnp.where(own, val,
                                                        ref[0, rows, :])


def _fwd_kernel(kinds_ref, q_ref, k_ref, v_ref, o_ref, *rest,
                sm_scale, causal, sub, grid, patterns, seq_len, need_lse,
                head_dim, window=None):
    # grid (B, head blocks, nq, heads a block, nk): the output block does
    # not move over the two inner axes, so each head of a lane block
    # writes its own lanes before the block goes back once
    block_q, block_k = q_ref.shape[1], k_ref.shape[1]
    (nq, nk), (tq, tk) = grid, sub
    qi, ki = _grid_pos(2, nq), _grid_pos(4, nk)
    head = _grid_pos(3, q_ref.shape[2] // head_dim)
    lse_ref = rest[0] if need_lse else None
    # where a row's keys all sit in one resident block the running state
    # is never revisited, and there is none: a strip writes its rows' output
    direct = nk == 1

    def _write(rows, acc, m, l):
        l = jnp.maximum(l, 1e-30)
        _put_head(o_ref, rows, (acc / l).astype(o_ref.dtype), head, head_dim)
        if need_lse:
            # lse rows that saw no valid key (padding) get a finite sentinel
            # so the backward's exp(NEG_INF - lse) underflows to exactly 0
            lse = jnp.where(m <= NEG_INF / 2, 0.0, m + jnp.log(l))
            lse_ref[0, 0, rows, :] = jnp.broadcast_to(
                lse, (acc.shape[0], _LANES))

    if direct:
        if seq_len < nq * block_q:
            # padding rows no strip reaches still need defined values
            _write(slice(None), jnp.zeros((block_q, q_ref.shape[2])),
                   jnp.full((block_q, 1), NEG_INF), jnp.zeros((block_q, 1)))
    else:
        m_scr, l_scr, acc_scr = rest[-3:]

        @functools.partial(_when, ki == 0)
        def _init():
            m_scr[:] = jnp.full(m_scr.shape, NEG_INF, m_scr.dtype)
            l_scr[:] = jnp.zeros(l_scr.shape, l_scr.dtype)
            acc_scr[:] = jnp.zeros(acc_scr.shape, acc_scr.dtype)

    def _strip(r0, r1, span):
        rows, cols = slice(r0 * tq, r1 * tq), slice(span[0] * tk,
                                                    span[3] * tk)
        q = _own_lanes(q_ref[0, rows, :], head, head_dim).astype(jnp.float32)
        k = k_ref[0, cols, :].astype(jnp.float32)     # [Tk, lanes]
        v = v_ref[0, cols, :].astype(jnp.float32)
        s = _strip_scores(q, k, sm_scale, qi * block_q + r0 * tq,
                          ki * block_k + span[0] * tk, 0, tk, span, seq_len,
                          causal, window)
        m_new = jnp.max(s, axis=-1, keepdims=True)    # [Tq, 1]
        if not direct:
            m_prev = m_scr[rows, :1]
            m_new = jnp.maximum(m_prev, m_new)
        p = jnp.exp(s - m_new)                        # [Tq, Tk]
        l_new = jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if direct:
            return _write(rows, pv, m_new, l_new)
        alpha = jnp.exp(m_prev - m_new)
        acc_scr[rows, :] = acc_scr[rows, :] * alpha + pv
        lanes = (q.shape[0], _LANES)
        m_scr[rows, :] = jnp.broadcast_to(m_new, lanes)
        l_scr[rows, :] = jnp.broadcast_to(l_scr[rows, :1] * alpha + l_new,
                                          lanes)

    _for_each_strip(kinds_ref, patterns, qi, ki, nk, _strip)

    if not direct:
        _when(ki == nk - 1, lambda: _write(
            slice(None), acc_scr[:], m_scr[:, :1], l_scr[:, :1]))


def _bwd_dq_kernel(kinds_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, dlt_ref,
                   dq_ref, dq_scr, *, sm_scale, causal, sub, grid, patterns,
                   seq_len, head_dim, window=None):
    block_q, block_k = q_ref.shape[1], k_ref.shape[1]
    (nq, nk), (tq, tk) = grid, sub
    qi, ki = _grid_pos(2, nq), _grid_pos(4, nk)       # the forward's grid
    head = _grid_pos(3, q_ref.shape[2] // head_dim)

    def _init():
        dq_scr[:] = jnp.zeros(dq_scr.shape, dq_scr.dtype)

    _when(ki == 0, _init)

    def _strip(r0, r1, span):
        rows, cols = slice(r0 * tq, r1 * tq), slice(span[0] * tk,
                                                    span[3] * tk)
        q = _own_lanes(q_ref[0, rows, :], head, head_dim).astype(jnp.float32)
        do = _own_lanes(do_ref[0, rows, :], head,
                        head_dim).astype(jnp.float32)
        k = k_ref[0, cols, :].astype(jnp.float32)     # [Tk, lanes]
        v = v_ref[0, cols, :].astype(jnp.float32)
        lse = lse_ref[0, 0, rows, :1]                          # [Tq, 1]
        dlt = dlt_ref[0, 0, rows, :1]
        s = _strip_scores(q, k, sm_scale, qi * block_q + r0 * tq,
                          ki * block_k + span[0] * tk, 0, tk, span, seq_len,
                          causal, window)
        p = jnp.exp(s - lse)                          # [Tq, Tk]
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - dlt)                           # [Tq, Tk]
        dq_scr[rows, :] += sm_scale * jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _for_each_strip(kinds_ref, patterns, qi, ki, nk, _strip)

    def _finish():
        _put_head(dq_ref, slice(None), dq_scr[:].astype(dq_ref.dtype), head,
                  head_dim)

    _when(ki == nk - 1, _finish)


def _bwd_dkv_kernel(kinds_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, dlt_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr, *,
                    sm_scale, causal, sub, grid, patterns, seq_len,
                    head_dim, window=None):
    # grid (B, kv head blocks, nk, members, nq): dk/dv accumulate across
    # a block's members AND the q blocks before one narrow write — the
    # output block index is constant over both inner dims, so pallas
    # keeps it resident until the last (g, qi) visit.  The members are
    # the GQA group's q heads, or the heads of one lane block, each
    # adding into its own lanes of dk and dv
    block_q, block_k = q_ref.shape[1], k_ref.shape[1]
    (nq, nk), (tq, tk) = grid, sub
    ki = _grid_pos(2, nk)
    g = pl.program_id(3)
    qi = _grid_pos(4, nq)                             # q innermost here
    ng = pl.num_programs(3)

    def _init():
        dk_scr[:] = jnp.zeros(dk_scr.shape, dk_scr.dtype)
        dv_scr[:] = jnp.zeros(dv_scr.shape, dv_scr.dtype)

    pl.when(jnp.logical_and(g == 0, qi == 0))(_init)

    def _strip(c0, c1, span):
        rows, cols = slice(span[0] * tq, span[3] * tq), slice(c0 * tk,
                                                              c1 * tk)
        # where the members share a lane block, `g` is the head's place in it
        q = _own_lanes(q_ref[0, rows, :], g, head_dim).astype(jnp.float32)
        do = _own_lanes(do_ref[0, rows, :], g, head_dim).astype(jnp.float32)
        k = k_ref[0, cols, :].astype(jnp.float32)     # [Tk, lanes]
        v = v_ref[0, cols, :].astype(jnp.float32)
        # keys down the rows, queries along the lanes: the two products
        # that accumulate dk and dv contract the lanes as they lie, and no
        # [Tq, Tk] tile is transposed
        lse = lse_ref[0, 0, :, rows]                           # [1, Tq]
        dlt = dlt_ref[0, 0, :, rows]
        s = _strip_scores(k, q, sm_scale, qi * block_q + span[0] * tq,
                          ki * block_k + c0 * tk, 1, tq, span, seq_len,
                          causal, window)
        p = jnp.exp(s - lse)                          # [Tk, Tq]
        dv_scr[cols, :] += jax.lax.dot_general(
            p, do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(v, do, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - dlt)                           # [Tk, Tq]
        dk_scr[cols, :] += sm_scale * jax.lax.dot_general(
            ds, q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _for_each_strip(kinds_ref, patterns, qi, ki, nk, _strip)

    def _finish():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)

    pl.when(jnp.logical_and(g == ng - 1, qi == nq - 1))(_finish)


# ---- how the kernels see [B, S, H, D] ----------------------------------------
#
# The projections around attention produce and consume `[B, S, H*D]`, and a
# kernel can take a head out of that layout by a block of lanes: no copy
# of q, k, v, dO, the output or a gradient is made for its sake.  Which
# shapes allow it is `_heads_a_block`; the others are staged
# `[B*H, S, D]`, a transposed copy each way, as every shape once was.

def _heads_a_block(n_heads, n_kv_heads, head_dim):
    """Heads in one block of lanes of the `[B, S, H*D]` layout, or None
    where the shapes need the transposed staging.

    A block's last dimension has to be whole lane tiles (Mosaic refuses a
    block of 1 over the heads axis of `[B, S, H, D]`).  A head that divides
    128 shares a tile with its neighbours, told apart inside the kernel by
    a lane mask, which needs `H*D` in whole tiles and, since a query head
    and its key/value head must then lie in the same lanes, no narrow
    key/value heads.  Any other head size (96, 80) straddles the tiles.
    A head of 128 or a multiple would be a block by itself, and the
    kernels take it (`pack` 1: it lowers, and the attention sublayer runs
    on the chip that way at the sparse-expert cell's shapes), but that
    cell's whole step stopped on the chip with it and the cause is not
    known (PERF.md section 7, PR 31): until it is, such a head keeps the
    transposed staging, where the padding to 128 lanes costs it nothing."""
    if (head_dim < _LANES and _LANES % head_dim == 0
            and n_kv_heads == n_heads and (n_heads * head_dim) % _LANES == 0):
        return _LANES // head_dim
    return None


def _stage(x, block, pack):
    """`[B, S, H, D]` as the kernels index it, the sequence padded up to a
    multiple of `block` (a pad of zero rows is no operation): the view
    `[B, S, H*D]` where `pack` heads share a lane block, a transposed
    `[B*H, S, D]` where it is None."""
    B, S, H, D = x.shape
    x = (x.reshape(B, S, H * D) if pack
         else x.transpose(0, 2, 1, 3).reshape(B * H, S, D))
    return jnp.pad(x, ((0, 0), (0, (-S) % block), (0, 0)))


def _unstage(x, shape, pack):
    """A kernel's result back as `shape` = `(B, S, H, D)`."""
    B, S, H, D = shape
    if pack:
        return x[:, :S].reshape(shape)
    return x[:, :S].reshape(B, H, S, D).transpose(0, 2, 1, 3)


def _head_rows(block, n_heads, head_dim, pack, index):
    """BlockSpec of `block` rows of one head of a staged operand;
    `index(*grid ids)` gives `(batch, head, row block)`."""
    if pack:
        def at(*ids):
            b, h, i = index(*ids)
            return (b, i, h // pack)
        return pl.BlockSpec((1, block, head_dim * pack), at)

    def at(*ids):
        b, h, i = index(*ids)
        return (b * n_heads + h, i, 0)
    return pl.BlockSpec((1, block, head_dim), at)


def _by_queries(q, k, n_blocks, blocks, causal, window, pack):
    """The grid the forward and dq walk, `(B, head blocks, nq, heads a
    block, nk)`, with the BlockSpecs of a head's query rows, of the key
    rows it sees and of its lane-replicated row statistics (`lse`,
    `delta`).  Narrow kv blocks are indexed by the q head's GROUP: no
    repeated kv ever materializes in HBM (the GQA bandwidth win)."""
    (B, _, H, D), H_kv = q.shape, k.shape[2]
    (nq, nk), (block_q, block_k) = n_blocks, blocks
    group, per = H // H_kv, pack or 1     # `per` heads a block of lanes

    def kv_index(b, hb, i, hh, j, _):
        seen = _k_blocks_of(i, block_q, block_k, causal, window, nk)
        return (b, (hb * per + hh) // group, seen(j))

    q_spec = _head_rows(block_q, H, D, pack,
                        lambda b, hb, i, hh, j, _: (b, hb * per + hh, i))
    kv_spec = _head_rows(block_k, H_kv, D, pack, kv_index)
    stat_spec = pl.BlockSpec(
        (1, 1, block_q, _LANES),
        lambda b, hb, i, hh, j, _: (b, hb * per + hh, i, 0))
    return (B, H // per, nq, per, nk), q_spec, kv_spec, stat_spec


def _scheduled_call(kernel, name, n_blocks, blocks, seq_len, causal, window,
                    interpret, layout, out_shape, **grid_spec):
    """`pl.pallas_call` of one of the kernels with its schedule: the table
    of block kinds rides in as a scalar-prefetch operand (the index maps
    take it as a last argument and ignore it).  `layout` (`packed`,
    `transposed` or `latent`) is counted in `flash.calls.<layout>`; the
    dk/dv kernels (`name` ends in `dkv`) walk a key strip's queries."""
    sub = _pick_subtile(*blocks)
    _count_subtiles(seq_len, *blocks, sub, causal, window)
    trace.counters().inc("flash.calls." + layout)
    kinds, patterns = _schedule(n_blocks, blocks, sub, seq_len, causal,
                                window, by_keys=not name.endswith("dkv"))
    body = functools.partial(
        kernel, causal=causal, sub=sub, grid=n_blocks, patterns=patterns,
        seq_len=seq_len, **({} if window is None else {"window": window}))
    # the latent kernels hold two more operands a step (the rotary key and
    # a query half as wide again): 16.1 MiB of dq's strips at the default
    # blocks, over the default scope of 16 of the chip's 128
    params = ({"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=_LATENT_VMEM_BYTES)} if layout == "latent" else {})
    call = pl.pallas_call(
        body, out_shape=out_shape, interpret=interpret, name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(num_scalar_prefetch=1,
                                               **grid_spec), **params)
    return functools.partial(call, kinds)


_LATENT_VMEM_BYTES = 32 << 20


def _layout(pack):
    return "packed" if pack else "transposed"


# Both implementations are jitted on their own: a model calls them once a
# layer with the same shapes, and an inner `jit` is traced and lowered ONCE
# for all of them (the kernels' unrolled strips are most of a step's
# tracing and lowering time otherwise); XLA inlines the calls again.
_STATIC = ("causal", "sm_scale", "block_q", "block_k", "interpret", "window")


@functools.partial(jax.jit, static_argnames=_STATIC + ("need_lse",))
def _flash_fwd_impl(q, k, v, causal, sm_scale, block_q, block_k, interpret,
                    need_lse, window=None):
    """Returns (out [B,S,H,D], lse [B,H,Sq_padded,LANES] or None).

    `need_lse=False` (the primal/serving path) omits the lse output
    entirely — pallas outputs can't be dead-code-eliminated, so an unused
    lse would cost real HBM writes on every inference forward."""
    B, S, H, D = q.shape
    pack = _heads_a_block(H, k.shape[2], D)
    qs, ks, vs = (_stage(x, block, pack)
                  for x, block in ((q, block_q), (k, block_k), (v, block_k)))
    Sq, Sk = qs.shape[1], ks.shape[1]
    nq, nk = Sq // block_q, Sk // block_k
    grid, o_spec, kv_spec, lse_spec = _by_queries(
        q, k, (nq, nk), (block_q, block_k), causal, window, pack)
    result = _scheduled_call(
        functools.partial(_fwd_kernel, sm_scale=sm_scale, need_lse=need_lse,
                          head_dim=D),
        "flash_fwd", (nq, nk), (block_q, block_k), S, causal, window,
        interpret, _layout(pack),
        grid=grid,
        in_specs=[o_spec, kv_spec, kv_spec],
        out_specs=[o_spec] + ([lse_spec] if need_lse else []),
        out_shape=[jax.ShapeDtypeStruct(qs.shape, q.dtype)] + (
            [jax.ShapeDtypeStruct((B, H, Sq, _LANES), jnp.float32)]
            if need_lse else []),
        # running max, denominator, accumulator: none where nk is 1
        scratch_shapes=[] if nk == 1 else [
            _scratch((block_q, _LANES)),
            _scratch((block_q, _LANES)),
            _scratch(o_spec.block_shape[1:]),
        ],
    )(qs, ks, vs)
    return (_unstage(result[0], q.shape, pack),
            result[1] if need_lse else None)


def _row_delta(g, out, g_lse, seq_padded):
    """delta = rowsum(dO * O): [B, H, Sq] — O(B·S·H·D) elementwise, jax-side.
    The sum over a head's lanes is a product with the heads' 0/1 indicator
    (exact at the highest precision: every term is x * 1): it reads dO
    and O as `[B, S, H*D]`, where a reduction over the last axis of
    `[B, S, H, D]` would have XLA lay both out anew in float32 first."""
    B, S, H, D = out.shape
    prod = (g.astype(jnp.float32) * out.astype(jnp.float32)).reshape(
        B, S, H * D)
    of_head = (jnp.arange(H * D)[:, None] // D == jnp.arange(H)).astype(
        jnp.float32)
    delta = jnp.einsum("bsk,kh->bhs", prod, of_head,
                       precision=jax.lax.Precision.HIGHEST)
    # an lse cotangent folds exactly into delta: ds_ij = p_ij*(dp_ij -
    # delta_i + g_lse_i), since dlse_i/ds_ij = p_ij
    if g_lse is not None:
        delta = delta - g_lse.astype(jnp.float32)
    return jnp.pad(delta, ((0, 0), (0, 0), (0, seq_padded - S)))


@functools.partial(jax.jit, static_argnames=_STATIC)
def _flash_bwd_impl(q, k, v, out, lse, g, causal, sm_scale, block_q, block_k,
                    interpret, g_lse=None, window=None):
    B, S, H, D = q.shape
    H_kv = k.shape[2]
    group = H // H_kv         # GQA: q heads per (narrow) kv head
    pack = _heads_a_block(H, H_kv, D)
    per = pack or 1           # heads a grid step's blocks hold
    qs, ks, vs, dos = (
        _stage(x, block, pack) for x, block in (
            (q, block_q), (k, block_k), (v, block_k), (g, block_q)))
    Sq, Sk = qs.shape[1], ks.shape[1]
    nq, nk = Sq // block_q, Sk // block_k

    delta = _row_delta(g, out, g_lse, Sq)
    # `lse` comes as the residual holds it, a value a query (`_residuals`).
    # dq reads both lane-replicated, a query a row; dk/dv a query a lane
    lse_t, delta_t = lse[:, :, None, :], delta[:, :, None, :]
    lse, delta = (jnp.broadcast_to(x[..., None], (B, H, Sq, _LANES))
                  for x in (lse, delta))

    grid, q_spec, k_spec, r_spec = _by_queries(
        q, k, (nq, nk), (block_q, block_k), causal, window, pack)
    dq = _scheduled_call(
        functools.partial(_bwd_dq_kernel, sm_scale=sm_scale, head_dim=D),
        "flash_dq", (nq, nk), (block_q, block_k), S, causal, window,
        interpret, _layout(pack),
        grid=grid,
        in_specs=[q_spec, k_spec, k_spec, q_spec, r_spec, r_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(qs.shape, q.dtype),
        scratch_shapes=[_scratch(q_spec.block_shape[1:])],
    )(qs, ks, vs, dos, lse, delta)

    # swap grid roles: (b, kv head block, k-block, member, q-block), q
    # innermost; a member is a q head of the kv head's GQA group or a head
    # of the lane block (never both: `_heads_a_block`).  dk/dv come out
    # NARROW, accumulated across the members (the narrow output replaces
    # the former repeat-then-sum cotangent)
    def q_index(b, kb, j, g, i, _):
        seeing = _q_blocks_of(j, block_q, block_k, causal, window, nq)
        return (b, kb * group * per + g, seeing(i))

    qk_spec = _head_rows(block_q, H, D, pack, q_index)
    kk_spec = _head_rows(block_k, H_kv, D, pack,
                         lambda b, kb, j, g, i, _: (b, kb * per, j))

    def q_lane_index(*ids):
        b, h, i = q_index(*ids)
        return (b, h, 0, i)

    rk_spec = pl.BlockSpec((1, 1, 1, block_q), q_lane_index)
    dk, dv = _scheduled_call(
        functools.partial(_bwd_dkv_kernel, sm_scale=sm_scale, head_dim=D),
        "flash_dkv", (nq, nk), (block_q, block_k), S, causal, window,
        interpret, _layout(pack),
        grid=(B, H_kv // per, nk, group * per, nq),
        in_specs=[qk_spec, kk_spec, kk_spec, qk_spec, rk_spec, rk_spec],
        out_specs=[kk_spec, kk_spec],
        out_shape=[jax.ShapeDtypeStruct(ks.shape, k.dtype),
                   jax.ShapeDtypeStruct(vs.shape, v.dtype)],
        scratch_shapes=[_scratch(kk_spec.block_shape[1:]),
                        _scratch(kk_spec.block_shape[1:])],
    )(qs, ks, vs, dos, lse_t, delta_t)

    return (_unstage(dq, q.shape, pack), _unstage(dk, k.shape, pack),
            _unstage(dv, v.shape, pack))


def attention_reference(q, k, v, causal=True, sm_scale=None, window=None):
    """Dense reference with semantics identical to the kernel (f32 softmax,
    large-finite mask).  Used for tests and as the dense fallback.
    Accepts narrow (GQA) k/v like the kernel does — repeated here."""
    if k.shape[2] != q.shape[2]:
        rep = q.shape[2] // k.shape[2]
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    D = q.shape[-1]
    if sm_scale is None:
        sm_scale = 1.0 / (D ** 0.5)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * sm_scale
    if causal:
        Sq, Sk = q.shape[1], k.shape[1]
        mask = jnp.tril(jnp.ones((Sq, Sk), dtype=bool))
        s = jnp.where(mask[None, None], s, NEG_INF)
    if window is not None:
        near = (jnp.arange(q.shape[1])[:, None]
                - jnp.arange(k.shape[1])[None, :]) < window
        s = jnp.where(near[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(q.dtype), v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, causal, sm_scale, block_q, block_k, interpret, window):
    out, _ = _flash_fwd_impl(q, k, v, causal, sm_scale, block_q, block_k,
                             interpret, need_lse=False, window=window)
    return out


def _residuals(out, lse):
    """What a forward rule keeps of its kernel's results for the backward,
    under the names a rematerialisation policy can save them by
    (`models.transformer.remat_block`): the output, and the row statistics
    as ONE value a query, `f32[B, H, Sq padded]`, not the 128 equal lanes
    the kernel writes (GPT-2 large at B=8: 0.66 MB a layer, not 84).  The
    backward broadcasts them back to the lanes the dq kernel reads, as it
    does `delta`."""
    return (checkpoint_name(out, "flash_out"),
            checkpoint_name(lse[:, :, :, 0], "flash_lse"))


def _flash_vjp_fwd(q, k, v, causal, sm_scale, block_q, block_k, interpret,
                   window):
    out, lse = _residuals(*_flash_fwd_impl(
        q, k, v, causal, sm_scale, block_q, block_k, interpret,
        need_lse=True, window=window))
    return out, (q, k, v, out, lse)


def _flash_vjp_bwd(causal, sm_scale, block_q, block_k, interpret, window,
                   res, g):
    q, k, v, out, lse = res
    return _flash_bwd_impl(q, k, v, out, lse, g, causal, sm_scale,
                           block_q, block_k, interpret, window=window)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_lse(q, k, v, causal, sm_scale, block_q, block_k, interpret):
    out, lse_full = _flash_fwd_impl(q, k, v, causal, sm_scale, block_q,
                                    block_k, interpret, need_lse=True)
    return out, lse_full[:, :, :q.shape[1], 0]


def _flash_lse_vjp_fwd(q, k, v, causal, sm_scale, block_q, block_k,
                       interpret):
    out, lse = _residuals(*_flash_fwd_impl(
        q, k, v, causal, sm_scale, block_q, block_k, interpret,
        need_lse=True))
    return (out, lse[:, :, :q.shape[1]]), (q, k, v, out, lse)


def _flash_lse_vjp_bwd(causal, sm_scale, block_q, block_k, interpret, res,
                       cotangents):
    g, g_lse = cotangents
    return _flash_bwd_impl(*res, g, causal, sm_scale, block_q, block_k,
                           interpret, g_lse=g_lse)


_flash_lse.defvjp(_flash_lse_vjp_fwd, _flash_lse_vjp_bwd)


def _pick_block(requested, seq_len):
    """Clamp a block size into the sequence range, then prefer the largest
    power-of-two block that DIVIDES the sequence — padding to a block
    multiple is pure masked-out waste (e.g. S=1536 at block 1024 would pad
    33% phantom rows; block 512 pads none)."""
    b = min(requested, max(seq_len, 16))
    if seq_len % b == 0:
        return b
    for cand in (1024, 512, 256, 128):
        if cand <= b and seq_len % cand == 0:
            return cand
    return b


def _resolve_call_args(q, k, sm_scale, block_q, block_k, interpret):
    """Shared prologue of the public wrappers: default scale, interpret
    auto-select (native Mosaic on TPU, interpreter elsewhere), and block
    sizes clamped into the padded sequence range.

    Default blocks are 1024x1024: the number of grid steps is what a
    larger block saves (S=1024 is one step a head), and what a block
    computes is cut to its visible sub-tiles of `_SUBTILE` inside it.  The
    1024 itself dates from an earlier runtime (S in [4096, 8192]) and was
    not measured again; the sub-tile was, on the v5e (PERF.md section 6, PR
    29).  A 1024x1024 strip of float32 scores is 4 MB of v5e-class ~128MB
    VMEM; pre-v4 generations with small VMEM may need block sizes passed
    explicitly.  A block is that many rows of ONE head (of the heads of
    one lane tile in turn, `_heads_a_block`), whichever way the operands
    are staged."""
    if q.shape[2] % k.shape[2]:
        raise ValueError(
            f"q heads {q.shape[2]} must be a multiple of kv heads "
            f"{k.shape[2]} (GQA: narrow k/v feed the kernel directly; "
            "no repeat needed)")
    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    if interpret is None:
        from tensorflowonspark_tpu.ops import default_interpret
        interpret = default_interpret()
    block_q = _pick_block(block_q, q.shape[1])
    block_k = _pick_block(block_k, k.shape[1])
    return float(sm_scale), int(block_q), int(block_k), bool(interpret)


def flash_attention_with_lse(q, k, v, causal=True, sm_scale=None,
                             block_q=1024, block_k=1024, interpret=None):
    """Like flash_attention but also returns the per-row logsumexp
    [B, H, S] — the merge key for combining attention computed over
    key/value shards (ring attention's per-step local compute).  Fully
    differentiable in both outputs."""
    sm_scale, block_q, block_k, interpret = _resolve_call_args(
        q, k, sm_scale, block_q, block_k, interpret)
    return _flash_lse(q, k, v, causal, sm_scale, block_q, block_k, interpret)


def flash_attention(q, k, v, causal=True, sm_scale=None,
                    block_q=1024, block_k=1024, interpret=None, window=None):
    """Flash attention over [B, S, H, D] q and [B, S, H_kv, D] k/v.

    `window` (static): query i sees key j only if i - j < window; key
    blocks wholly left of it are neither fetched nor computed, so a window
    layer's work grows with S x window and not with S x S.

    GQA-native: ``H_kv`` may be any divisor of ``H`` — narrow k/v blocks
    are indexed per q-head group inside the kernel, so the repeated k/v
    (and the repeat's summed cotangent) never materialize in HBM.
    Sequence lengths need not be multiples of the block sizes (padded rows
    and keys are masked out of both passes).  The arguments are read where
    they lie: reshaped from a projection's `[B, S, H*D]` they cost no copy
    (`_heads_a_block` says for which shapes).  `interpret=None`
    auto-selects: native Mosaic on TPU, interpreter elsewhere (the CPU test
    mesh).
    """
    sm_scale, block_q, block_k, interpret = _resolve_call_args(
        q, k, sm_scale, block_q, block_k, interpret)
    if window is not None and window < 1:
        raise ValueError(f"window={window} must be at least 1")
    return _flash(q, k, v, causal, sm_scale, block_q, block_k, interpret,
                  None if window is None else int(window))


# ---- latent attention: two widths, one rotary key a token --------------------
#
# Multi-head latent attention (MLA) scores a head's query against a key of
# two parts: `k_nope` [B, S, H, Dn], a head's own (the latent projected up),
# and `k_rope` [B, S, Dr], ONE rotated vector a token that all the heads
# share; the values are Dv wide (192 = 128 + 64 over 128 in the published
# models).  The three kernels below take them as they are: the scores are
# the sum of two products, no `[B, S, H, Dn + Dr]` key with the rotary
# part copied a head is ever built, no value is padded to the query's
# width, and the rotary key's gradient is summed over the heads inside the
# dk/dv kernel, whose output block for it stays resident over the heads
# axis of the grid.  Schedule, strips and masks are the other kernels'
# (`_schedule`); a head's rows are staged `[B*H, S, D]` as every head of
# 128 is (`_heads_a_block`).  Counted as `flash.calls.latent`.

def _dot(a, b, contract):
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               preferred_element_type=jnp.float32)


def _mla_fwd_kernel(kinds_ref, q_ref, kn_ref, kr_ref, v_ref, o_ref, *rest,
                    sm_scale, causal, sub, grid, patterns, seq_len, need_lse):
    # grid (B, H, nq, nk); the forward of `_fwd_kernel`, the scores in two
    # products
    block_q, block_k = q_ref.shape[1], kn_ref.shape[1]
    (nq, nk), (tq, tk) = grid, sub
    qi, ki = _grid_pos(2, nq), _grid_pos(3, nk)
    nope = kn_ref.shape[2]
    lse_ref = rest[0] if need_lse else None
    direct = nk == 1

    def _write(rows, acc, m, l):
        l = jnp.maximum(l, 1e-30)
        o_ref[0, rows, :] = (acc / l).astype(o_ref.dtype)
        if need_lse:
            lse = jnp.where(m <= NEG_INF / 2, 0.0, m + jnp.log(l))
            lse_ref[0, 0, rows, :] = jnp.broadcast_to(
                lse, (acc.shape[0], _LANES))

    if direct:
        if seq_len < nq * block_q:
            _write(slice(None), jnp.zeros((block_q, v_ref.shape[2])),
                   jnp.full((block_q, 1), NEG_INF), jnp.zeros((block_q, 1)))
    else:
        m_scr, l_scr, acc_scr = rest[-3:]

        @functools.partial(_when, ki == 0)
        def _init():
            m_scr[:] = jnp.full(m_scr.shape, NEG_INF, m_scr.dtype)
            l_scr[:] = jnp.zeros(l_scr.shape, l_scr.dtype)
            acc_scr[:] = jnp.zeros(acc_scr.shape, acc_scr.dtype)

    def _strip(r0, r1, span):
        rows, cols = slice(r0 * tq, r1 * tq), slice(span[0] * tk,
                                                    span[3] * tk)
        qn = q_ref[0, rows, :nope].astype(jnp.float32)
        qr = q_ref[0, rows, nope:].astype(jnp.float32)
        kn = kn_ref[0, cols, :].astype(jnp.float32)
        kr = kr_ref[0, cols, :].astype(jnp.float32)
        v = v_ref[0, cols, :].astype(jnp.float32)
        s = (_dot(qn, kn, ((1,), (1,))) + _dot(qr, kr, ((1,), (1,)))
             ) * sm_scale
        s = _mask_strip(s, qi * block_q + r0 * tq,
                        ki * block_k + span[0] * tk, 0, tk, span, seq_len,
                        causal, None)
        m_new = jnp.max(s, axis=-1, keepdims=True)
        if not direct:
            m_prev = m_scr[rows, :1]
            m_new = jnp.maximum(m_prev, m_new)
        p = jnp.exp(s - m_new)
        l_new = jnp.sum(p, axis=-1, keepdims=True)
        pv = _dot(p, v, ((1,), (0,)))
        if direct:
            return _write(rows, pv, m_new, l_new)
        alpha = jnp.exp(m_prev - m_new)
        acc_scr[rows, :] = acc_scr[rows, :] * alpha + pv
        lanes = (qn.shape[0], _LANES)
        m_scr[rows, :] = jnp.broadcast_to(m_new, lanes)
        l_scr[rows, :] = jnp.broadcast_to(l_scr[rows, :1] * alpha + l_new,
                                          lanes)

    _for_each_strip(kinds_ref, patterns, qi, ki, nk, _strip)

    if not direct:
        _when(ki == nk - 1, lambda: _write(
            slice(None), acc_scr[:], m_scr[:, :1], l_scr[:, :1]))


def _mla_dq_kernel(kinds_ref, q_ref, kn_ref, kr_ref, v_ref, do_ref, lse_ref,
                   dlt_ref, dq_ref, dq_scr, *, sm_scale, causal, sub, grid,
                   patterns, seq_len):
    block_q, block_k = q_ref.shape[1], kn_ref.shape[1]
    (nq, nk), (tq, tk) = grid, sub
    qi, ki = _grid_pos(2, nq), _grid_pos(3, nk)
    nope = kn_ref.shape[2]

    def _init():
        dq_scr[:] = jnp.zeros(dq_scr.shape, dq_scr.dtype)

    _when(ki == 0, _init)

    def _strip(r0, r1, span):
        rows, cols = slice(r0 * tq, r1 * tq), slice(span[0] * tk,
                                                    span[3] * tk)
        qn = q_ref[0, rows, :nope].astype(jnp.float32)
        qr = q_ref[0, rows, nope:].astype(jnp.float32)
        do = do_ref[0, rows, :].astype(jnp.float32)
        kn = kn_ref[0, cols, :].astype(jnp.float32)
        kr = kr_ref[0, cols, :].astype(jnp.float32)
        v = v_ref[0, cols, :].astype(jnp.float32)
        lse = lse_ref[0, 0, rows, :1]
        dlt = dlt_ref[0, 0, rows, :1]
        s = (_dot(qn, kn, ((1,), (1,))) + _dot(qr, kr, ((1,), (1,)))
             ) * sm_scale
        s = _mask_strip(s, qi * block_q + r0 * tq,
                        ki * block_k + span[0] * tk, 0, tk, span, seq_len,
                        causal, None)
        p = jnp.exp(s - lse)
        ds = p * (_dot(do, v, ((1,), (1,))) - dlt)            # [Tq, Tk]
        dq_scr[rows, :nope] += sm_scale * _dot(ds, kn, ((1,), (0,)))
        dq_scr[rows, nope:] += sm_scale * _dot(ds, kr, ((1,), (0,)))

    _for_each_strip(kinds_ref, patterns, qi, ki, nk, _strip)

    def _finish():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)

    _when(ki == nk - 1, _finish)


def _mla_dkv_kernel(kinds_ref, q_ref, kn_ref, kr_ref, v_ref, do_ref, lse_ref,
                    dlt_ref, dkn_ref, dkr_ref, dv_ref, dkn_scr, dkr_scr,
                    dv_scr, *, sm_scale, causal, sub, grid, patterns,
                    seq_len):
    # grid (B, nk, H, nq): a head's dk_nope and dv accumulate across the q
    # blocks and go back once a head; the rotary key's gradient accumulates
    # across the heads as well, its block index constant over both inner
    # axes, and goes back once a key block
    block_q, block_k = q_ref.shape[1], kn_ref.shape[1]
    (nq, nk), (tq, tk) = grid, sub
    ki, qi = _grid_pos(1, nk), _grid_pos(3, nq)
    h, n_heads = pl.program_id(2), pl.num_programs(2)
    nope = kn_ref.shape[2]

    def _init_head():
        dkn_scr[:] = jnp.zeros(dkn_scr.shape, dkn_scr.dtype)
        dv_scr[:] = jnp.zeros(dv_scr.shape, dv_scr.dtype)

    def _init_key():
        dkr_scr[:] = jnp.zeros(dkr_scr.shape, dkr_scr.dtype)

    _when(qi == 0, _init_head)
    pl.when(jnp.logical_and(h == 0, qi == 0))(_init_key)

    def _strip(c0, c1, span):
        rows, cols = slice(span[0] * tq, span[3] * tq), slice(c0 * tk,
                                                              c1 * tk)
        qn = q_ref[0, rows, :nope].astype(jnp.float32)
        qr = q_ref[0, rows, nope:].astype(jnp.float32)
        do = do_ref[0, rows, :].astype(jnp.float32)
        kn = kn_ref[0, cols, :].astype(jnp.float32)
        kr = kr_ref[0, cols, :].astype(jnp.float32)
        v = v_ref[0, cols, :].astype(jnp.float32)
        # keys down the rows, queries along the lanes, as `_bwd_dkv_kernel`
        lse = lse_ref[0, 0, :, rows]                           # [1, Tq]
        dlt = dlt_ref[0, 0, :, rows]
        s = (_dot(kn, qn, ((1,), (1,))) + _dot(kr, qr, ((1,), (1,)))
             ) * sm_scale
        s = _mask_strip(s, qi * block_q + span[0] * tq,
                        ki * block_k + c0 * tk, 1, tq, span, seq_len,
                        causal, None)
        p = jnp.exp(s - lse)                                   # [Tk, Tq]
        dv_scr[cols, :] += _dot(p, do, ((1,), (0,)))
        ds = p * (_dot(v, do, ((1,), (1,))) - dlt)
        dkn_scr[cols, :] += sm_scale * _dot(ds, qn, ((1,), (0,)))
        dkr_scr[cols, :] += sm_scale * _dot(ds, qr, ((1,), (0,)))

    _for_each_strip(kinds_ref, patterns, qi, ki, nk, _strip)

    def _finish_head():
        dkn_ref[0] = dkn_scr[:].astype(dkn_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)

    def _finish_key():
        dkr_ref[0] = dkr_scr[:].astype(dkr_ref.dtype)

    _when(qi == nq - 1, _finish_head)
    pl.when(jnp.logical_and(h == n_heads - 1, qi == nq - 1))(_finish_key)


def _latent_specs(n_heads, by_keys):
    """`(head, token)`, the BlockSpec makers of a latent kernel's grid:
    `(B, H, nq, nk)`, or with `by_keys` `(B, nk, H, nq)`.  `head(block,
    width, keys)` is `block` rows of one head of a `[B*H, S, width]` operand,
    key rows if `keys` and query rows if not; `token(block, width)` the key
    rows of an operand with one vector a token, `[B, S, width]`."""
    def ids(*grid):                       # -> (batch, head, q block, k block)
        return (grid[0], grid[2], grid[3], grid[1]) if by_keys else grid[:4]

    def head(block, width, keys=False):
        def at(*grid):
            b, h, i, j = ids(*grid)
            return (b * n_heads + h, j if keys else i, 0)
        return pl.BlockSpec((1, block, width), at)

    def token(block, width):
        def at(*grid):
            b, _, _, j = ids(*grid)
            return (b, j, 0)
        return pl.BlockSpec((1, block, width), at)

    return head, token


def _pad_rows(x, block):
    """`[B, S, D]` with S padded up to a multiple of `block`."""
    return jnp.pad(x, ((0, 0), (0, (-x.shape[1]) % block), (0, 0)))


_MLA_STATIC = ("causal", "sm_scale", "block_q", "block_k", "interpret")


@functools.partial(jax.jit, static_argnames=_MLA_STATIC + ("need_lse",))
def _mla_fwd_impl(q, kn, kr, v, causal, sm_scale, block_q, block_k,
                  interpret, need_lse):
    """`(out [B, S, H, Dv], lse [B, H, Sq padded, LANES] or None)`."""
    B, S, H, Dq = q.shape
    Dn, Dr, Dv = kn.shape[3], kr.shape[2], v.shape[3]
    qs, kns, vs = (_stage(x, block, None) for x, block in (
        (q, block_q), (kn, block_k), (v, block_k)))
    krs = _pad_rows(kr, block_k)
    Sq, Sk = qs.shape[1], kns.shape[1]
    nq, nk = Sq // block_q, Sk // block_k

    head, token = _latent_specs(H, by_keys=False)
    o_spec = head(block_q, Dv)
    result = _scheduled_call(
        functools.partial(_mla_fwd_kernel, sm_scale=sm_scale,
                          need_lse=need_lse),
        "mla_fwd", (nq, nk), (block_q, block_k), S, causal, None, interpret,
        "latent",
        grid=(B, H, nq, nk),
        in_specs=[head(block_q, Dq), head(block_k, Dn, keys=True),
                  token(block_k, Dr), head(block_k, Dv, keys=True)],
        out_specs=[o_spec] + ([pl.BlockSpec(
            (1, 1, block_q, _LANES), lambda b, h, i, j, _: (b, h, i, 0))]
            if need_lse else []),
        out_shape=[jax.ShapeDtypeStruct((B * H, Sq, Dv), q.dtype)] + (
            [jax.ShapeDtypeStruct((B, H, Sq, _LANES), jnp.float32)]
            if need_lse else []),
        scratch_shapes=[] if nk == 1 else [
            _scratch((block_q, _LANES)), _scratch((block_q, _LANES)),
            _scratch((block_q, Dv))],
    )(qs, kns, krs, vs)
    return (_unstage(result[0], (B, S, H, Dv), None),
            result[1] if need_lse else None)


@functools.partial(jax.jit, static_argnames=_MLA_STATIC)
def _mla_bwd_impl(q, kn, kr, v, out, lse, g, causal, sm_scale, block_q,
                  block_k, interpret):
    B, S, H, Dq = q.shape
    Dn, Dr, Dv = kn.shape[3], kr.shape[2], v.shape[3]
    qs, kns, vs, dos = (_stage(x, block, None) for x, block in (
        (q, block_q), (kn, block_k), (v, block_k), (g, block_q)))
    krs = _pad_rows(kr, block_k)
    Sq, Sk = qs.shape[1], kns.shape[1]
    nq, nk = Sq // block_q, Sk // block_k
    delta = _row_delta(g, out, None, Sq)
    lse_t, delta_t = lse[:, :, None, :], delta[:, :, None, :]
    lse, delta = (jnp.broadcast_to(x[..., None], (B, H, Sq, _LANES))
                  for x in (lse, delta))

    # dq: the forward's grid (B, H, nq, nk)
    by_q, kr_q = _latent_specs(H, by_keys=False)
    stat_q = pl.BlockSpec((1, 1, block_q, _LANES),
                          lambda b, h, i, j, _: (b, h, i, 0))
    dq = _scheduled_call(
        functools.partial(_mla_dq_kernel, sm_scale=sm_scale),
        "mla_dq", (nq, nk), (block_q, block_k), S, causal, None, interpret,
        "latent",
        grid=(B, H, nq, nk),
        in_specs=[by_q(block_q, Dq), by_q(block_k, Dn, keys=True),
                  kr_q(block_k, Dr), by_q(block_k, Dv, keys=True),
                  by_q(block_q, Dv), stat_q, stat_q],
        out_specs=by_q(block_q, Dq),
        out_shape=jax.ShapeDtypeStruct(qs.shape, q.dtype),
        scratch_shapes=[_scratch((block_q, Dq))],
    )(qs, kns, krs, vs, dos, lse, delta)

    # dk/dv: grid (B, nk, H, nq), q innermost, the heads around it
    by_k, kr_k = _latent_specs(H, by_keys=True)
    kr_k = kr_k(block_k, Dr)
    stat_k = pl.BlockSpec((1, 1, 1, block_q),
                          lambda b, j, h, i, _: (b, h, 0, i))
    dkn, dkr, dv = _scheduled_call(
        functools.partial(_mla_dkv_kernel, sm_scale=sm_scale),
        "mla_dkv", (nq, nk), (block_q, block_k), S, causal, None, interpret,
        "latent",
        grid=(B, nk, H, nq),
        in_specs=[by_k(block_q, Dq), by_k(block_k, Dn, keys=True), kr_k,
                  by_k(block_k, Dv, keys=True), by_k(block_q, Dv), stat_k,
                  stat_k],
        out_specs=[by_k(block_k, Dn, keys=True), kr_k,
                   by_k(block_k, Dv, keys=True)],
        out_shape=[jax.ShapeDtypeStruct(kns.shape, kn.dtype),
                   jax.ShapeDtypeStruct(krs.shape, kr.dtype),
                   jax.ShapeDtypeStruct(vs.shape, v.dtype)],
        scratch_shapes=[_scratch((block_k, Dn)), _scratch((block_k, Dr)),
                        _scratch((block_k, Dv))],
    )(qs, kns, krs, vs, dos, lse_t, delta_t)
    return (_unstage(dq, q.shape, None), _unstage(dkn, kn.shape, None),
            dkr[:, :S], _unstage(dv, v.shape, None))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _mla(q, kn, kr, v, causal, sm_scale, block_q, block_k, interpret):
    return _mla_fwd_impl(q, kn, kr, v, causal, sm_scale, block_q, block_k,
                         interpret, need_lse=False)[0]


def _mla_vjp_fwd(q, kn, kr, v, causal, sm_scale, block_q, block_k,
                 interpret):
    out, lse = _residuals(*_mla_fwd_impl(
        q, kn, kr, v, causal, sm_scale, block_q, block_k, interpret,
        need_lse=True))
    return out, (q, kn, kr, v, out, lse)


def _mla_vjp_bwd(causal, sm_scale, block_q, block_k, interpret, res, g):
    return _mla_bwd_impl(*res, g, causal, sm_scale, block_q, block_k,
                         interpret)


_mla.defvjp(_mla_vjp_fwd, _mla_vjp_bwd)


def latent_attention_reference(q, k_nope, k_rope, v, causal=True,
                               sm_scale=None):
    """What `flash_attention_latent` computes, by `attention_reference`
    over the explicit `[k_nope | k_rope]` key a head and the values padded
    to the query's width: for the tests."""
    Dq = q.shape[3]
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        k_rope[:, :, None, :], k_nope.shape[:3] + k_rope.shape[2:])], axis=-1)
    vp = jnp.pad(v, ((0, 0),) * 3 + ((0, Dq - v.shape[3]),))
    return attention_reference(q, k, vp, causal=causal, sm_scale=sm_scale
                               )[..., :v.shape[3]]


def flash_attention_latent(q, k_nope, k_rope, v, causal=True, sm_scale=None,
                           block_q=1024, block_k=1024, interpret=None):
    """Flash attention for latent (MLA) heads: `q` [B, S, H, Dn + Dr] whose
    last `Dr` lanes are rotated, `k_nope` [B, S, H, Dn], `k_rope` [B, S, Dr]
    (one rotated key a token, shared by all H heads), `v` [B, S, H, Dv]:
    `softmax((q_n . k_n + q_r . k_r) * sm_scale) v`, `[B, S, H, Dv]`.
    `sm_scale` defaults to `(Dn + Dr) ** -0.5`.  Differentiable in all
    four; `k_rope`'s gradient is the sum over the heads."""
    if (q.shape[3] != k_nope.shape[3] + k_rope.shape[2]
            or k_nope.shape[2] != q.shape[2] or v.shape[2] != q.shape[2]):
        raise ValueError(
            f"latent attention wants q [B, S, H, Dn + Dr], k_nope [B, S, H, "
            f"Dn], k_rope [B, S, Dr], v [B, S, H, Dv]; got {q.shape}, "
            f"{k_nope.shape}, {k_rope.shape}, {v.shape}")
    sm_scale, block_q, block_k, interpret = _resolve_call_args(
        q, k_nope, sm_scale, block_q, block_k, interpret)
    return _mla(q, k_nope, k_rope, v, causal, sm_scale, block_q, block_k,
                interpret)
