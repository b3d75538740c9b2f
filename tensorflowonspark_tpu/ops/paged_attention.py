"""Pallas paged flash-decode attention: read kv pages in place.

The paged slot cache (models/transformer._paged_attention_body) keeps kv
in a shared pool ``pages_key/pages_value [kv_pages, page, n_kv, Dh]``
with a per-row ``page_table [B, max_pages]`` naming each row's pages.
The reference read path gathers every row's FULL logical ``[max_seq,
n_kv, Dh]`` view out of the pool (``jnp.take`` over the whole table),
materializes the GQA head expansion, and softmaxes over ``max_seq``
masked positions — O(max_seq) HBM traffic per decoded token regardless
of how many tokens each row actually holds.

This kernel is the vLLM-PagedAttention / Flash-Decoding fix:

- the page table and per-row lengths are SCALAR-PREFETCHED
  (``pltpu.PrefetchScalarGridSpec``), so each kv BlockSpec index_map
  looks the physical page up and DMAs it straight out of the pool — no
  logical-view gather ever materializes;
- q heads are grouped onto their kv head inside the kernel (the block
  holds one kv head's whole GQA group), so the repeated kv of
  ``_kv_repeat`` never exists in HBM;
- pages past a row's true length are never read: the index_map clamps
  the page index at the row's last occupied page (consecutive grid
  steps then name the SAME block, whose re-fetch Pallas elides) and
  ``pl.when`` skips their compute entirely;
- online softmax (running max / denominator / accumulator in VMEM
  scratch, f32) over the visited pages only;
- split-K over the page axis: each split emits an unnormalized partial
  (acc, m, l) and a jax-side logsumexp combine merges them — the
  flash-decoding shape that keeps long-context single-token decode from
  serializing over one long page walk;
- int8 kv dequantizes INSIDE the page read (payload block + per-token
  scale block, multiplied after the f32 cast), so the wide cache never
  exists anywhere;
- ``interpret=`` threads through (ops.default_interpret()), so the CPU
  tier executes this exact kernel body in the Pallas interpreter.

Layout notes: block shapes are built from runtime dims (``page``,
``Dh``, ``ROWS``) — on TPU, best layouts want head_dim a multiple of
128 and page_size a multiple of the dtype sublane tile (8 f32 / 16 bf16
/ 32 int8); any sizes are CORRECT, Mosaic pads the rest.  The int8
scale pools are transposed to ``[kv_pages, n_kv, page]`` before the
call so their minor dim is the page axis — a per-step copy of the
scale arrays only (4/Dh of the int8 payload bytes, ~3% at Dh=128),
never of the payload pool.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30  # large-finite: exp(NEG_INF - m) == 0 without inf-inf NaNs
_LANES = 128     # m/l carry a lane-replicated trailing dim for layout


def _scratch(shape, dtype=jnp.float32):
    return pltpu.VMEM(shape, dtype)


def _pick_splits(requested, max_pages):
    """Largest split count <= requested that DIVIDES the page axis (a
    ragged tail split would need its own masked page range for zero
    win; every divisor keeps the per-split walk uniform)."""
    for cand in range(min(int(requested), max_pages), 1, -1):
        if max_pages % cand == 0:
            return cand
    return 1


def _decode_kernel(table_ref, len_ref, q_ref, k_ref, v_ref, *rest,
                   sm_scale, page, s_chunk, group, n_per, quant):
    if quant:
        ks_ref, vs_ref = rest[:2]
        rest = rest[2:]
    acc_ref, m_ref, l_ref, m_scr, l_scr, acc_scr = rest
    b = pl.program_id(0)
    sp = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full(m_scr.shape, NEG_INF, m_scr.dtype)
        l_scr[:] = jnp.zeros(l_scr.shape, l_scr.dtype)
        acc_scr[:] = jnp.zeros(acc_scr.shape, acc_scr.dtype)

    pidx = sp * n_per + j          # logical page this grid step covers
    n_tok = len_ref[b]             # row's written length (incl. chunk)

    # only occupied pages are visited: everything at or past the row's
    # length bound skips compute (its DMA was clamped to the last
    # occupied page by the index_map, which pallas elides as a re-fetch)
    @pl.when(pidx * page < n_tok)
    def _visit():
        q = q_ref[0, 0].astype(jnp.float32)          # [ROWS, Dh]
        k = k_ref[0, :, 0, :].astype(jnp.float32)    # [page, Dh]
        v = v_ref[0, :, 0, :].astype(jnp.float32)
        if quant:
            # int8 dequant fused into the page read: payload * per-token
            # scale, after the f32 cast (the wide kv never materializes)
            k = k * ks_ref[0, 0][:, None]
            v = v * vs_ref[0, 0][:, None]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * sm_scale
        # row r of the grouped q block is (query s_chunk-pos r//group,
        # group member r%group); key j is visible iff j <= idx + s with
        # idx = n_tok - s_chunk (the slot-cache visibility rule)
        k_pos = pidx * page + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        q_pos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // group
        s = jnp.where(k_pos <= (n_tok - s_chunk) + q_pos, s, NEG_INF)

        m_prev = m_scr[:, :1]                        # [ROWS, 1]
        l_prev = l_scr[:, :1]
        m_blk = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_blk)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                       # [ROWS, page]
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[:] = acc_scr[:] * alpha + pv
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(j == n_per - 1)
    def _finish():
        acc_ref[0, 0, 0] = acc_scr[:]
        m_ref[0, 0, 0] = m_scr[:]
        l_ref[0, 0, 0] = l_scr[:]


def paged_attention(q, pages_key, pages_value, page_table, lengths, *,
                    key_scales=None, value_scales=None, sm_scale=None,
                    k_splits=8, interpret=None):
    """Flash-decode attention over an in-place paged kv pool.

    Args:
      q: ``[B, S, H, Dh]`` query chunk (S=1 decode steps, S>1 prefill
        chunks).
      pages_key / pages_value: the pool, ``[kv_pages, page, n_kv, Dh]``
        — activation dtype, or int8 with ``key_scales``/``value_scales``
        ``[kv_pages, page, n_kv]`` f32 (per-(token, head) symmetric
        scales, transformer._kv_quantize's storage form).
      page_table: ``[B, max_pages]`` int32 physical page per logical
        block.  Entries past a row's length are never read (the walk is
        clamped at the row's last occupied page).
      lengths: ``[B]`` int32 — tokens WRITTEN per row, including the
        current chunk (the post-write cache_index).  Query position s
        sees key j iff ``j <= lengths - S + s``; rows must satisfy
        ``lengths >= S`` (queries with no visible key — possible only
        below that — get unspecified values; ``lengths == 0`` rows
        return exact zeros).
      k_splits: target split-K parallelism over the page axis (clamped
        to a divisor of max_pages).

    Returns ``[B, S, H, Dh]`` in q's dtype.
    """
    B, S, H, Dh = q.shape
    NP, page, n_kv, Dh_kv = pages_key.shape
    if pages_value.shape != pages_key.shape or Dh_kv != Dh:
        raise ValueError(
            f"pool shapes {pages_key.shape} / {pages_value.shape} must "
            f"match and end in head_dim {Dh}")
    if H % n_kv:
        raise ValueError(
            f"q heads {H} must be a multiple of kv heads {n_kv} (GQA "
            "groups map onto their kv head inside the kernel)")
    quant = pages_key.dtype == jnp.int8
    if quant and (key_scales is None or value_scales is None):
        raise ValueError("int8 pools need key_scales and value_scales "
                         "[kv_pages, page, n_kv]")
    if not quant and (key_scales is not None or value_scales is not None):
        raise ValueError("scales are only meaningful for int8 pools")
    max_pages = page_table.shape[1]
    if sm_scale is None:
        sm_scale = 1.0 / (Dh ** 0.5)
    if interpret is None:
        from tensorflowonspark_tpu.ops import default_interpret
        interpret = default_interpret()

    group = H // n_kv
    rows = S * group
    # grouped-q rows pad to the sublane tile of q's dtype
    mult = 8 if q.dtype == jnp.float32 else 16
    ROWS = max(mult, -(-rows // mult) * mult)
    q_r = q.reshape(B, S, n_kv, group, Dh).transpose(0, 2, 1, 3, 4)
    q_r = q_r.reshape(B, n_kv, rows, Dh)
    if ROWS != rows:
        q_r = jnp.pad(q_r, ((0, 0), (0, 0), (0, ROWS - rows), (0, 0)))

    n_splits = _pick_splits(k_splits, max_pages)
    n_per = max_pages // n_splits
    table = page_table.astype(jnp.int32)
    lengths = lengths.astype(jnp.int32)

    def _page_idx(b, h, sp, j, table_ref, len_ref):
        # clamp at the row's last occupied page so out-of-bound grid
        # steps re-name the previous block (pallas elides the re-fetch)
        pidx = sp * n_per + j
        last = jnp.maximum(len_ref[b] - 1, 0) // page
        return table_ref[b, jnp.minimum(pidx, last)]

    q_spec = pl.BlockSpec((1, 1, ROWS, Dh),
                          lambda b, h, sp, j, tr, lr: (b, h, 0, 0))
    kv_spec = pl.BlockSpec(
        (1, page, 1, Dh),
        lambda b, h, sp, j, tr, lr: (_page_idx(b, h, sp, j, tr, lr),
                                     0, h, 0))
    out_spec = pl.BlockSpec((1, 1, 1, ROWS, Dh),
                            lambda b, h, sp, j, tr, lr: (b, h, sp, 0, 0))
    red_spec = pl.BlockSpec((1, 1, 1, ROWS, _LANES),
                            lambda b, h, sp, j, tr, lr: (b, h, sp, 0, 0))
    in_specs = [q_spec, kv_spec, kv_spec]
    inputs = [q_r, pages_key, pages_value]
    if quant:
        # minor-dim = page axis so the scale blocks are lane-tiled; this
        # copies the (small) scale arrays only, never the payload pool
        sc_spec = pl.BlockSpec(
            (1, 1, page),
            lambda b, h, sp, j, tr, lr: (_page_idx(b, h, sp, j, tr, lr),
                                         h, 0))
        in_specs += [sc_spec, sc_spec]
        inputs += [key_scales.transpose(0, 2, 1),
                   value_scales.transpose(0, 2, 1)]

    kernel = functools.partial(
        _decode_kernel, sm_scale=float(sm_scale), page=page, s_chunk=S,
        group=group, n_per=n_per, quant=quant)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n_kv, n_splits, n_per),
        in_specs=in_specs,
        out_specs=[out_spec, red_spec, red_spec],
        scratch_shapes=[
            _scratch((ROWS, _LANES)),
            _scratch((ROWS, _LANES)),
            _scratch((ROWS, Dh)),
        ])
    acc, m, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, n_kv, n_splits, ROWS, Dh),
                                 jnp.float32),
            jax.ShapeDtypeStruct((B, n_kv, n_splits, ROWS, _LANES),
                                 jnp.float32),
            jax.ShapeDtypeStruct((B, n_kv, n_splits, ROWS, _LANES),
                                 jnp.float32),
        ],
        interpret=interpret,
        name="paged_decode",
    )(table, lengths, *inputs)

    # LSE combine across splits: out = sum_s e^{m_s - M} acc_s /
    # sum_s e^{m_s - M} l_s.  Splits past a row's pages carry (m=-inf,
    # l=0, acc=0) and drop out; rows with NO visible key anywhere
    # (lengths == 0) hit the denominator guard and return exact zeros.
    m0, l0 = m[..., 0], l[..., 0]            # [B, n_kv, splits, ROWS]
    mx = jnp.max(m0, axis=2)
    w = jnp.exp(m0 - mx[:, :, None])
    denom = jnp.maximum(jnp.sum(w * l0, axis=2), 1e-30)
    out = jnp.sum(w[..., None] * acc, axis=2) / denom[..., None]
    out = out[:, :, :rows].reshape(B, n_kv, S, group, Dh)
    return out.transpose(0, 2, 1, 3, 4).reshape(B, S, H, Dh).astype(q.dtype)


def paged_attention_reference(q, pages_key, pages_value, page_table,
                              lengths, *, key_scales=None,
                              value_scales=None, sm_scale=None):
    """Dense gather reference with the kernel's exact semantics (f32
    softmax, large-finite mask, lengths-relative visibility) — the
    oracle for the parity tests, shaped like the einsum read body in
    models/transformer._paged_attention_body.  Rows with ``lengths ==
    0`` return zeros, matching the kernel's empty-row definition."""
    B, S, H, Dh = q.shape
    NP, page, n_kv, _ = pages_key.shape
    L = page_table.shape[1] * page
    if sm_scale is None:
        sm_scale = 1.0 / (Dh ** 0.5)
    kb = jnp.take(pages_key, page_table, axis=0)   # [B, mp, page, n_kv, Dh]
    vb = jnp.take(pages_value, page_table, axis=0)
    if pages_key.dtype == jnp.int8:
        ks = jnp.take(key_scales, page_table, axis=0)
        vs = jnp.take(value_scales, page_table, axis=0)
        kb = kb.astype(jnp.float32) * ks[..., None]
        vb = vb.astype(jnp.float32) * vs[..., None]
    kf = kb.reshape(B, L, n_kv, Dh).astype(jnp.float32)
    vf = vb.reshape(B, L, n_kv, Dh).astype(jnp.float32)
    if n_kv != H:
        kf = jnp.repeat(kf, H // n_kv, axis=2)
        vf = jnp.repeat(vf, H // n_kv, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        kf) * sm_scale
    idx = lengths - S
    visible = (jnp.arange(L)[None, None, :]
               <= (idx[:, None, None] + jnp.arange(S)[None, :, None]))
    logits = jnp.where(visible[:, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, vf)
    out = jnp.where(lengths[:, None, None, None] > 0, out, 0.0)
    return out.astype(q.dtype)
