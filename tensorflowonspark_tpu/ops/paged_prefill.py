"""Pallas paged-prefill flash attention: in-place page writes, O(chunk).

The blend write path (models/transformer._paged_attention_body) routes a
prefill chunk's k/v into the paged pool with one-hot einsums
(``bsn,bso,bshd->nohd``) over ALL ``kv_pages x page`` positions, then
reads attention context by gathering each row's FULL logical
``[max_seq, n_kv, Dh]`` view out of the pool — per chunk that is
O(pool) write traffic and O(max_seq) read traffic no matter how short
the chunk is.  Prefill-role replicas and the host-tier warm-miss path
live in this loop, so it sets time to first token directly.

This module is the prefill twin of ops/paged_attention.py (the PR-4
flash-decode read) and closes ROADMAP open item 1 with two kernels:

- a PAGE-WRITE kernel: the page table and per-row start offsets are
  scalar-prefetched, each grid step DMAs exactly one physical pool page
  to VMEM, blends the chunk positions that land in it (one-hot matmul,
  the same routing rule as the einsum blend — including the
  clip-at-last-block behaviour of bucket-pad overshoot), and stores the
  page back through ``input_output_aliases`` — per-chunk write bytes
  scale with ceil(S/page)+1 pages, not with the pool;
- a chunked flash-attention READ kernel: online softmax over
  [earlier context pages || current chunk] — context pages stream
  straight out of the pool (clamped index_map + ``pl.when``, only
  occupied pages visited, ops/paged_attention.py discipline), the
  chunk's own k/v come from the activations, and the causal
  ``j <= start + s`` rule splits into "all context visible" + an
  in-chunk triangle.  No dense ``[B, max_seq]`` kv view ever exists.

int8 pools: the chunk is quantized ONCE (bit-identical to
models/transformer._kv_quantize — deterministic f32 round/clip, so the
pool bytes match the blend exactly) and the payload + scale-page writes
ride the same in-place page store; the read kernel dequantizes context
pages inside the page read like the decode kernel.  Scale pools keep
their canonical ``[kv_pages, page, n_kv]`` layout on the write side (it
is the cache schema and the kv-migration wire format); the read side
uses the transposed-scales copy trick from ops/paged_attention.py.

Sink-page contract (serve.ContinuousBatcher): page-table entries past a
row's allocation and the whole table of a pad row alias a reserved
garbage sink page.  The write kernel honours it by construction — it
routes through the table like the blend, so pad rows and bucket-pad
overshoot land in the sink; concurrent sink stores from different rows
may race on TPU (the blend sums them instead) but sink bytes are
garbage by contract and masked on every read.

``interpret=`` threads through ops.default_interpret(), so CPU tier-1
executes these exact kernel bodies in the Pallas interpreter.
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


def _quantize(x):
    """Symmetric per-(token, head) int8 over head_dim.  MUST stay
    bit-identical to models/transformer._kv_quantize (deterministic f32
    round/clip): the kernel path requantizes the chunk itself, and pool
    bytes only match the blend reference because both quantizers agree.
    Duplicated here so ops never imports models (import cycle)."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q8 = jnp.clip(jnp.round(xf / scale[..., None]), -127,
                  127).astype(jnp.int8)
    return q8, scale


def _dequantize(q8, scale, dtype):
    return (q8.astype(jnp.float32) * scale[..., None]).astype(dtype)


# ------------------------------------------------------------ write -----


def _page_write_kernel(table_ref, starts_ref, k_ref, v_ref, *rest,
                       page, s_chunk, max_pages, quant):
    """Grid (B, W): step (b, w) owns logical block start//page + w of
    row b and stores the chunk positions routed to it into the block's
    physical page (brought in by the index_map)."""
    if quant:
        ks_ref, vs_ref = rest[:2]
        pk_in, pv_in, pks_in, pvs_in = rest[2:6]
        pk_out, pv_out, pks_out, pvs_out = rest[6:]
    else:
        pk_in, pv_in, pk_out, pv_out = rest
    b = pl.program_id(0)
    w = pl.program_id(1)
    start = starts_ref[b]
    lb = start // page + w

    # blocks past the table are CLAMPED by the index_map onto the
    # previous step's page, whose out-block VMEM buffer is retained
    # (same index -> no flush/refetch): a skipped step must not touch
    # out_ref or it would overwrite the predecessor's stores with the
    # stale pre-write in_ref content
    @pl.when(lb < max_pages)
    def _store():
        # hit[p, s]: the blend routes chunk position s to offset p of
        # THIS block — same rule as the einsum write, including the
        # clip(pos//page, 0, max_pages-1) that parks bucket-pad
        # overshoot in the last logical block (the sink, by contract)
        pos = start + jax.lax.broadcasted_iota(
            jnp.int32, (page, s_chunk), 1)
        blk = jnp.clip(pos // page, 0, max_pages - 1)
        offs = jax.lax.broadcasted_iota(jnp.int32, (page, s_chunk), 0)
        hit = (blk == lb) & ((pos % page) == offs)
        oh = hit.astype(jnp.float32)                 # [page, S]
        row = jnp.any(hit, axis=1)[:, None, None]    # [page, 1, 1]

        def _blend(chunk_ref, in_ref, out_ref):
            # one-hot matmul = the dynamic shift start%page (and, like
            # the einsum, a SUM where clipped positions collide); f32
            # accumulation is exact for the one-term rows
            x = chunk_ref[0].astype(jnp.float32)     # [S, n_kv, Dh]
            n_kv, dh = x.shape[1], x.shape[2]
            new = jax.lax.dot_general(
                oh, x.reshape(s_chunk, n_kv * dh),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            cur = in_ref[0]                          # [page, n_kv, Dh]
            out_ref[0] = jnp.where(
                row, new.reshape(page, n_kv, dh).astype(cur.dtype), cur)

        _blend(k_ref, pk_in, pk_out)
        _blend(v_ref, pv_in, pv_out)
        if quant:

            def _blend_scale(sc_ref, in_ref, out_ref):
                new = jax.lax.dot_general(
                    oh, sc_ref[0], (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)  # [page, n_kv]
                out_ref[0] = jnp.where(row[:, :, 0], new, in_ref[0])

            _blend_scale(ks_ref, pks_in, pks_out)
            _blend_scale(vs_ref, pvs_in, pvs_out)


def _write_pages(k_st, v_st, k_sc, v_sc, pages_key, pages_value,
                 key_scales, value_scales, table, starts, *, interpret):
    """In-place page store: returns the updated pool leaves (inputs are
    aliased to outputs, so under jit the pool never copies)."""
    B, S, n_kv, Dh = k_st.shape
    NP, page = pages_key.shape[:2]
    max_pages = table.shape[1]
    quant = k_sc is not None
    # a chunk touches at most ceil(S/page)+1 logical blocks (the +1 is
    # the straddle of an unaligned start)
    W = -(-S // page) + 1

    def _block(b, w, table_ref, starts_ref):
        lb = starts_ref[b] // page + w
        return table_ref[b, jnp.minimum(lb, max_pages - 1)]

    chunk_spec = pl.BlockSpec((1, S, n_kv, Dh),
                              lambda b, w, tr, sr: (b, 0, 0, 0))
    pool_spec = pl.BlockSpec(
        (1, page, n_kv, Dh),
        lambda b, w, tr, sr: (_block(b, w, tr, sr), 0, 0, 0))
    in_specs = [chunk_spec, chunk_spec]
    inputs = [k_st, v_st]
    out_specs = [pool_spec, pool_spec]
    out_shape = [jax.ShapeDtypeStruct(pages_key.shape, pages_key.dtype),
                 jax.ShapeDtypeStruct(pages_value.shape,
                                      pages_value.dtype)]
    if quant:
        csc_spec = pl.BlockSpec((1, S, n_kv),
                                lambda b, w, tr, sr: (b, 0, 0))
        # scale pools stay in their canonical [NP, page, n_kv] layout:
        # this is the cache schema and the kv-migration wire format, and
        # the blocks are tiny (4/Dh of the payload bytes)
        psc_spec = pl.BlockSpec(
            (1, page, n_kv),
            lambda b, w, tr, sr: (_block(b, w, tr, sr), 0, 0))
        in_specs += [csc_spec, csc_spec]
        inputs += [k_sc, v_sc]
        out_specs += [psc_spec, psc_spec]
        out_shape += [
            jax.ShapeDtypeStruct(key_scales.shape, key_scales.dtype),
            jax.ShapeDtypeStruct(value_scales.shape, value_scales.dtype)]
    pool_inputs = [pages_key, pages_value]
    pool_in_specs = [pool_spec, pool_spec]
    if quant:
        pool_inputs += [key_scales, value_scales]
        pool_in_specs += [psc_spec, psc_spec]
    # input_output_aliases indices COUNT the scalar-prefetch operands
    # (table, starts), then chunk payloads (+ chunk scales), then pools
    first_pool = 2 + len(inputs)
    aliases = {first_pool + i: i for i in range(len(pool_inputs))}

    kernel = functools.partial(
        _page_write_kernel, page=page, s_chunk=S, max_pages=max_pages,
        quant=quant)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, W),
        in_specs=in_specs + pool_in_specs,
        out_specs=out_specs)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        input_output_aliases=aliases,
        interpret=interpret,
        name="paged_prefill_write",
    )(table, starts, *inputs, *pool_inputs)


# ------------------------------------------------------------- read -----


def _prefill_read_kernel(table_ref, starts_ref, q_ref, ck_ref, cv_ref,
                         pk_ref, pv_ref, *rest, sm_scale, page, s_chunk,
                         group, n_ctx, quant):
    """Grid (B, n_kv, n_ctx + 1): j < n_ctx walks row b's occupied
    context pages, j == n_ctx folds in the chunk's own k/v and
    normalizes — one online softmax over [context || chunk]."""
    if quant:
        ks_ref, vs_ref = rest[:2]
        rest = rest[2:]
    out_ref, m_scr, l_scr, acc_scr = rest
    b = pl.program_id(0)
    j = pl.program_id(2)
    start = starts_ref[b]

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full(m_scr.shape, NEG_INF, m_scr.dtype)
        l_scr[:] = jnp.zeros(l_scr.shape, l_scr.dtype)
        acc_scr[:] = jnp.zeros(acc_scr.shape, acc_scr.dtype)

    def _online(k, v, kmask):
        q = q_ref[0, 0].astype(jnp.float32)          # [ROWS, Dh]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = jnp.where(kmask, s * sm_scale, NEG_INF)
        m_prev = m_scr[:, :1]                        # [ROWS, 1]
        l_prev = l_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[:] = acc_scr[:] * alpha + pv
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    # context pages: every chunk query sits at or past `start`, so the
    # causal rule degenerates to "positions < start are visible" — the
    # straddled page's fresh chunk positions (>= start) are masked off
    # here and come from the activations below instead.  Pages at or
    # past start skip compute (their DMA was clamped onto the last
    # occupied page by the index_map, which pallas elides as a re-fetch)
    @pl.when((j < n_ctx) & (j * page < start))
    def _ctx():
        k = pk_ref[0, :, 0, :].astype(jnp.float32)   # [page, Dh]
        v = pv_ref[0, :, 0, :].astype(jnp.float32)
        if quant:
            # int8 dequant fused into the page read, decode-kernel style
            k = k * ks_ref[0, 0][:, None]
            v = v * vs_ref[0, 0][:, None]
        k_pos = j * page + jax.lax.broadcasted_iota(
            jnp.int32, (1, page), 1)
        _online(k, v, k_pos < start)

    # the chunk itself: row r of the grouped q block is (query position
    # r//group, GQA member r%group); chunk key jc is visible iff
    # jc <= r//group (the j <= start + s rule with both sides >= start)
    @pl.when(j == n_ctx)
    def _chunk():
        k = ck_ref[0, :, 0, :].astype(jnp.float32)   # [S, Dh]
        v = cv_ref[0, :, 0, :].astype(jnp.float32)
        rows = out_ref.shape[2]
        jc = jax.lax.broadcasted_iota(jnp.int32, (rows, s_chunk), 1)
        qs = jax.lax.broadcasted_iota(jnp.int32, (rows, s_chunk), 0)
        _online(k, v, jc <= qs // group)
        # every query sees at least its own position, so l > 0 for all
        # live rows; the guard only shields the ROWS padding
        out_ref[0, 0] = acc_scr[:] / jnp.maximum(l_scr[:, :1], 1e-30)


def _read_attention(q, ck, cv, pages_key, pages_value, key_scales,
                    value_scales, table, starts, *, sm_scale, interpret):
    """Flash attention of the chunk against [context pages || chunk]."""
    B, S, H, Dh = q.shape
    NP, page, n_kv = pages_key.shape[:3]
    max_pages = table.shape[1]
    quant = key_scales is not None
    group = H // n_kv
    rows = S * group
    # grouped-q rows pad to the sublane tile of q's dtype
    mult = 8 if q.dtype == jnp.float32 else 16
    ROWS = max(mult, -(-rows // mult) * mult)
    q_r = q.reshape(B, S, n_kv, group, Dh).transpose(0, 2, 1, 3, 4)
    q_r = q_r.reshape(B, n_kv, rows, Dh)
    if ROWS != rows:
        q_r = jnp.pad(q_r, ((0, 0), (0, 0), (0, ROWS - rows), (0, 0)))

    def _ctx_page(b, h, j, table_ref, starts_ref):
        # clamp at the last occupied context page so steps past the
        # context re-name the previous block (pallas elides the re-fetch)
        last = jnp.maximum(starts_ref[b] - 1, 0) // page
        return table_ref[b, jnp.minimum(j, last)]

    q_spec = pl.BlockSpec((1, 1, ROWS, Dh),
                          lambda b, h, j, tr, sr: (b, h, 0, 0))
    chunk_spec = pl.BlockSpec((1, S, 1, Dh),
                              lambda b, h, j, tr, sr: (b, 0, h, 0))
    kv_spec = pl.BlockSpec(
        (1, page, 1, Dh),
        lambda b, h, j, tr, sr: (_ctx_page(b, h, j, tr, sr), 0, h, 0))
    out_spec = pl.BlockSpec((1, 1, ROWS, Dh),
                            lambda b, h, j, tr, sr: (b, h, 0, 0))
    in_specs = [q_spec, chunk_spec, chunk_spec, kv_spec, kv_spec]
    inputs = [q_r, ck, cv, pages_key, pages_value]
    if quant:
        # minor-dim = page axis so the scale blocks are lane-tiled; this
        # copies the (small) scale arrays only, never the payload pool
        sc_spec = pl.BlockSpec(
            (1, 1, page),
            lambda b, h, j, tr, sr: (_ctx_page(b, h, j, tr, sr), h, 0))
        in_specs += [sc_spec, sc_spec]
        inputs += [key_scales.transpose(0, 2, 1),
                   value_scales.transpose(0, 2, 1)]

    kernel = functools.partial(
        _prefill_read_kernel, sm_scale=float(sm_scale), page=page,
        s_chunk=S, group=group, n_ctx=max_pages, quant=quant)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n_kv, max_pages + 1),
        in_specs=in_specs,
        out_specs=[out_spec],
        scratch_shapes=[
            _scratch((ROWS, _LANES)),
            _scratch((ROWS, _LANES)),
            _scratch((ROWS, Dh)),
        ])
    (out,) = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, n_kv, ROWS, Dh),
                                        jnp.float32)],
        interpret=interpret,
        name="paged_prefill_read",
    )(table, starts, *inputs)
    out = out[:, :, :rows].reshape(B, n_kv, S, group, Dh)
    return out.transpose(0, 2, 1, 3, 4).reshape(B, S, H, Dh).astype(q.dtype)


# ---------------------------------------------------------- wrapper -----


def paged_prefill(q, k, v, pages_key, pages_value, page_table, starts, *,
                  key_scales=None, value_scales=None, sm_scale=None,
                  interpret=None):
    """Chunked prefill over an in-place paged kv pool: page-granular
    writes, then flash attention over [context pages || chunk].

    Args:
      q, k, v: ``[B, S, *, Dh]`` chunk activations (q has H heads, k/v
        the narrow n_kv) — the PR-5 batched ragged prefill layout, one
        row per admitted request (pad rows carry a sink page table).
      pages_key / pages_value: the pool, ``[kv_pages, page, n_kv, Dh]``
        — activation dtype, or int8 with ``key_scales``/``value_scales``
        ``[kv_pages, page, n_kv]`` f32 (the chunk is requantized here,
        bit-identical to the blend's storage).
      page_table: ``[B, max_pages]`` int32; entries past a row's
        allocation MUST alias the caller's sink page (they do receive
        bucket-pad overshoot writes).
      starts: ``[B]`` int32 pre-write positions (the row's cache_index
        before this chunk): chunk position s lands at ``starts + s`` and
        sees keys ``j <= starts + s``.

    Returns ``(out, pools)``: ``out [B, S, H, Dh]`` in q's dtype, and
    ``pools = (pages_key, pages_value, key_scales, value_scales)`` — the
    updated pool leaves (inputs are aliased to outputs so the pool
    updates in place under jit; scale leaves are None without int8).
    """
    B, S, H, Dh = q.shape
    NP, page, n_kv, Dh_kv = pages_key.shape
    if pages_value.shape != pages_key.shape or Dh_kv != Dh:
        raise ValueError(
            f"pool shapes {pages_key.shape} / {pages_value.shape} must "
            f"match and end in head_dim {Dh}")
    if k.shape != (B, S, n_kv, Dh) or v.shape != k.shape:
        raise ValueError(
            f"chunk k/v {k.shape} / {v.shape} must be "
            f"{(B, S, n_kv, Dh)}")
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
    if sm_scale is None:
        sm_scale = 1.0 / (Dh ** 0.5)
    if interpret is None:
        from tensorflowonspark_tpu.ops import default_interpret
        interpret = default_interpret()
    table = page_table.astype(jnp.int32)
    starts = starts.astype(jnp.int32)

    if quant:
        k_st, k_sc = _quantize(k)
        v_st, v_sc = _quantize(v)
        # the read side sees exactly what a pool round-trip would give
        # (quantization is deterministic, so this matches the blend
        # reference bit for bit)
        ck = _dequantize(k_st, k_sc, k.dtype)
        cv = _dequantize(v_st, v_sc, v.dtype)
    else:
        k_st, v_st, k_sc, v_sc = k, v, None, None
        ck, cv = k, v

    pools = _write_pages(k_st, v_st, k_sc, v_sc, pages_key, pages_value,
                         key_scales, value_scales, table, starts,
                         interpret=interpret)
    new_pk, new_pv = pools[0], pools[1]
    new_ks = pools[2] if quant else None
    new_vs = pools[3] if quant else None
    # the read walks the POST-write pool: context pages are byte-equal
    # either way, and the straddled page's fresh positions are masked
    out = _read_attention(q, ck, cv, new_pk, new_pv, new_ks, new_vs,
                          table, starts, sm_scale=sm_scale,
                          interpret=interpret)
    return out, (new_pk, new_pv, new_ks, new_vs)
