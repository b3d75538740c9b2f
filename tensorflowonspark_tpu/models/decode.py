"""Autoregressive generation loop over the Transformer's kv cache.

Net-new relative to the reference (its inference paths are batch
feed-forward only: pipeline.py:585-644, TFModel.scala:245-292 map batches
through a saved model).  TPU-idiomatic generation: the per-token step is one
jitted function with STATIC shapes — the kv cache is a fixed
[B, max_seq_len, n_kv_heads, head_dim] buffer updated in place via
dynamic_update_slice (models/transformer.py Attention._decode_attention) —
and the token loop is a lax.scan, so the whole generation compiles once and
stays on-device.
"""
import dataclasses
import functools
import threading

import jax
import jax.numpy as jnp


def _params_view(params, cfg=None):
    """Model-ready view of `params` inside a jitted program.

    Quantized weight leaves (int8 ``{"q", "scale"}`` dicts and int4
    ``Int4Weight`` from `quantize.quantize_tree`) route one of two ways,
    picked by the owning model's ``cfg.quant_matmul_impl``:

    - ``"kernel"`` (default): 2-D leaves stay QUANTIZED
      (`quantize.qdense_view`) and `transformer.QuantDense` consumes
      them through the Pallas fused-dequant matmul
      (ops/quant_matmul.py) — weight tiles dequantize in VMEM, so the
      dense kernel never exists in HBM and each decode step reads ~2x
      (int8) / ~4x (int4) fewer weight bytes than the W16 serving store
      (decode is weight-bandwidth bound).
    - ``"dequant"`` (and ``cfg=None``, e.g. non-Transformer callers):
      leaves dequantize HERE, under the trace — XLA fuses the
      ``q.astype(f32) * scale`` into the consuming matmul's operand
      read (the pre-kernel behavior, kept as the parity oracle and the
      sharded fallback).

    Unquantized trees pass through untouched; the walk happens at trace
    time only.  Every jitted decode entry point routes params through
    this, so quantized trees work in solo `generate`, streaming,
    speculative rounds, and the serving slot engine alike.
    """
    from tensorflowonspark_tpu.quantize import dequantize_tree, qdense_view

    if (cfg is not None
            and getattr(cfg, "quant_matmul_impl", "dequant") == "kernel"):
        return qdense_view(params)
    return dequantize_tree(params)


def init_cache(model_or_cfg, batch_size, kv_dtype=None):
    """Build the decode-mode model + empty cache.

    Accepts a Transformer (or its config); returns (decode_model, cache).
    The cache is all-zeros by construction, so only its SHAPES are derived
    from the model (jax.eval_shape — no throwaway parameter init, no
    transient 2x parameter HBM).  ``kv_dtype`` overrides the config's
    cache storage ("int8" = quantized kv, TransformerConfig.kv_dtype).
    """
    from tensorflowonspark_tpu.models.transformer import (
        Transformer, TransformerConfig)

    cfg = (model_or_cfg.cfg if isinstance(model_or_cfg, Transformer)
           else model_or_cfg)
    if not isinstance(cfg, TransformerConfig):
        raise TypeError(f"expected Transformer or TransformerConfig, "
                        f"got {type(model_or_cfg)}")
    decode_model = Transformer(dataclasses.replace(
        cfg, decode=True,
        **({"kv_dtype": kv_dtype} if kv_dtype is not None else {})))
    shapes = jax.eval_shape(
        lambda: decode_model.init(jax.random.key(0),
                                  jnp.zeros((batch_size, 1), jnp.int32)))
    cache = jax.tree_util.tree_map(
        lambda a: jnp.zeros(a.shape, a.dtype), shapes["cache"])
    return decode_model, cache


@functools.lru_cache(maxsize=32)
def _jitted_step(decode_model):
    """One compiled decode step per model config (cached across generate()
    calls — linen modules hash by their config fields).  Params are an
    ARGUMENT, not a closure constant, so repeated calls hit the jit cache
    and sharded (e.g. Megatron-TP) params work: the compiler propagates
    their shardings through the cache update."""

    @jax.jit
    def step(params, tokens, cache):
        logits, mut = decode_model.apply(
            {"params": _params_view(params, decode_model.cfg),
             "cache": cache}, tokens,
            mutable=["cache"])
        return logits[:, -1], mut["cache"]

    return step


@functools.lru_cache(maxsize=32)
def _jitted_step_all(decode_model):
    """Like _jitted_step but returns logits at EVERY fed position — the
    verify pass of speculative decoding needs the target's next-token
    distribution after each proposed token, not just the last."""

    @jax.jit
    def step(params, tokens, cache):
        logits, mut = decode_model.apply(
            {"params": _params_view(params, decode_model.cfg),
             "cache": cache}, tokens,
            mutable=["cache"])
        return logits, mut["cache"]

    return step


@functools.lru_cache(maxsize=32)
def _jitted_decode_body(decode_model, greedy, with_eos):
    """One fused host-loop decode step: model apply + token pick + eos
    masking in a single dispatch.  `greedy`/`with_eos` are static (part
    of the cache key); params/temperature/eos_id are arguments so
    parameter trees don't trigger retraces.  The sampling-control
    arguments (``topks``/``topps`` filter arrays, ``seen``/``rep``
    repetition-penalty state) are PRESENCE-static like the slot step's:
    omitted -> the exact plain program; passed -> dynamic device arrays,
    so sweeping top_p values (or penalty rates) never recompiles."""

    # the cache (argnum 2) is donated: each step's dynamic_update_slice
    # then writes in place instead of copying hundreds of MB of kv per
    # token; the host loop rebinds the returned cache and never touches
    # the donated one again
    @functools.partial(jax.jit, donate_argnums=(2,))
    def body(params, tok, cache, done, rng_t, temperature, eos_id,
             topks=None, topps=None, minps=None, seen=None,
             rep=None):
        logits, mut = decode_model.apply(
            {"params": _params_view(params, decode_model.cfg),
             "cache": cache}, tok[:, None],
            mutable=["cache"])
        logits = logits[:, -1]
        if seen is not None:
            seen = seen.at[jnp.arange(tok.shape[0]), tok].set(1)
            logits = apply_repetition_penalty(logits, seen, rep)
        if greedy:
            nxt = jnp.argmax(logits, axis=-1)
        else:
            scaled = logits / temperature
            if topks is not None:
                scaled = filter_top_k_p(scaled, topks, topps, minps)
            nxt = jax.random.categorical(rng_t, scaled, axis=-1)
        if with_eos:
            nxt = jnp.where(done, eos_id, nxt)
            done = done | (nxt == eos_id)
        if seen is not None:
            return nxt, mut["cache"], done, seen
        return nxt, mut["cache"], done

    return body


# --------------------------------------------------------------- slots ----
# Continuous-batching primitives: a `decode_slots=True` model keeps a
# PER-ROW cache_index, so every batch row is an independent serving slot.
# New requests prefill into a free row while the other rows keep decoding;
# finished rows retire at token boundaries (serve.ContinuousBatcher drives
# these).  Net-new beyond the reference (its serving is batch forward
# only, TFModel.scala:245-292).

def init_slot_cache(model_or_cfg, n_slots, page_size=0, n_pages=0,
                    kv_dtype=None, paged_attn_impl=None,
                    paged_prefill_impl=None, table_pages=0):
    """Build the slot-decode model + empty cache with `n_slots` rows.
    ``page_size``/``n_pages`` > 0 switches to the PAGED kv layout
    (see `init_paged_slot_cache`); ``kv_dtype="int8"`` quantizes the
    cache storage (TransformerConfig.kv_dtype); ``paged_attn_impl``
    picks the paged READ path ("kernel" = the Pallas flash-decode
    kernel, "einsum" = the gather reference —
    TransformerConfig.paged_attn_impl; None keeps the config's);
    ``paged_prefill_impl`` picks the paged S>1 chunk path ("kernel" =
    the Pallas in-place page-write + chunked flash read, "blend" = the
    one-hot einsum blend reference —
    TransformerConfig.paged_prefill_impl; None keeps the config's);
    ``table_pages`` > 0 starts every row's page table at that width
    instead of the full ``max_seq_len // page_size``
    (TransformerConfig.kv_table_pages — the growable-table layout;
    callers widen with `_jitted_grow_page_table` as rows outgrow it)."""
    from tensorflowonspark_tpu.models.transformer import (
        Transformer, TransformerConfig)

    cfg = (model_or_cfg.cfg if isinstance(model_or_cfg, Transformer)
           else model_or_cfg)
    if not isinstance(cfg, TransformerConfig):
        raise TypeError(f"expected Transformer or TransformerConfig, "
                        f"got {type(model_or_cfg)}")
    slot_model = Transformer(
        dataclasses.replace(
            cfg, decode=True, decode_slots=True,
            kv_page_size=page_size, kv_pages=n_pages,
            kv_table_pages=table_pages,
            **({"kv_dtype": kv_dtype} if kv_dtype is not None else {}),
            **({"paged_attn_impl": paged_attn_impl}
               if paged_attn_impl is not None else {}),
            **({"paged_prefill_impl": paged_prefill_impl}
               if paged_prefill_impl is not None else {})))
    shapes = jax.eval_shape(
        lambda: slot_model.init(jax.random.key(0),
                                jnp.zeros((n_slots, 1), jnp.int32)))
    cache = jax.tree_util.tree_map(
        lambda a: jnp.zeros(a.shape, a.dtype), shapes["cache"])
    return slot_model, cache


def init_paged_slot_cache(model_or_cfg, n_slots, page_size, n_pages,
                          kv_dtype=None, paged_attn_impl=None,
                          paged_prefill_impl=None, table_pages=0):
    """Build a PAGED slot-decode model + empty cache: kv lives in a
    shared pool of ``n_pages`` pages of ``page_size`` tokens, mapped per
    row through a page table (TransformerConfig.kv_page_size).  The
    serving layer owns page allocation (serve.ContinuousBatcher's free
    list); `_jitted_set_row_page_table` installs a row's pages before
    its prefill.  CALLER CONTRACT: reserve one pool page as a garbage
    SINK and point every unallocated/retired table entry at it — tail
    blocks DO receive writes (bucket-padded prefill overshoot,
    post-retirement garbage steps), so entries must never default to a
    page another row owns (serve.ContinuousBatcher allocates
    kv_pages + 1 and uses the extra page as the sink).

    ``table_pages`` > 0 allocates the tables at that INITIAL width
    instead of the full ``max_seq_len // page_size`` — the growable
    layout: short-prompt workloads then pay table bytes proportional to
    what they actually map, and `_jitted_grow_page_table` widens every
    row geometrically (sink-padded tails) when a long prompt outgrows
    the current width.  0 keeps the historical full-width tables."""
    return init_slot_cache(model_or_cfg, n_slots, page_size=page_size,
                           n_pages=n_pages, kv_dtype=kv_dtype,
                           paged_attn_impl=paged_attn_impl,
                           paged_prefill_impl=paged_prefill_impl,
                           table_pages=table_pages)


def _leaf_name(path):
    last = path[-1]
    return getattr(last, "key", getattr(last, "name", None))


def _first_named_leaf(tree, name):
    """First leaf whose path ends in `name` (every layer agrees on the
    per-row index shapes, so one representative leaf is enough)."""
    found = []

    def look(path, leaf):
        if _leaf_name(path) == name and not found:
            found.append(leaf)
        return leaf

    jax.tree_util.tree_map_with_path(look, tree)
    return found[0]


_POOL_LEAVES = ("pages_key", "pages_value",   # dim 0 = pool, not rows
                "pages_key_scale", "pages_value_scale")  # int8 kv scales

_DENSE_KV_LEAVES = ("cached_key", "cached_value",   # dim 0 = rows
                    "cached_key_scale", "cached_value_scale")


def _path_str(path):
    """Stable string form of a tree path — the block name the kv
    migration wire format keys device arrays by.  Source and destination
    replicas build the same model config, hence the same tree structure,
    hence identical path strings."""
    return "/".join(str(getattr(p, "key", getattr(p, "name", p)))
                    for p in path)


@functools.lru_cache(maxsize=32)
def _jitted_set_row_page_table(slot_model):
    """Install row `row`'s page mapping (serving-side allocation): every
    layer's page_table gets `entries` [max_pages] at that row."""

    # donate: the cache (incl. the full kv pool) must update in place —
    # an undonated call would copy multi-GB of pool per admission
    @functools.partial(jax.jit, donate_argnums=(0,))
    def set_table(cache, row, entries):
        def set_leaf(path, leaf):
            if _leaf_name(path) == "page_table":
                return leaf.at[row].set(entries.astype(jnp.int32))
            return leaf

        return jax.tree_util.tree_map_with_path(set_leaf, cache)

    return set_table


@functools.lru_cache(maxsize=64)
def _jitted_grow_page_table(slot_model, new_width):
    """Widen every layer's page_table to `new_width` entries (the
    growable-table splice): existing mappings keep their columns, the
    new tail columns fill with the `sink` page id — the same
    tails-alias-the-sink contract `_jitted_set_row_page_table` relies
    on, so the widened table is immediately safe to step.  One cache
    entry (and one trace) per (model, width); serving grows in pow2
    steps, so the jit cache stays O(log max_width) like the per-width
    retraces of the step/prefill jits themselves."""

    # donate: the pool leaves pass through untouched and must not copy;
    # the page_table leaves change shape, so those reallocate (tiny —
    # [n_slots, new_width] int32)
    @functools.partial(jax.jit, donate_argnums=(0,))
    def grow(cache, sink):
        def grow_leaf(path, leaf):
            if _leaf_name(path) != "page_table":
                return leaf
            b, w = leaf.shape
            pad = jnp.full((b, new_width - w), sink, jnp.int32)
            return jnp.concatenate([leaf, pad], axis=1)

        return jax.tree_util.tree_map_with_path(grow_leaf, cache)

    return grow


# ---- kv migration helpers (kvtransfer.MigrationEngine) ------------------
# A migrating row's occupied kv leaves the device exactly once (gather ->
# copy_to_host_async on the source) and re-enters exactly once (scatter
# into freshly allocated pages / the destination row).  Page-id vectors
# are pow2-padded by the caller — pad entries point at the SINK page, so
# both the gather's extra reads and the scatter's pad writes are
# harmless by the same contract prefill overshoot relies on.


@functools.lru_cache(maxsize=32)
def _jitted_gather_pages(slot_model):
    """Snapshot pool pages `ids` ([n] int32) out of every pool leaf:
    {path: leaf[ids]} — fresh buffers, so the pool can keep stepping
    while the snapshot rides device->host."""

    @jax.jit
    def gather(cache, ids):
        out = {}

        def look(path, leaf):
            if _leaf_name(path) in _POOL_LEAVES:
                out[_path_str(path)] = jnp.take(leaf, ids, axis=0)
            return leaf

        jax.tree_util.tree_map_with_path(look, cache)
        return out

    return gather


@functools.lru_cache(maxsize=32)
def _jitted_scatter_pages(slot_model):
    """Write migrated page blocks ({path: [n, page, ...]}) into pool
    pages `ids` ([n] int32; pad entries = sink)."""

    @functools.partial(jax.jit, donate_argnums=(0,))
    def scatter(cache, ids, blocks):
        # callers (submit_resume validation) guarantee `blocks` carries
        # one entry per pool leaf, so the branch is purely structural
        def set_leaf(path, leaf):
            if _leaf_name(path) not in _POOL_LEAVES:
                return leaf
            blk = blocks[_path_str(path)]
            return leaf.at[ids].set(blk.astype(leaf.dtype))

        return jax.tree_util.tree_map_with_path(set_leaf, cache)

    return scatter


@functools.lru_cache(maxsize=32)
def _jitted_gather_row_kv(slot_model):
    """Dense-cache analog of `_jitted_gather_pages`: snapshot row `row`'s
    full kv window out of every cached_* leaf ({path: [max_seq, ...]}).
    Positions past the row's cache_index hold garbage the causal mask
    never exposes — shipping the whole window keeps this one compile."""

    @jax.jit
    def gather(cache, row):
        out = {}

        def look(path, leaf):
            if _leaf_name(path) in _DENSE_KV_LEAVES:
                out[_path_str(path)] = jax.lax.dynamic_index_in_dim(
                    leaf, row, 0, keepdims=False)
            return leaf

        jax.tree_util.tree_map_with_path(look, cache)
        return out

    return gather


@functools.lru_cache(maxsize=32)
def _jitted_scatter_row_kv(slot_model):
    """Install migrated dense-row blocks at row `row`."""

    @functools.partial(jax.jit, donate_argnums=(0,))
    def scatter(cache, row, blocks):
        # as in _jitted_scatter_pages: one block per dense kv leaf is a
        # caller invariant, so the branch is purely structural
        def set_leaf(path, leaf):
            if _leaf_name(path) not in _DENSE_KV_LEAVES:
                return leaf
            blk = blocks[_path_str(path)]
            return jax.lax.dynamic_update_index_in_dim(
                leaf, blk.astype(leaf.dtype), row, 0)

        return jax.tree_util.tree_map_with_path(set_leaf, cache)

    return scatter


@functools.lru_cache(maxsize=32)
def _jitted_set_row_index(slot_model):
    """Set ONE row's cache_index/pos_index (resume-from-pages: the
    migrated row rejoins decode at its committed position; `_set_cache_
    index` sets all rows, `_set_row_indices_vec` needs a full vector)."""

    @functools.partial(jax.jit, donate_argnums=(0,))
    def set_idx(cache, row, value):
        value32 = jnp.asarray(value, jnp.int32)

        def set_leaf(path, leaf):
            if _leaf_name(path) in ("cache_index", "pos_index"):
                return leaf.at[row].set(value32)
            return leaf

        return jax.tree_util.tree_map_with_path(set_leaf, cache)

    return set_idx


def _reset_row_indices(row_cache, value):
    """Set every per-row index leaf (cache_index / pos_index) of a sliced
    single-row cache to `value`."""
    value = jnp.asarray(value, jnp.int32)

    def set_leaf(path, leaf):
        if _leaf_name(path) in ("cache_index", "pos_index"):
            return jnp.full(leaf.shape, value, jnp.int32)
        return leaf

    return jax.tree_util.tree_map_with_path(set_leaf, row_cache)


def _slot_prefill_body(slot_model, variables, cache, chunk, row, start,
                       n_valid):
    """Shared prefill core (plain and LoRA builders wrap it): slice row
    `row` out of the batch cache, run the chunk through it starting at
    position `start`, write the row back."""
    # pool leaves (paged kv) are SHARED across rows: they pass into
    # the row apply whole and come back whole; per-row leaves
    # (cached kv, indices, page_table) slice to the row
    def _slice(path, a):
        if _leaf_name(path) in _POOL_LEAVES:
            return a
        return jax.lax.dynamic_slice_in_dim(a, row, 1, 0)

    row_cache = jax.tree_util.tree_map_with_path(_slice, cache)
    row_cache = _reset_row_indices(row_cache, start)
    logits, mut = slot_model.apply(
        dict(variables, cache=row_cache), chunk, mutable=["cache"])
    new_row = _reset_row_indices(mut["cache"], start + n_valid)

    def _write(path, full, upd):
        if _leaf_name(path) in _POOL_LEAVES:
            return upd
        return jax.lax.dynamic_update_slice_in_dim(full, upd, row, 0)

    cache = jax.tree_util.tree_map_with_path(_write, cache, new_row)
    last = jax.lax.dynamic_slice_in_dim(logits, n_valid - 1, 1, 1)
    return last[:, 0], cache          # [1, V], updated batch cache


@functools.lru_cache(maxsize=32)
def _jitted_slot_prefill(slot_model):
    """Prefill ONE slot row with one prompt CHUNK.  `chunk` is
    bucket-padded to a static length; `n_valid` (traced) is the number of
    real tokens in it — the row index lands at ``start + n_valid`` so the
    pad tail is never visible to later steps.  The returned logits are
    the LAST valid position's distribution (only meaningful on the final
    chunk of a prompt).  Whole-prompt prefill is the single-chunk case
    (start=0, n_valid=true_len)."""

    @functools.partial(jax.jit, donate_argnums=(1,))
    def prefill(params, cache, chunk, row, start, n_valid):
        return _slot_prefill_body(
            slot_model,
            {"params": _params_view(params, slot_model.cfg)}, cache, chunk,
            row, start, n_valid)

    return prefill


def _slot_step_body(slot_model, variables, toks, temps, seeds, ords,
                    topks=None, topps=None, minps=None, seen=None,
                    reps=None, rems=None, eoss=None, eos_on=None):
    """Shared decode-step core: feed each row its current token, per-row
    greedy/sampled pick (`temps[b] == 0` = greedy).

    Sampling keys follow the SHARED schedule (`step_keys`): row b's noise
    for its new-token ordinal ``ords[b]`` is ``fold_in(key(seeds[b]),
    ords[b])`` — a pure function of the request seed and position, so a
    slot run reproduces a solo `generate(rng=key(seed))` token-for-token
    (same dtype/program caveats aside).  All chains live device-side so
    the serving loop issues exactly ONE dispatch per token — every extra
    per-step device op (a host fold_in, an h2d of tokens) is another
    dispatch on the step's critical path (cost on this chip: not
    measured).

    ``topks``/``topps`` (presence is STATIC — omitting them compiles the
    exact unfiltered program) apply per-row top-k / nucleus filtering to
    the temperature-scaled logits (`filter_top_k_p`); disabled rows
    (k=0, p=1.0) keep the full distribution.  ``seen``/``reps`` (also
    statically present) apply per-row repetition penalty to the RAW
    logits first (`apply_repetition_penalty`; the fed token joins `seen`
    before the penalty, and the updated mask is returned as an extra
    output).

    ``rems``/``eoss``/``eos_on`` (statically present, like the sampling
    extras) move the per-step STOP decision on-device: row b's remaining
    budget decrements and ``done[b]`` is raised when the budget hits zero
    or the picked token equals its eos id (``eos_on`` masks rows with no
    eos configured).  The async serving engine reads ``done`` from the
    readback chunk instead of inspecting tokens on the host, so the
    device thread never blocks on token values to decide whether to keep
    dispatching."""
    logits, mut = slot_model.apply(variables, toks[:, None],
                                   mutable=["cache"])
    logits = logits[:, -1]
    if seen is not None:
        seen = seen.at[jnp.arange(toks.shape[0]), toks].set(1)
        logits = apply_repetition_penalty(logits, seen, reps)
    greedy = jnp.argmax(logits, axis=-1)
    keys = jax.vmap(
        lambda s, t: jax.random.fold_in(jax.random.key(s), t))(
            seeds, ords)
    scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
    if topks is not None:
        scaled = filter_top_k_p(scaled, topks, topps, minps)
    sampled = jax.vmap(jax.random.categorical)(keys, scaled)
    pick = jnp.where(temps > 0, sampled, greedy)
    out = (pick, mut["cache"], ords + 1)
    if seen is not None:
        out = out + (seen,)
    if rems is not None:
        rems2 = rems - 1
        done = (rems2 <= 0) | (eos_on & (pick == eoss))
        out = out + (rems2, done)
    return out


@functools.lru_cache(maxsize=32)
def _jitted_slot_step(slot_model):
    """One decode step over ALL slots (see `_slot_step_body`)."""

    @functools.partial(jax.jit, donate_argnums=(1,),
                       donate_argnames=("seen", "rems"))
    def step(params, cache, toks, temps, seeds, ords,
             topks=None, topps=None, minps=None, seen=None,
             reps=None, rems=None, eoss=None, eos_on=None):
        return _slot_step_body(
            slot_model,
            {"params": _params_view(params, slot_model.cfg),
             "cache": cache},
            toks, temps, seeds, ords, topks, topps, minps, seen,
            reps, rems, eoss, eos_on)

    return step


def _lora_with_ids(lora, ids):
    """Insert the per-row adapter-id array into a lora bank tree: every
    dict level holding adapter banks (a ``*_a`` key) gets ``ids`` — the
    layout transformer.Attention._proj reads (serve.ContinuousBatcher
    builds the bank tree; ids are the only per-step-varying leaves)."""
    def walk(node):
        if isinstance(node, dict):
            out = {k: walk(v) for k, v in node.items()}
            if any(k.endswith("_a") for k in node):
                out["ids"] = ids
            return out
        return node

    return walk(lora)


@functools.lru_cache(maxsize=32)
def _jitted_slot_step_lora(slot_model):
    """`_jitted_slot_step` with a per-row LoRA adapter bank: the SAME
    `_slot_step_body`, plus the ``lora`` collection (banks + resident
    [n_slots] adapter ids) threaded into the apply — N tenants share the
    one batched step (multi-adapter serving; see
    transformer.Attention._proj for the math and the null-adapter-0
    convention)."""

    @functools.partial(jax.jit, donate_argnums=(2,),
                       donate_argnames=("seen", "rems"))
    def step(params, lora, cache, toks, temps, seeds, ords, ids,
             topks=None, topps=None, minps=None, seen=None,
             reps=None, rems=None, eoss=None, eos_on=None):
        return _slot_step_body(
            slot_model,
            {"params": _params_view(params, slot_model.cfg),
             "cache": cache,
             "lora": _lora_with_ids(lora, ids)},
            toks, temps, seeds, ords, topks, topps, minps, seen,
            reps, rems, eoss, eos_on)

    return step


@functools.lru_cache(maxsize=32)
def _jitted_slot_prefill_lora(slot_model):
    """`_jitted_slot_prefill` with a LoRA bank: the SAME
    `_slot_prefill_body`, with the joining row prefilling under ITS
    adapter (``adapter_id``; the sliced row apply runs at batch 1, so
    ids is the one-element array)."""

    @functools.partial(jax.jit, donate_argnums=(2,))
    def prefill(params, lora, cache, chunk, row, start, n_valid,
                adapter_id):
        ids = jnp.full((1,), adapter_id, jnp.int32)
        return _slot_prefill_body(
            slot_model,
            {"params": _params_view(params, slot_model.cfg),
             "lora": _lora_with_ids(lora, ids)},
            cache, chunk, row, start, n_valid)

    return prefill


def _slot_prefill_many_body(slot_model, variables, cache, chunks, rows,
                            starts, n_valids, sink):
    """Batched multi-row prefill core: ONE dispatch writes one
    bucket-padded chunk for up to P rows (serve.ContinuousBatcher's
    admission pipeline batches waiting requests' chunks here instead of
    dispatching width-1 prefills that leave the MXU idle).

    ``chunks`` [P, bucket] int32; ``rows``/``starts``/``n_valids`` [P]
    int32 give each row's slot index, cache write offset (prefix-cache
    skip), and true token count inside its padded chunk.  The
    decode_slots attention already computes per-row positions from the
    per-row index leaves, so rows at DIFFERENT offsets batch into one
    apply.  PAD rows carry row index == n_slots: out of bounds by
    construction, so their per-row gathers CLIP to the last real row
    (read-only, harmless) and their writebacks scatter-DROP (JAX
    out-of-bounds semantics), while their page tables are overridden
    with the ``sink`` page — the paged pool write SUMS over batch rows,
    so a pad row writing through a clipped table would corrupt a live
    row's pages.  Valid rows must be DISTINCT for the same reason (a
    duplicated row would double-write its pool pages).  Returns
    (last-valid-position logits [P, V], updated batch cache).
    """
    rows = rows.astype(jnp.int32)
    n_slots = _first_named_leaf(cache, "cache_index").shape[0]
    valid = rows < n_slots

    def _gather(path, a):
        if _leaf_name(path) in _POOL_LEAVES:
            return a                          # shared pool: pass whole
        g = a[rows]                           # OOB (pad rows) clips
        if _leaf_name(path) == "page_table":
            g = jnp.where(valid[:, None], g, jnp.asarray(sink, jnp.int32))
        return g

    sub = jax.tree_util.tree_map_with_path(_gather, cache)
    sub = _set_row_indices_vec(sub, starts)
    logits, mut = slot_model.apply(dict(variables, cache=sub), chunks,
                                   mutable=["cache"])
    new_sub = _set_row_indices_vec(mut["cache"], starts + n_valids)

    def _write(path, full, upd):
        if _leaf_name(path) in _POOL_LEAVES:
            return upd                        # updated in place by apply
        return full.at[rows].set(upd)         # OOB (pad rows) drops

    cache = jax.tree_util.tree_map_with_path(_write, cache, new_sub)
    pick = jnp.clip(n_valids - 1, 0, chunks.shape[1] - 1)
    last = jnp.take_along_axis(logits, pick[:, None, None], axis=1)[:, 0]
    return last, cache                        # [P, V], updated cache


@functools.lru_cache(maxsize=32)
def _jitted_slot_prefill_many(slot_model):
    """Batched multi-row prefill: one chunk for up to P rows per
    dispatch (`_slot_prefill_many_body`).  Chunk width and row count are
    static shapes — the serving layer pads both to power-of-2 buckets
    (`build_prefill_batch`), so compile count stays bounded by
    O(log(prefill_chunk) * log(prefill_rows)) variants."""

    @functools.partial(jax.jit, donate_argnums=(1,))
    def prefill(params, cache, chunks, rows, starts, n_valids, sink):
        return _slot_prefill_many_body(
            slot_model,
            {"params": _params_view(params, slot_model.cfg)}, cache,
            chunks, rows, starts, n_valids, sink)

    return prefill


@functools.lru_cache(maxsize=32)
def _jitted_slot_prefill_many_lora(slot_model):
    """`_jitted_slot_prefill_many` with per-row LoRA adapter identities:
    each admitting row prefills under ITS adapter (``adapter_ids`` [P];
    pad rows use the null adapter 0, whose delta is exactly zero)."""

    @functools.partial(jax.jit, donate_argnums=(2,))
    def prefill(params, lora, cache, chunks, rows, starts, n_valids,
                sink, adapter_ids):
        return _slot_prefill_many_body(
            slot_model,
            {"params": _params_view(params, slot_model.cfg),
             "lora": _lora_with_ids(lora, adapter_ids.astype(jnp.int32))},
            cache, chunks, rows, starts, n_valids, sink)

    return prefill


def build_prefill_batch(entries, width, bucket, n_slots):
    """Host-side slot builder for one batched prefill dispatch.

    ``entries`` is [(row, chunk_tokens, start)] for up to ``width``
    admitting rows; the result pads to the STATIC (width, bucket)
    dispatch shape.  Pad rows take row index ``n_slots`` — out of
    bounds by construction, so their writebacks scatter-drop and the
    jit substitutes the sink page table (`_slot_prefill_many_body`).
    Returns (chunks, rows, starts, n_valids) device-ready for
    `_jitted_slot_prefill_many`."""
    import numpy as np

    assert len(entries) <= width, (len(entries), width)
    assert len({row for row, _, _ in entries}) == len(entries), \
        "duplicate rows in one prefill dispatch would double-write " \
        "their pool pages (the paged cache write sums over batch rows)"
    chunks = np.zeros((width, bucket), np.int32)
    rows = np.full((width,), n_slots, np.int32)
    starts = np.zeros((width,), np.int32)
    n_valids = np.ones((width,), np.int32)
    for i, (row, toks, start) in enumerate(entries):
        assert 0 < len(toks) <= bucket, (len(toks), bucket)
        chunks[i, :len(toks)] = toks
        rows[i] = row
        starts[i] = start
        n_valids[i] = len(toks)
    return (jnp.asarray(chunks), jnp.asarray(rows), jnp.asarray(starts),
            jnp.asarray(n_valids))


@functools.lru_cache(maxsize=32)
def _jitted_set_row(slot_model):
    """Tiny device update used at slot joins: place the joining request's
    first token / temperature / sampling chain / stop bookkeeping into
    row `row` of the resident arrays.  NOT donated: the serving loop may
    still hold readback chunks aliasing the old buffers."""

    @jax.jit
    def set_row(toks, temps, seeds, ords, topks, topps, minps, rems,
                eoss, eos_on, row, tok, temp, seed, ordinal, topk, topp,
                minp, rem, eos, eon):
        return (toks.at[row].set(tok), temps.at[row].set(temp),
                seeds.at[row].set(seed), ords.at[row].set(ordinal),
                topks.at[row].set(topk), topps.at[row].set(topp),
                minps.at[row].set(minp), rems.at[row].set(rem),
                eoss.at[row].set(eos), eos_on.at[row].set(eon))

    return set_row


def _set_row_indices_vec(cache, values):
    """Set every per-row index leaf (cache_index / pos_index) of the full
    slot cache to the per-row `values` [n_slots] (speculative rewind)."""
    values = jnp.asarray(values, jnp.int32)

    def set_leaf(path, leaf):
        last = path[-1]
        name = getattr(last, "key", getattr(last, "name", None))
        if name in ("cache_index", "pos_index"):
            return jnp.broadcast_to(values, leaf.shape).astype(jnp.int32)
        return leaf

    return jax.tree_util.tree_map_with_path(set_leaf, cache)


@functools.lru_cache(maxsize=32)
def _jitted_slot_spec_round(t_model, d_model, k):
    """One fused speculative round over ALL slots (greedy rows only):
    k unrolled draft slot-steps propose, ONE target pass over the [n, k]
    block verifies, per-row longest-prefix acceptance commits 1..k tokens,
    and BOTH caches rewind per row — a single dispatch per round.

    Returns ``(new_toks, t_next [n, k], commit [n], t_cache, d_cache)``:
    row r committed ``commit[r]`` tokens this round, which are
    ``t_next[r, :commit[r]]`` (every committed token is the target's own
    greedy choice — speculation changes speed, never tokens).  Unlike the
    grouped `speculative_generate` (batch-min acceptance), acceptance is
    PER ROW: each slot advances at its own agreement rate.  Inactive rows
    decode garbage the serving loop's generation filter drops; their
    cache writes land beyond any live region and rewind with everyone
    else.

    With ``rems``/``eoss``/``eos_on`` (statically present) the per-row
    stop decision joins the round on-device: ``n_del[r]`` is how many of
    the committed tokens are DELIVERABLE — committed, within the row's
    remaining budget, and not past its first eos — and ``done[r]`` is
    raised when the budget is exhausted or an eos landed among the
    delivered tokens.  Mirrors exactly the host loop's
    per-token remaining/eos walk over ``t_next[r, :commit[r]]``.
    Returns ``(new_toks, t_next, commit, n_del, done, rems_new,
    t_cache, d_cache)`` in that mode."""

    @functools.partial(jax.jit, donate_argnums=(2, 3),
                       donate_argnames=("rems",))
    def spec_round(t_params, d_params, t_cache, d_cache, toks,
                   rems=None, eoss=None, eos_on=None):
        t_params = _params_view(t_params, t_model.cfg)
        d_params = _params_view(d_params, d_model.cfg)
        # per-row committed length = cache_index before this round (all
        # layers agree; read one leaf)
        idx = _first_named_leaf(t_cache, "cache_index")
        props = []
        d_tok = toks
        for _ in range(k):                      # unrolled: k static
            d_logits, mut = d_model.apply(
                {"params": d_params, "cache": d_cache}, d_tok[:, None],
                mutable=["cache"])
            d_cache = mut["cache"]
            d_tok = jnp.argmax(d_logits[:, -1], axis=-1)
            props.append(d_tok)
        props = jnp.stack(props, axis=1)                     # [n, k]
        block = jnp.concatenate([toks[:, None], props[:, :-1]], axis=1)
        t_logits, mut = t_model.apply(
            {"params": t_params, "cache": t_cache}, block,
            mutable=["cache"])
        t_cache = mut["cache"]
        t_next = jnp.argmax(t_logits, axis=-1)               # [n, k]
        matches = props == t_next
        a = jnp.where(matches.all(axis=1), k - 1,
                      jnp.argmin(matches, axis=1))           # [n], <= k-1
        commit = a + 1                                       # 1..k tokens
        new_toks = jnp.take_along_axis(t_next, a[:, None], axis=1)[:, 0]
        new_idx = idx + commit
        t_cache = _set_row_indices_vec(t_cache, new_idx)
        d_cache = _set_row_indices_vec(d_cache, new_idx)
        if rems is None:
            return new_toks, t_next, commit, t_cache, d_cache
        # deliverable = committed AND within budget AND not past the
        # first eos (inclusive) — the host loop's per-token walk, batched
        mask = jnp.arange(k)[None, :] < commit[:, None]
        is_eos = eos_on[:, None] & (t_next == eoss[:, None]) & mask
        j_eos = jnp.where(is_eos.any(axis=1), jnp.argmax(is_eos, axis=1),
                          k)                                 # [n], k = none
        n_del = jnp.minimum(commit,
                            jnp.minimum(jnp.maximum(rems, 0), j_eos + 1))
        rems_new = rems - n_del
        done = (rems_new <= 0) | (j_eos < n_del)
        return (new_toks, t_next, commit, n_del, done, rems_new,
                t_cache, d_cache)

    return spec_round


# Speculation v2 key schedule: the three extra random draws of a spec
# round (draft proposal, acceptance test, residual resample) each live
# in their own stream, derived per POSITION ordinal `o` as
# fold_in(fold_in(key(seed), o), TAG).  The plain path's sampling key is
# the single-fold fold_in(key(seed), o) (`step_keys`), which the spec
# path never consumes — so the draws at a given ordinal are identical
# no matter how ordinals are grouped into rounds, making sampled
# speculative output invariant to draft length, adaptive-k timing, and
# fault-injected fallbacks to plain rounds.
_SPEC_DRAFT_TAG = 1
_SPEC_ACCEPT_TAG = 2
_SPEC_RESAMPLE_TAG = 3


def _spec_pos_keys(seeds, ords, i, tag):
    """Per-row keys for in-round position `i` of stream `tag` (see the
    schedule note above)."""
    return jax.vmap(lambda s, o: jax.random.fold_in(
        jax.random.fold_in(jax.random.key(s), o + i), tag))(seeds, ords)


def ngram_propose(ctx, ctx_len, k, max_match=3):
    """Model-free draft: propose ``k`` continuation tokens per row by
    suffix-matching the row's OWN context (prompt-lookup decoding).

    ``ctx [n, C]`` holds each row's committed tokens (prompt + delivered
    output), ``ctx_len [n]`` the valid length; ``ctx[r, ctx_len[r]-1]``
    is the token being fed this round.  Each position re-matches the
    block-so-far suffix (up to ``max_match`` tokens, longest match wins,
    most recent site breaks ties) against the context with earlier
    proposals VIRTUALLY appended — so proposal ``i`` is a pure function
    of the row's committed prefix at that ordinal, independent of where
    round boundaries fall.  That invariance is what keeps sampled
    speculative output seed-deterministic under adaptive draft lengths.
    Rows with no match (or no context) fall back to repeating their
    last token — a still-lossless guess.  Zero weight bytes, zero
    FLOPs beyond [n, C] integer compares."""
    n, C = ctx.shape
    rows = jnp.arange(n)
    pos = jnp.arange(C)[None, :]
    ctx_v = ctx
    props = []
    for i in range(k):
        len_v = ctx_len + i
        # the last `max_match` tokens of the block-so-far (clip-gathered;
        # short rows mask the affected match terms below)
        tail = [jnp.take_along_axis(
            ctx_v, jnp.clip(len_v - 1 - g, 0, C - 1)[:, None],
            axis=1)[:, 0] for g in range(max_match)]
        score = jnp.zeros((n, C), jnp.int32)
        chain = jnp.ones((n, C), bool)
        shifted = ctx_v
        for g in range(max_match):
            chain = (chain & (shifted == tail[g][:, None])
                     & (len_v >= g + 1)[:, None])
            score = score + chain.astype(jnp.int32)
            # compare position j-(g+1) next round: shift right, j=0 invalid
            shifted = jnp.concatenate(
                [jnp.full((n, 1), -1, ctx_v.dtype), shifted[:, :-1]], axis=1)
        # candidate j needs a continuation inside the valid region
        # (j <= len-2, which also excludes the trivial self-match)
        valid = pos <= (len_v - 2)[:, None]
        rank = jnp.where(valid & (score >= 1), score * (C + 1) + pos, -1)
        j_star = jnp.argmax(rank, axis=1)
        found = jnp.take_along_axis(rank, j_star[:, None], axis=1)[:, 0] >= 0
        cont = jnp.take_along_axis(
            ctx_v, jnp.clip(j_star + 1, 0, C - 1)[:, None], axis=1)[:, 0]
        prop = jnp.where(found, cont, tail[0])
        props.append(prop)
        # virtual append (full rows drop instead of clobbering the tail)
        ctx_v = ctx_v.at[rows, len_v].set(prop, mode="drop")
    return jnp.stack(props, axis=1)


def _ngram_append(ctx, ctx_len, c_tok, n_del):
    """Commit this round's deliverable tokens into the n-gram table:
    scatter ``c_tok[r, :n_del[r]]`` at ``ctx_len[r]`` (masked positions
    are pushed out of range and DROPPED, matching the paged cache's
    OOB-write semantics)."""
    n, k = c_tok.shape
    pos = ctx_len[:, None] + jnp.arange(k)[None, :]
    pos = jnp.where(jnp.arange(k)[None, :] < n_del[:, None], pos,
                    ctx.shape[1])
    ctx = ctx.at[jnp.arange(n)[:, None], pos].set(c_tok, mode="drop")
    return ctx, ctx_len + n_del


def spec_accept_sampled(t_logits, props, temps, seeds, ords, topks=None,
                        topps=None, minps=None, q_logits=None):
    """Canonical speculative-sampling acceptance walk (Leviathan et al.;
    Chen et al.) over one verify block — the pure math, factored out so
    distribution preservation is testable without an engine.

    ``t_logits [n, k, V]`` are the target's raw logits at the k block
    positions, ``props [n, k]`` the proposed tokens, ``q_logits`` the
    proposer's (scaled+filtered) logits or None for point-mass proposals
    (n-gram / greedy drafts).  Position i accepts with probability
    min(1, p_i(x_i)/q_i(x_i)) — computed division-free as
    ``u*q < p`` — and the first rejection resamples from the residual
    max(p - q, 0) (for point masses: p with the proposal zeroed),
    renormalized.  Chained over positions this reproduces the target's
    sampling distribution EXACTLY for any proposal distribution, which
    is the lossless guarantee.  Randomness comes from the tagged
    per-position streams above, so outputs are reproducible and
    round-boundary invariant.

    Returns ``(c_tok [n, k], commit [n])``: row r commits
    ``c_tok[r, :commit[r]]`` — accepted proposals plus either the
    resampled correction or (full acceptance) the last proposal."""
    n, k, _ = t_logits.shape
    rows = jnp.arange(n)
    scaled = t_logits / jnp.maximum(temps, 1e-6)[:, None, None]
    if topks is not None:
        p_sl = jnp.stack([filter_top_k_p(scaled[:, i], topks, topps, minps)
                          for i in range(k)], axis=1)
    else:
        p_sl = scaled
    p_probs = jax.nn.softmax(p_sl, axis=-1)
    p_prop = jnp.take_along_axis(p_probs, props[..., None], axis=-1)[..., 0]
    if q_logits is None:
        q_probs = None
        q_prop = jnp.ones_like(p_prop)
    else:
        q_probs = jax.nn.softmax(q_logits, axis=-1)
        q_prop = jnp.take_along_axis(q_probs, props[..., None],
                                     axis=-1)[..., 0]
    u = jnp.stack([jax.vmap(jax.random.uniform)(
        _spec_pos_keys(seeds, ords, i, _SPEC_ACCEPT_TAG))
        for i in range(k)], axis=1)                           # [n, k]
    accept = u * q_prop < p_prop        # u < min(1, p/q), division-free
    j = jnp.where(accept.all(axis=1), k, jnp.argmin(accept, axis=1))
    if q_probs is None:
        res = p_probs.at[rows[:, None], jnp.arange(k)[None, :],
                         props].set(0.0)
    else:
        res = jnp.maximum(p_probs - q_probs, 0.0)
    # degenerate residual (p == q to float precision) falls back to p
    res_ok = res.sum(axis=-1, keepdims=True) > 1e-9
    res_l = jnp.where(res_ok, jnp.where(res > 0, jnp.log(res), -jnp.inf),
                      p_sl)
    y = jnp.stack([jax.vmap(jax.random.categorical)(
        _spec_pos_keys(seeds, ords, i, _SPEC_RESAMPLE_TAG), res_l[:, i])
        for i in range(k)], axis=1)
    commit = jnp.minimum(j, k - 1) + 1
    ii = jnp.arange(k)[None, :]
    c_tok = jnp.where(ii == j[:, None], y, props)
    return c_tok, commit


@functools.lru_cache(maxsize=32)
def _jitted_set_row_ctx():
    """Install one row's committed-token history into the n-gram context
    table at admission / resume / rollback / park-restore.  ``toks`` is
    padded to a power-of-two bucket by the caller (bounded compile
    variants); entries past ``length`` keep their old values — stale
    tokens are invisible because the lookup never ranks positions past
    ``ctx_len``.  The table is donated: it lives only on the device
    thread and never rides readback chunks."""

    @functools.partial(jax.jit, donate_argnames=("ctx",))
    def set_ctx(ctx, ctx_len, row, toks, length):
        width = toks.shape[0]
        old = jax.lax.dynamic_index_in_dim(ctx, row, axis=0,
                                           keepdims=False)[:width]
        new = jnp.where(jnp.arange(width) < length, toks, old)
        ctx = jax.lax.dynamic_update_slice(ctx, new[None, :], (row, 0))
        return ctx, ctx_len.at[row].set(length)

    return set_ctx


@functools.lru_cache(maxsize=64)
def _jitted_slot_spec_round_v2(t_model, d_model, k, lora=False):
    """One fused speculative round over ALL slots, v2: lossless for
    sampled rows, draftable without a draft model, LoRA-composable.

    Per round: k proposals per row (``d_model`` draft slot-steps, or —
    when ``d_model is None`` — the `ngram_propose` context lookup), ONE
    target pass over the ``[n, k]`` block verifies, and each row commits
    1..k tokens:

    - greedy rows (``temps <= 0``) keep v1's longest-prefix rule — every
      committed token is the target's own argmax, byte-identical to
      plain decode by construction;
    - sampled rows run `spec_accept_sampled` — the canonical
      min(1, p/q) rejection walk with residual resampling, applied to
      the SAME scaled/filtered logits chain (`filter_top_k_p`) the
      plain step samples from, so the output distribution is exactly
      the non-speculative one.

    With ``lora=True`` the target verifies under the per-row adapter
    banks (``lora_tree``/``ids``) while the draft stays on base weights
    — any divergence just lowers acceptance; verification corrects it.
    Both caches (and the n-gram table) rewind/advance per row by the
    commit length, and the budget/eos walk mirrors v1 over the
    committed tokens.  Everything is one dispatch, hostsync-clean.

    Returns ``(new_toks, c_tok [n, k], commit, n_del, done, rems_new,
    ords_new, t_cache, d_cache)`` in model mode, with ``(ctx, ctx_len)``
    replacing ``d_cache`` in n-gram mode."""
    use_ngram = d_model is None
    donate = ("t_cache", "rems") + (("ctx",) if use_ngram else ("d_cache",))

    @functools.partial(jax.jit, donate_argnames=donate)
    def spec_round(t_params, t_cache, toks, temps, seeds, ords, rems,
                   eoss, eos_on, d_params=None, d_cache=None, ctx=None,
                   ctx_len=None, lora_tree=None, ids=None, topks=None,
                   topps=None, minps=None):
        t_params = _params_view(t_params, t_model.cfg)
        idx = _first_named_leaf(t_cache, "cache_index")
        is_g = temps <= 0

        def _filt(logits):
            s = logits / jnp.maximum(temps, 1e-6)[:, None]
            if topks is not None:
                s = filter_top_k_p(s, topks, topps, minps)
            return s

        if use_ngram:
            props = ngram_propose(ctx, ctx_len, k)
            q_sl = None
        else:
            d_params_v = _params_view(d_params, d_model.cfg)
            d_tok, plist, qlist = toks, [], []
            for i in range(k):                  # unrolled: k static
                d_logits, mut = d_model.apply(
                    {"params": d_params_v, "cache": d_cache},
                    d_tok[:, None], mutable=["cache"])
                d_cache = mut["cache"]
                dl = d_logits[:, -1]
                d_sc = _filt(dl)
                d_tok = jnp.where(
                    is_g, jnp.argmax(dl, axis=-1),
                    jax.vmap(jax.random.categorical)(
                        _spec_pos_keys(seeds, ords, i, _SPEC_DRAFT_TAG),
                        d_sc))
                plist.append(d_tok)
                qlist.append(d_sc)
            props = jnp.stack(plist, axis=1)                  # [n, k]
            q_sl = jnp.stack(qlist, axis=1)                   # [n, k, V]
        block = jnp.concatenate([toks[:, None], props[:, :-1]], axis=1)
        variables = {"params": t_params, "cache": t_cache}
        if lora:
            variables["lora"] = _lora_with_ids(lora_tree, ids)
        t_logits, mut = t_model.apply(variables, block, mutable=["cache"])
        t_cache = mut["cache"]
        t_pick = jnp.argmax(t_logits, axis=-1)                # [n, k]
        matches = props == t_pick
        a = jnp.where(matches.all(axis=1), k - 1,
                      jnp.argmin(matches, axis=1))
        commit_g = a + 1
        c_s, commit_s = spec_accept_sampled(
            t_logits, props, temps, seeds, ords, topks=topks, topps=topps,
            minps=minps, q_logits=q_sl)
        commit = jnp.where(is_g, commit_g, commit_s)
        c_tok = jnp.where(is_g[:, None], t_pick, c_s)
        new_toks = jnp.take_along_axis(c_tok, (commit - 1)[:, None],
                                       axis=1)[:, 0]
        ords_new = ords + commit
        t_cache = _set_row_indices_vec(t_cache, idx + commit)
        if not use_ngram:
            d_cache = _set_row_indices_vec(d_cache, idx + commit)
        # deliverable walk (v1's rule, over the committed tokens)
        mask = jnp.arange(k)[None, :] < commit[:, None]
        is_eos = eos_on[:, None] & (c_tok == eoss[:, None]) & mask
        j_eos = jnp.where(is_eos.any(axis=1), jnp.argmax(is_eos, axis=1),
                          k)
        n_del = jnp.minimum(commit,
                            jnp.minimum(jnp.maximum(rems, 0), j_eos + 1))
        rems_new = rems - n_del
        done = (rems_new <= 0) | (j_eos < n_del)
        if use_ngram:
            ctx, ctx_len = _ngram_append(ctx, ctx_len, c_tok, n_del)
            return (new_toks, c_tok, commit, n_del, done, rems_new,
                    ords_new, t_cache, ctx, ctx_len)
        return (new_toks, c_tok, commit, n_del, done, rems_new,
                ords_new, t_cache, d_cache)

    return spec_round


_LOOP_PROBE = {}    # platform name -> measured "scan" | "host" verdict
_LOOP_PROBE_LOCK = threading.Lock()   # one measurement at a time: racing
# probes would contend on the device and could cache a skewed verdict


def probe_loop_driver():
    """Measure ONCE per process (per default-device platform) whether this
    runtime drives device loops faster from lax.scan or from
    host-dispatched steps, and cache the verdict.

    Directly-attached TPUs run compiled while/scan iterations at device
    speed; a runtime that makes each loop iteration expensive (an earlier
    runtime of this repo did, by multiples) runs the SAME per-token
    program faster host-dispatched.  An "auto" that never looks ships the
    slow path to exactly such platforms — so measure: race
    `generate(loop="scan")` against
    `generate(loop="host")` on a tiny fixed LM (best of 2 each, compiles
    excluded).  Scan wins ties and anything within 1.3x — it is the
    idiomatic choice, and the probe only needs to catch multiple-x loop
    penalties.
    """
    # the probe runs on the default device, so the cache key must be the
    # default device's platform — no caller-supplied override
    platform = jax.devices()[0].platform
    with _LOOP_PROBE_LOCK:
        return _probe_locked(platform)


def _probe_locked(platform):
    import time

    cached = _LOOP_PROBE.get(platform)
    if cached is not None:
        return cached

    # The probe body must be a REAL decode step: synthetic matmul chains
    # do not reproduce a loop penalty (where one was seen, on an earlier
    # runtime, it tracked the step's kernel/buffer structure, not its
    # FLOPs).  So race the two drivers of `generate` itself on a tiny
    # fixed LM: one-time cost is two small compiles + 2x32 decoded tokens.
    from tensorflowonspark_tpu.models.transformer import (Transformer,
                                                          TransformerConfig)

    cfg = TransformerConfig(vocab_size=128, d_model=128, n_heads=4,
                            n_kv_heads=2, n_layers=4, d_ff=256,
                            max_seq_len=64, dtype="float32", rope=True,
                            attention_impl="dense")
    model = Transformer(cfg)
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 4), jnp.int32))["params"]
    prompt = jnp.ones((1, 4), jnp.int32)
    n = 32

    def run(driver):
        return generate(model, params, prompt, n, loop=driver)

    def best_of(driver, reps=2):
        run(driver).block_until_ready()     # compile outside timing
        t = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            run(driver).block_until_ready()
            t = min(t, time.perf_counter() - t0)
        return t

    scan_t = best_of("scan")
    host_t = best_of("host")
    verdict = "host" if host_t * 1.3 < scan_t else "scan"
    import logging
    logging.getLogger(__name__).info(
        "decode loop probe on %s: scan %.2fms vs host %.2fms -> %s",
        platform, scan_t * 1e3, host_t * 1e3, verdict)
    _LOOP_PROBE[platform] = verdict
    return verdict


def _set_cache_index(cache, value):
    """Rewind/commit: set every layer's cache_index to `value`.  Entries
    past the index are invisible (decode attention masks keys at
    j > index + s) and get overwritten by later writes, so rewinding the
    index alone discards rejected speculative tokens."""
    value = jnp.asarray(value, jnp.int32)

    def set_leaf(path, leaf):
        last = path[-1]
        name = getattr(last, "key", getattr(last, "name", None))
        return value if name == "cache_index" else leaf

    return jax.tree_util.tree_map_with_path(set_leaf, cache)


def apply_repetition_penalty(logits, seen, rep):
    """HF-style repetition penalty, shared by every decode path: logits
    of tokens already seen (prompt + previously generated — `seen`
    [n, V] nonzero marks them) divide by ``rep`` when positive and
    multiply when negative (`rep` [n] f32; 1.0 = disabled).  Runs on the
    RAW logits before temperature/top-k/top-p (HF processor-then-warper
    ordering), so it shifts greedy argmax too."""
    pen = jnp.where(logits > 0, logits / rep[:, None],
                    logits * rep[:, None])
    return jnp.where(seen > 0, pen, logits)


def seen_from_prompt(prompt, vocab_size):
    """[B, V] int8 presence mask of the prompt tokens — the initial
    `seen` state of `apply_repetition_penalty` (each decode path then
    marks tokens as it feeds them)."""
    B = prompt.shape[0]
    seen = jnp.zeros((B, vocab_size), jnp.int8)
    return seen.at[jnp.arange(B)[:, None], prompt].set(1)


def filter_top_k_p(logits, top_k, top_p, min_p=None):
    """Per-row top-k / nucleus (top-p) / min-p logit filtering, shared by
    EVERY sampling path (solo `generate`/`generate_stream` and the
    serving slot step) so cross-path token parity holds with filters on.

    `logits` [n, V] are the (already temperature-scaled) sampling logits;
    `top_k` [n] int32 (0 disables) keeps each row's k highest;
    `top_p` [n] f32 (1.0 disables) keeps the smallest prefix of the
    descending-sorted distribution whose cumulative probability reaches
    p (the top token always survives); `min_p` [n] f32 (0.0 disables)
    then drops tokens whose probability under the SURVIVING distribution
    is below ``min_p * max_prob`` (llama.cpp-style relative floor).
    Filtered entries become -inf.  HF-warper ordering: temperature ->
    top_k -> top_p -> min_p, each operating on the RENORMALIZED
    survivors of the previous (k=2 probs [.5, .3, .2] -> [.625, .375],
    so p=0.6 keeps only the top token)."""
    V = logits.shape[-1]
    sorted_l = jnp.sort(logits, axis=-1)[:, ::-1]            # [n, V] desc
    k = jnp.where(top_k > 0, jnp.clip(top_k, 1, V), V)
    pos = jnp.arange(V)[None, :]
    in_k = pos < k[:, None]                  # positional top-k on sorted
    probs = jax.nn.softmax(jnp.where(in_k, sorted_l, -jnp.inf), axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    # keep sorted position i while the renormalized mass BEFORE it is
    # < p (the first token always passes; ties at the kth/threshold
    # value keep together via the value comparison below)
    keep_sorted = in_k & ((cum - probs) < top_p[:, None])
    if min_p is not None:
        # relative floor on the top-k/top-p survivors: renormalized
        # prob >= min_p * max (the max survives by construction, so
        # this never empties a row)
        probs2 = jax.nn.softmax(
            jnp.where(keep_sorted, sorted_l, -jnp.inf), axis=-1)
        keep_sorted = keep_sorted & (
            probs2 >= min_p[:, None] * probs2[:, :1])
    thr = jnp.min(jnp.where(keep_sorted, sorted_l, jnp.inf), axis=-1)
    return jnp.where(logits >= thr[:, None], logits, -jnp.inf)


def step_keys(rng, n):
    """The sampling key schedule shared by EVERY decode path: the key for
    new-token ordinal ``t`` is ``fold_in(rng, t)``.  A pure function of
    (request key, position), so a solo `generate`, a `generate_stream`,
    and a serving slot (serve.ContinuousBatcher keeps per-row (seed,
    ordinal) and derives the same keys on device) all sample IDENTICAL
    noise for the same request — cross-path parity is by construction,
    not by luck (tests/test_slots.py pins it)."""
    return jax.vmap(lambda t: jax.random.fold_in(rng, t))(jnp.arange(n))


def replay_key(seed, ordinal):
    """Reconstruct the sampling key for new-token ordinal ``ordinal`` of
    a request seeded with integer ``seed`` — the crash-recovery side of
    the `step_keys` schedule.  Because the key is a pure function of
    (seed, position) with NO chained state, a re-driven session needs
    only (seed, tokens-emitted-so-far) to continue byte-identically: the
    gateway journals both, and a replica rebuilding the session calls
    the chain at ``ordinal = len(emitted)`` as if the crash never
    happened (tests/test_chaos.py pins the parity)."""
    return jax.random.fold_in(jax.random.key(int(seed)), int(ordinal))


def _check_penalty(repetition_penalty):
    """Validate a repetition penalty; True when active.  The finite cap
    matters: rep=inf times a zero-valued seen logit is NaN, which would
    poison the whole row's pick instead of erroring at the boundary."""
    if not 0 < repetition_penalty <= 1e6:
        raise ValueError(
            f"repetition_penalty={repetition_penalty!r} must be in "
            "(0, 1e6] (1.0 disables; >1 discourages repeats)")
    return repetition_penalty != 1.0


def _body_control_kwargs(batch, temperature, top_k, top_p, min_p=0.0):
    """Dynamic top-k/top-p/min-p arrays for `_jitted_decode_body` (empty
    when the filter is off — presence is the only static bit, so
    sweeping filter values never recompiles)."""
    if temperature > 0 and (top_k or top_p < 1.0 or min_p > 0.0):
        return {"topks": jnp.full((batch,), top_k, jnp.int32),
                "topps": jnp.full((batch,), top_p, jnp.float32),
                "minps": jnp.full((batch,), min_p, jnp.float32)}
    return {}


def _solo_pick_fn(temperature, top_k, top_p, min_p=0.0):
    """The solo-path token pick (shared by `generate`/`generate_stream`):
    greedy argmax, or temperature-scaled (optionally top-k/top-p/min-p
    filtered, `filter_top_k_p`) categorical — the same math the serving
    slot step applies per row, so cross-path parity holds with filters
    on."""
    if not (isinstance(top_k, int) and top_k >= 0):
        raise ValueError(f"top_k={top_k!r} must be an int >= 0")
    if not 0.0 < top_p <= 1.0:
        raise ValueError(f"top_p={top_p!r} must be in (0, 1]")
    if not 0.0 <= min_p < 1.0:
        raise ValueError(f"min_p={min_p!r} must be in [0, 1)")

    def pick(logits, rng_t):
        if temperature <= 0:
            return jnp.argmax(logits, axis=-1)
        scaled = logits / temperature
        if top_k or top_p < 1.0 or min_p > 0.0:
            B = logits.shape[0]
            scaled = filter_top_k_p(
                scaled, jnp.full((B,), top_k, jnp.int32),
                jnp.full((B,), top_p, jnp.float32),
                jnp.full((B,), min_p, jnp.float32))
        return jax.random.categorical(rng_t, scaled, axis=-1)

    return pick


def generate_stream(model, params, prompt, max_new_tokens, temperature=0.0,
                    rng=None, eos_id=None, top_k=0, top_p=1.0,
                    min_p=0.0, repetition_penalty=1.0, kv_dtype=None):
    """Yield each new token as a host numpy [B] array as soon as it is
    decoded — the streaming form of `generate` (host-loop only: a
    per-token readback is inherent to streaming).

    Token-for-token identical to ``generate(...)`` with the same
    arguments: both draw token ``t``'s noise from ``fold_in(rng, t)``
    (see `step_keys`), so a streamed sampling run reproduces the batch
    call.  The serving layer forwards these as server-sent events
    (`serve`'s ``:generate`` with ``"stream": true``).  ``top_k`` /
    ``top_p`` / ``min_p`` (in [0, 1)) filter the sampled distribution
    (ignored when greedy — see `filter_top_k_p`).
    """
    import numpy as np

    if temperature > 0 and rng is None:
        raise ValueError("sampling (temperature > 0) requires `rng`")
    pick = _solo_pick_fn(temperature, top_k, top_p, min_p)
    penalized = _check_penalty(repetition_penalty)
    if max_new_tokens <= 0:
        return
    decode_model, cache = init_cache(model, prompt.shape[0],
                                     kv_dtype=kv_dtype)
    cfg = decode_model.cfg
    if prompt.shape[1] + max_new_tokens > cfg.max_seq_len:
        raise ValueError(
            f"prompt {prompt.shape[1]} + max_new_tokens {max_new_tokens} "
            f"exceeds max_seq_len {cfg.max_seq_len}")

    _step = _jitted_step(decode_model)

    rng = rng if rng is not None else jax.random.key(0)
    keys = step_keys(rng, max_new_tokens)
    last_logits, cache = _step(params, prompt, cache)         # prefill
    seen = rep = None
    if penalized:
        seen = seen_from_prompt(prompt, cfg.vocab_size)
        rep = jnp.full((prompt.shape[0],), repetition_penalty, jnp.float32)
        last_logits = apply_repetition_penalty(last_logits, seen, rep)
    tok = pick(last_logits, keys[0])
    done = jnp.zeros(tok.shape, bool)
    if eos_id is not None:
        done = done | (tok == eos_id)
        tok = jnp.where(done, eos_id, tok)
    yield np.asarray(tok)

    body = _jitted_decode_body(decode_model, temperature == 0,
                               eos_id is not None)
    bkw = _body_control_kwargs(prompt.shape[0], temperature, top_k,
                               top_p, min_p)
    temp = jnp.asarray(max(temperature, 1e-9), jnp.float32)
    eos = jnp.asarray(eos_id if eos_id is not None else 0, jnp.int32)
    for t in range(max_new_tokens - 1):
        if penalized:
            tok, cache, done, seen = body(params, tok, cache, done,
                                          keys[t + 1], temp, eos,
                                          seen=seen, rep=rep, **bkw)
        else:
            tok, cache, done = body(params, tok, cache, done, keys[t + 1],
                                    temp, eos, **bkw)
        yield np.asarray(tok)


def speculative_generate(model, params, draft_model, draft_params, prompt,
                         max_new_tokens, k=4):
    """Greedy generation with draft-model speculation — EXACTLY the tokens
    `generate(model, params, prompt, ..., temperature=0)` produces, faster
    when the draft agrees with the target often.

    Each round: the draft proposes `k` tokens autoregressively, then ONE
    target forward over the proposed block verifies all of them (the
    kv-cache decode step already handles multi-token blocks — it is the
    prefill path).  The longest matching prefix is committed plus the
    target's own next token (which equals the draft token wherever they
    agreed), so every committed token is the target's greedy choice.
    Rounds advance all rows by the same amount (the batch-min acceptance);
    rejected cache entries are discarded by rewinding cache_index alone.

    Why this exists: decode pays one small-kernel pass per token,
    whatever the launch overhead of the runtime; a verified block
    amortizes the target's per-token pass over ~acceptance+1 tokens.

    `model`/`draft_model` are Transformers (or configs) sharing a vocab;
    the draft is typically a few-layer model.  Greedy only — sampling
    needs rejection sampling, which changes the acceptance rule.
    """
    import numpy as np

    if k < 1:
        raise ValueError(f"k={k} must be >= 1")
    if max_new_tokens <= 0:
        return prompt
    B, T0 = prompt.shape
    t_model, t_cache = init_cache(model, B)
    d_model, d_cache = init_cache(draft_model, B)
    if t_model.cfg.vocab_size != d_model.cfg.vocab_size:
        raise ValueError(
            f"target vocab {t_model.cfg.vocab_size} != draft vocab "
            f"{d_model.cfg.vocab_size}")
    # the verify block may write up to k tokens past the committed prefix
    # before rewinding, so leave k slots of headroom in BOTH caches
    for cfg in (t_model.cfg, d_model.cfg):
        if T0 + max_new_tokens + k > cfg.max_seq_len:
            raise ValueError(
                f"prompt {T0} + max_new_tokens {max_new_tokens} + k {k} "
                f"exceeds max_seq_len {cfg.max_seq_len}")

    t_step = _jitted_step(t_model)          # [B, S] -> last-position logits
    t_verify = _jitted_step_all(t_model)    # [B, S] -> all-position logits
    d_step = _jitted_step(d_model)

    # prefill both caches over the prompt; first token comes from the target
    t_logits, t_cache = t_step(params, prompt, t_cache)
    _, d_cache = d_step(draft_params, prompt, d_cache)
    last = jnp.argmax(t_logits, axis=-1)    # [B], committed, not yet fed
    committed = [np.asarray(last)]
    base = T0                               # tokens IN both caches

    while len(committed) < max_new_tokens:
        m = min(k, max_new_tokens - len(committed))
        if m == 0:
            break
        # --- draft proposes m tokens after `last` -----------------------
        props = []
        d_tok = last
        for _ in range(m):
            d_logits, d_cache = d_step(draft_params, d_tok[:, None], d_cache)
            d_tok = jnp.argmax(d_logits, axis=-1)
            props.append(d_tok)
        props = jnp.stack(props, axis=1)                     # [B, m]
        # --- one target pass verifies the whole block -------------------
        block = jnp.concatenate([last[:, None], props[:, :-1]], axis=1)
        t_logits_all, t_cache = t_verify(params, block, t_cache)
        t_next = jnp.argmax(t_logits_all, axis=-1)           # [B, m]
        # row-wise longest matching prefix; advance by the batch minimum
        # (rows that matched further agree with t_next there anyway)
        matches_np = np.asarray(props == t_next)             # [B, m]
        n_acc = np.where(matches_np.all(axis=1), m,
                         matches_np.argmin(axis=1))          # [B]
        a = int(n_acc.min())
        a = min(a, m - 1)  # cap: committing a+1 <= m tokens this round
        props_np, t_next_np = np.asarray(props), np.asarray(t_next)
        for j in range(a):
            committed.append(props_np[:, j])
        committed.append(t_next_np[:, a])
        last = jnp.asarray(t_next_np[:, a])
        # --- commit/rewind: prefix + block head (last) + accepted -------
        base = base + 1 + a
        t_cache = _set_cache_index(t_cache, base)
        # draft cache holds [.., last(fed), p1..p_{m-1}(fed)] — same rewind
        d_cache = _set_cache_index(d_cache, base)

    new = jnp.asarray(np.stack(committed[:max_new_tokens], axis=1))
    return jnp.concatenate([prompt, new], axis=1)


def generate(model, params, prompt, max_new_tokens, temperature=0.0,
             rng=None, eos_id=None, loop="auto", top_k=0, top_p=1.0,
             min_p=0.0, repetition_penalty=1.0, kv_dtype=None):
    """Generate continuations of `prompt` [B, T0] -> [B, T0+max_new_tokens].

    temperature=0 is greedy argmax; >0 samples from softmax(logits/T),
    optionally top-k / nucleus / min-p filtered (``top_k``/``top_p``/
    ``min_p`` in [0, 1); ignored when greedy — see `filter_top_k_p`).  ``repetition_penalty`` > 1
    discourages tokens already in the prompt or generated so far
    (HF processor semantics — applied to the raw logits before
    temperature, so it shifts greedy decoding too).
    With `eos_id`, sequences that emit it keep emitting eos_id (shapes stay
    static; trim host-side).  Runs as prefill (one call over the prompt)
    + the token loop.

    ``loop`` picks the token-loop driver:

    - ``"scan"`` — one ``lax.scan`` over all steps: a single dispatch for
      the whole generation, the idiomatic choice on directly-attached
      TPUs.
    - ``"host"`` — a Python loop dispatching one jitted step per token,
      fully async (no per-token sync; one readback at the end).  On
      runtimes where XLA while-loop iterations are expensive this is the
      fast path (on this chip: not measured).
    - ``"auto"`` (default) — the ``TFOS_TPU_DECODE_LOOP`` env var when
      set (``scan``/``host``); otherwise a one-time measured probe of
      this runtime picks the faster driver (`probe_loop_driver`).
      Generations shorter than 16 tokens never trigger the probe (they
      cost less than the measurement); they use the cached verdict when
      one exists, else ``scan``.
    """
    import os

    if temperature > 0 and rng is None:
        raise ValueError("sampling (temperature > 0) requires `rng`")
    pick = _solo_pick_fn(temperature, top_k, top_p, min_p)
    penalized = _check_penalty(repetition_penalty)
    if loop not in ("auto", "scan", "host"):
        raise ValueError(f"loop={loop!r} not in ('auto', 'scan', 'host')")
    if loop == "auto":
        loop = os.environ.get("TFOS_TPU_DECODE_LOOP")
        if loop is None:
            cached = _LOOP_PROBE.get(jax.devices()[0].platform)
            if cached is not None:
                loop = cached
            elif max_new_tokens >= 16:
                loop = probe_loop_driver()
            else:
                # a short generation costs less than the probe itself;
                # take the idiomatic default until someone pays for a
                # long run (or warms the probe explicitly, as serve does)
                loop = "scan"
        elif loop not in ("scan", "host"):
            raise ValueError(
                f"TFOS_TPU_DECODE_LOOP={loop!r} not in ('scan', 'host')")
    if max_new_tokens <= 0:
        return prompt
    decode_model, cache = init_cache(model, prompt.shape[0],
                                     kv_dtype=kv_dtype)
    cfg = decode_model.cfg
    if prompt.shape[1] + max_new_tokens > cfg.max_seq_len:
        raise ValueError(
            f"prompt {prompt.shape[1]} + max_new_tokens {max_new_tokens} "
            f"exceeds max_seq_len {cfg.max_seq_len}")

    _step = _jitted_step(decode_model)

    def step(tokens, cache):
        return _step(params, tokens, cache)

    rng = rng if rng is not None else jax.random.key(0)
    keys = step_keys(rng, max_new_tokens)
    last_logits, cache = step(prompt, cache)                  # prefill
    seen = rep = None
    if penalized:
        seen = seen_from_prompt(prompt, cfg.vocab_size)
        rep = jnp.full((prompt.shape[0],), repetition_penalty, jnp.float32)
        last_logits = apply_repetition_penalty(last_logits, seen, rep)
    tok = pick(last_logits, keys[0])                          # [B]
    done = jnp.zeros(tok.shape, bool)
    if eos_id is not None:
        done = done | (tok == eos_id)
        tok = jnp.where(done, eos_id, tok)

    def scan_body(carry, rng_t):
        tok, cache, done, seen = carry
        logits, cache = step(tok[:, None], cache)
        if penalized:
            seen = seen.at[jnp.arange(tok.shape[0]), tok].set(1)
            logits = apply_repetition_penalty(logits, seen, rep)
        nxt = pick(logits, rng_t)
        if eos_id is not None:
            nxt = jnp.where(done, eos_id, nxt)
            done = done | (nxt == eos_id)
        return (nxt, cache, done, seen), nxt

    if loop == "host":
        # same per-token program, host-dispatched: ONE jitted call per
        # token (step + pick + eos fused), every call queued async (no
        # per-token readback) — steady-state cost is max(device step,
        # dispatch) instead of the while-loop's per-iteration overhead
        body = _jitted_decode_body(decode_model, temperature == 0,
                                   eos_id is not None)
        bkw = _body_control_kwargs(prompt.shape[0], temperature, top_k,
                                   top_p, min_p)
        temp = jnp.asarray(max(temperature, 1e-9), jnp.float32)
        eos = jnp.asarray(eos_id if eos_id is not None else 0, jnp.int32)
        toks = [tok]
        for t in range(max_new_tokens - 1):
            if penalized:
                tok, cache, done, seen = body(params, tok, cache, done,
                                              keys[t + 1], temp, eos,
                                              seen=seen, rep=rep, **bkw)
            else:
                tok, cache, done = body(params, tok, cache, done,
                                        keys[t + 1], temp, eos, **bkw)
            toks.append(tok)
        new_tokens = jnp.stack(toks, axis=1)
    else:
        # seen rides the scan carry (a [B, V] int8 — trivial next to the
        # kv cache already there); None when the penalty is off
        carry0 = (tok, cache, done,
                  seen if penalized else jnp.zeros((), jnp.int8))
        (_, _, _, _), rest = jax.lax.scan(scan_body, carry0, keys[1:])
        new_tokens = jnp.concatenate([tok[:, None], rest.T], axis=1)
    return jnp.concatenate([prompt, new_tokens], axis=1)
