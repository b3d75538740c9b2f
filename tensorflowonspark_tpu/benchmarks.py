"""Shared benchmark definitions: chip peaks and the flagship-LM config.

Single source of truth for the driver metric (bench.py) and the repro
harness (scripts/bench_lm.py) so the two cannot drift — recorded
numbers are only comparable if every harness builds the exact same step.
"""

# bf16 matmul peaks by device_kind substring (public spec sheet numbers)
PEAK_BF16 = {
    "TPU v5 lite": 197e12,
    "TPU v4": 275e12,
    "TPU v5p": 459e12,
    "TPU v6": 918e12,
}


def bf16_peak(device_kind):
    """Peak bf16 FLOP/s for a jax device_kind string, or None if unknown —
    callers must NOT silently substitute a default: an MFU percent against
    the wrong peak is a fabricated number."""
    return next((v for k, v in PEAK_BF16.items() if k in device_kind), None)


# The round-3 flagship-LM benchmark config: 0.87B params, the north-star
# workload class on one chip.  Frozen — changing any value invalidates
# comparability with every earlier record of it.
FLAGSHIP_LM = dict(
    vocab_size=32000, d_model=2048, n_heads=16, n_kv_heads=8,
    n_layers=16, d_ff=8192, max_seq_len=1024, dtype="bfloat16",
    rope=True, attention_impl="auto")
# Round-5 re-baseline: same dims, RMSNorm — the
# config this framework RECOMMENDS for new decoder-only models since
# round 3 (the frozen v1 kept LayerNorm only for comparability; the
# round-4 verdict called the freeze stale).  v1 stays measured in aux
# for one transition round, exactly like the round-3 metric change.
FLAGSHIP_LM_V2 = dict(FLAGSHIP_LM, norm_type="rmsnorm")
FLAGSHIP_BATCH = 8
FLAGSHIP_MU_DTYPE = "bfloat16"
# Round-6 headline optimizer: the single-pass fused AdamW kernel
# (ops/fused_optim.py) — same math as optax adamw(mu_dtype=bfloat16), one
# HBM pass over grad/param/moments instead of the optax chain's several.
# The optax reference stays measurable via make_flagship_step(
# optimizer="adamw") and bench.py's transition aux row.
FLAGSHIP_OPTIMIZER = "adamw_fused"
# bench.py's vs_baseline denominator: the round-1 flagship-LM figure, taken
# on an earlier runtime and never re-measured on this chip (the benchmark
# PR, ROADMAP S1, replaces it with a ledger row)
ROUND1_LM_MFU = 47.0

# The decode_ms segment workload (bench.py --segments): steady-state
# paged slot decode on the flagship dims, sized for the gather path's
# worst case — long max_seq, rows only partially filled — where the
# flash-decode kernel's per-row length bound pays most.  Frozen like
# FLAGSHIP_LM: changing any value invalidates decode_ms comparability.
FLAGSHIP_DECODE = dict(n_slots=16, page_size=64, max_seq=4096, fill=2000)


def make_decode_step(impl="kernel", n_slots=None, page_size=None,
                     max_seq=None, fill=None, quantize=None):
    """Build the steady-state paged slot-decode step for the decode_ms
    segment: flagship-LM dims (FLAGSHIP_LM_V2) at ``max_seq``, every row
    fully page-mapped and pre-filled to ``fill`` tokens, so each timed
    step is one mid-stream decode token for all ``n_slots`` rows.
    ``impl`` picks the paged READ path ("kernel" = the Pallas
    flash-decode kernel, "einsum" = the full-gather reference —
    TransformerConfig.paged_attn_impl).  ``quantize`` ("int8"/"int4")
    stores the weights quantized exactly as serving does (quantize_tree
    then the compute-width cast for the survivors, serve._load_lm's
    order), so the step decodes through the fused-dequant quant_matmul
    path.  Returns
    ``(step, params, cache, (toks, temps, seeds, ords))``; the cache is
    donated — advance with
    ``toks, cache, ords = step(params, cache, toks, temps, seeds, ords)``.
    The kv content is untrained garbage (zeros): decode cost is
    shape/length-bound, not value-bound, so timing is unaffected."""
    import jax
    import jax.numpy as jnp

    from tensorflowonspark_tpu.models import decode as decode_mod
    from tensorflowonspark_tpu.models.transformer import (
        Transformer, TransformerConfig)

    d = FLAGSHIP_DECODE
    n_slots = n_slots or d["n_slots"]
    page = page_size or d["page_size"]
    max_seq = max_seq or d["max_seq"]
    fill = d["fill"] if fill is None else fill
    cfg = TransformerConfig(**dict(FLAGSHIP_LM_V2, max_seq_len=max_seq))
    model = Transformer(cfg)
    # params don't depend on seq length: init with a short trace
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    if quantize:
        from tensorflowonspark_tpu import quantize as quantize_mod
        params = quantize_mod.quantize_tree(params, mode=quantize)
        params = quantize_mod.cast_float_leaves(params, cfg.dtype)
    from tensorflowonspark_tpu.serve import max_table_pages
    max_pages = max_table_pages(max_seq, page)
    # every row fully mapped (pages are row-contiguous; +1 = the sink,
    # unused here but init_paged_slot_cache's caller contract): steps
    # can never write past an allocated page, and the KERNEL's work is
    # still bounded by `fill` (its per-row length bound), while the
    # einsum body gathers the whole max_seq view — the contrast the
    # segment measures
    slot_model, cache = decode_mod.init_paged_slot_cache(
        model, n_slots, page, n_slots * max_pages + 1,
        paged_attn_impl=impl)
    set_table = decode_mod._jitted_set_row_page_table(slot_model)
    for row in range(n_slots):
        entries = jnp.arange(row * max_pages, (row + 1) * max_pages,
                             dtype=jnp.int32)
        cache = set_table(cache, jnp.asarray(row, jnp.int32), entries)

    def _fill_leaf(path, leaf):
        if decode_mod._leaf_name(path) in ("cache_index", "pos_index"):
            return jnp.full(leaf.shape, fill, jnp.int32)
        return leaf

    cache = jax.tree_util.tree_map_with_path(_fill_leaf, cache)
    step = decode_mod._jitted_slot_step(slot_model)
    toks = jnp.zeros((n_slots,), jnp.int32)
    temps = jnp.zeros((n_slots,), jnp.float32)   # greedy
    seeds = jnp.zeros((n_slots,), jnp.int32)
    ords = jnp.zeros((n_slots,), jnp.int32)
    return step, params, cache, (toks, temps, seeds, ords)


# The qmm_ms segment workload (bench.py --segments): one decode-shaped
# weight matmul on the flagship's widest projection — d_model -> d_ff
# (2048 x 8192, the DenseMLP up-projection kernel) with a decode batch
# of rows.  Decode matmuls are weight-read-bound (rows is the slot
# batch, tiny next to the kernel), so the fused-dequant stores' smaller
# resident bytes (qmm_weight_bytes) should convert ~directly into step
# time.  Frozen like FLAGSHIP_DECODE: changing any value invalidates
# qmm_ms comparability.
FLAGSHIP_QMM = dict(rows=16, in_dim=2048, out_dim=8192, group_size=128)


def make_qmm_op(mode="bf16", rows=None, in_dim=None, out_dim=None,
                group_size=None):
    """Build the qmm_ms segment op: a jitted ``fn(x, w) -> y`` plus its
    ``(x, w)`` operands for one flagship projection matmul.  ``mode``
    picks the weight store — "bf16" = the dense compute-width matmul
    (the W16 serving baseline), "int8" / "int4" = the fused-dequant
    Pallas kernels (ops.quant_matmul) over the quantized leaf, built by
    the same quantize_tree serving uses.  The activation is bf16 in
    every mode: weight-only quantization (W8A16 / W4A16)."""
    import jax
    import jax.numpy as jnp

    from tensorflowonspark_tpu import quantize as quantize_mod
    from tensorflowonspark_tpu.ops import quant_matmul

    d = FLAGSHIP_QMM
    rows = rows or d["rows"]
    K = in_dim or d["in_dim"]
    N = out_dim or d["out_dim"]
    G = group_size or d["group_size"]
    kx, kw = jax.random.split(jax.random.key(0))
    x = jax.random.normal(kx, (rows, K), jnp.bfloat16)
    w = jax.random.normal(kw, (K, N), jnp.float32)
    if mode == "bf16":
        return jax.jit(jnp.dot), x, w.astype(jnp.bfloat16)
    qleaf = quantize_mod.quantize_tree(
        {"proj": {"kernel": w}}, mode=mode, min_elements=0,
        group_size=G)["proj"]["kernel"]
    return jax.jit(quant_matmul), x, qleaf


def qmm_weight_bytes(mode, in_dim=None, out_dim=None, group_size=None):
    """Analytic resident weight bytes for one qmm_ms matmul — the
    per-step weight read the segment exists to price (a decode matmul
    streams the whole kernel once per step).  bf16: K·N·2.  int8: K·N
    payload + N per-channel f32 scales.  int4: the nibble-packed
    payload (two input rows per stored byte, input dim padded to whole
    groups) + one f32 scale per (group, output channel)."""
    d = FLAGSHIP_QMM
    K = in_dim or d["in_dim"]
    N = out_dim or d["out_dim"]
    G = group_size or d["group_size"]
    if mode == "bf16":
        return K * N * 2
    if mode == "int8":
        return K * N + N * 4
    if mode == "int4":
        n_groups = -(-K // G)
        return n_groups * (G // 2) * N + n_groups * N * 4
    raise ValueError(f"unknown qmm mode {mode!r}")


# The prefill_ms segment workload (bench.py --segments): steady-state
# batched multi-row prefill into a paged pool — every row already
# holding `fill` tokens of context, each timed dispatch pushing one
# more `chunk`-wide slab for ALL rows through _jitted_slot_prefill_many.
# `fill` is deliberately NOT page-aligned (matching FLAGSHIP_DECODE's)
# so the steady state exercises the page-straddling chunk path.  The
# contrast is the paged S>1 WRITE discipline ("kernel" = the Pallas
# paged-prefill flash kernel writing W = chunk//page + 1 pages per row
# in place, "blend" = the one-hot einsum blend that materializes the
# ENTIRE pool every chunk — TransformerConfig.paged_prefill_impl).
# Frozen like FLAGSHIP_DECODE: changing any value invalidates
# prefill_ms comparability.
FLAGSHIP_PREFILL_KERNEL = dict(n_slots=4, page_size=64, max_seq=4096,
                               fill=2000, chunk=256)


def make_prefill_chunk_step(impl="kernel", n_slots=None, page_size=None,
                            max_seq=None, fill=None, chunk=None):
    """Build the steady-state paged prefill chunk step for the
    prefill_ms segment: flagship-LM dims (FLAGSHIP_LM_V2) at
    ``max_seq``, every row fully page-mapped, each dispatch prefilling
    the same ``chunk``-wide slab at offset ``fill`` for all ``n_slots``
    rows at once.  Re-dispatch is idempotent — the row indices are SET
    to ``fill + chunk`` (not accumulated) and the same pages are
    rewritten — so timing loops just rebind the donated cache.
    ``impl`` picks the paged S>1 prefill path ("kernel" = the Pallas
    in-place page-write kernel, "blend" = the full-pool einsum blend —
    TransformerConfig.paged_prefill_impl).  Returns
    ``(prefill, params, cache, (chunks, rows, starts, n_valids, sink))``;
    advance with ``logits, cache = prefill(params, cache, *args)``.
    The kv content is untrained garbage: prefill cost is shape-bound,
    not value-bound, so timing is unaffected."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from tensorflowonspark_tpu.models import decode as decode_mod
    from tensorflowonspark_tpu.models.transformer import (
        Transformer, TransformerConfig)

    d = FLAGSHIP_PREFILL_KERNEL
    n_slots = n_slots or d["n_slots"]
    page = page_size or d["page_size"]
    max_seq = max_seq or d["max_seq"]
    fill = d["fill"] if fill is None else fill
    chunk = chunk or d["chunk"]
    cfg = TransformerConfig(**dict(FLAGSHIP_LM_V2, max_seq_len=max_seq))
    model = Transformer(cfg)
    # params don't depend on seq length: init with a short trace
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    from tensorflowonspark_tpu.serve import max_table_pages
    max_pages = max_table_pages(max_seq, page)
    n_pages = n_slots * max_pages + 1       # +1 = the sink page
    slot_model, cache = decode_mod.init_paged_slot_cache(
        model, n_slots, page, n_pages, paged_prefill_impl=impl)
    set_table = decode_mod._jitted_set_row_page_table(slot_model)
    for row in range(n_slots):
        entries = jnp.arange(row * max_pages, (row + 1) * max_pages,
                             dtype=jnp.int32)
        cache = set_table(cache, jnp.asarray(row, jnp.int32), entries)
    prefill = decode_mod._jitted_slot_prefill_many(slot_model)
    rs = np.random.RandomState(0)
    chunks = jnp.asarray(rs.randint(1, cfg.vocab_size, (n_slots, chunk)),
                         jnp.int32)
    rows = jnp.arange(n_slots, dtype=jnp.int32)
    starts = jnp.full((n_slots,), fill, jnp.int32)
    n_valids = jnp.full((n_slots,), chunk, jnp.int32)
    sink = jnp.asarray(n_pages - 1, jnp.int32)
    return prefill, params, cache, (chunks, rows, starts, n_valids, sink)


def prefill_chunk_write_bytes(impl, n_slots=None, page_size=None,
                              max_seq=None, chunk=None):
    """Analytic KV-pool WRITE traffic per prefill_ms dispatch (all
    layers, k + v, bf16 pool): the blend path materializes a full new
    pool every chunk — every page, occupied or not — while the kernel
    writes only the W = chunk//page + 1 pages each row's chunk can
    touch, in place.  The segment reports both so the
    traffic-scales-with-chunk claim is a number in the JSON, not
    prose."""
    d = FLAGSHIP_PREFILL_KERNEL
    n_slots = n_slots or d["n_slots"]
    page = page_size or d["page_size"]
    max_seq = max_seq or d["max_seq"]
    chunk = chunk or d["chunk"]
    n_kv = FLAGSHIP_LM_V2["n_kv_heads"]
    dh = FLAGSHIP_LM_V2["d_model"] // FLAGSHIP_LM_V2["n_heads"]
    page_bytes = page * n_kv * dh * 2       # bf16 kv pool
    if impl == "blend":
        from tensorflowonspark_tpu.serve import max_table_pages
        pages = n_slots * max_table_pages(max_seq, page) + 1   # WHOLE pool
    else:
        pages = n_slots * (chunk // page + 1)     # W pages/row, in place
    return FLAGSHIP_LM_V2["n_layers"] * 2 * pages * page_bytes


# The ttft_ms segment workload (bench.py --segments): a burst of queued
# prompts admitted through the continuous batcher's prefill engine —
# time-to-first-token with batched multi-row prefill (prefill_rows=4)
# vs the sequential admission baseline (prefill_rows=1).  Dense slot
# cache: the segment isolates admission batching, not page residency.
# Frozen like FLAGSHIP_LM: changing any value invalidates ttft_ms
# comparability.
FLAGSHIP_PREFILL = dict(n_slots=8, prompts=8, prompt_len=768, max_new=2,
                        prefill_chunk=256, prefill_rows=4, max_seq=1024)


def make_prefill_burst(prefill_rows=None, n_slots=None, prompts=None,
                       prompt_len=None, max_new=None, prefill_chunk=None,
                       max_seq=None):
    """Build the ttft_ms segment workload: a ContinuousBatcher on the
    flagship-LM dims (FLAGSHIP_LM_V2 at ``max_seq``) plus the burst of
    distinct random prompts to submit.  Returns
    ``(batcher, prompts_list, max_new)``; the caller submits the burst,
    drains every handle, and reads TTFT from ``batcher.stats()``
    (ttft_ms_sum / ttft_count deltas).  Caller must ``batcher.stop()``.
    Prompt content is random garbage: prefill cost is shape-bound, not
    value-bound, so timing is unaffected; prompts are DISTINCT so the
    prefix cache cannot short-circuit the work being measured."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from tensorflowonspark_tpu import serve as serve_mod
    from tensorflowonspark_tpu.models.transformer import (
        Transformer, TransformerConfig)

    d = FLAGSHIP_PREFILL
    rows = d["prefill_rows"] if prefill_rows is None else prefill_rows
    n_slots = n_slots or d["n_slots"]
    n_prompts = prompts or d["prompts"]
    prompt_len = prompt_len or d["prompt_len"]
    max_new = max_new or d["max_new"]
    chunk = prefill_chunk or d["prefill_chunk"]
    max_seq = max_seq or d["max_seq"]
    cfg = TransformerConfig(**dict(FLAGSHIP_LM_V2, max_seq_len=max_seq))
    model = Transformer(cfg)
    # params don't depend on seq length: init with a short trace
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    batcher = serve_mod.ContinuousBatcher(
        model, params, n_slots=n_slots, read_chunk=4, prefill_chunk=chunk,
        prefill_rows=rows)
    rs = np.random.RandomState(0)
    prompts_list = [rs.randint(1, cfg.vocab_size,
                               prompt_len).astype("int32").tolist()
                    for _ in range(n_prompts)]
    return batcher, prompts_list, max_new


# The engine_tps segment workload (bench.py --segments): sustained decode
# through the FULL ContinuousBatcher — admission, dispatch, readback,
# stream delivery — not a bare step microbench.  Short prompts + long
# generations so steady-state decode dominates and the segment measures
# the engine's host/device overlap (async double-buffered loop vs the
# serialized baseline), the exact path decode_ms cannot see.  Frozen like
# FLAGSHIP_PREFILL: changing any value invalidates engine_tps
# comparability.
FLAGSHIP_ENGINE = dict(n_slots=8, prompts=16, prompt_len=64, max_new=96,
                       prefill_chunk=256, prefill_rows=4, max_seq=256)


def make_engine_burst(engine="async", n_slots=None, prompts=None,
                      prompt_len=None, max_new=None, prefill_chunk=None,
                      prefill_rows=None, max_seq=None, pipeline_depth=2,
                      quantize=None):
    """Build the engine_tps segment workload: a ContinuousBatcher on the
    flagship-LM dims running the requested ``engine`` ("async" = the
    double-buffered producer/consumer pipeline, "serial" = the
    single-thread dispatch/process baseline) plus the prompt burst to
    submit.  ``quantize`` ("int8"/"int4") stores the weights quantized
    exactly as serving does (serve._load_lm's quantize-then-cast order),
    so the whole burst decodes through the fused-dequant quant_matmul
    path.  Returns ``(batcher, prompts_list, max_new)``; the caller
    submits the burst, drains every handle, and computes tokens/s from
    wall clock (device-idle fraction comes from ``batcher.stats()``).
    Caller must ``batcher.stop()``.  Prompts are distinct random garbage
    for the same reasons as :func:`make_prefill_burst`."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from tensorflowonspark_tpu import serve as serve_mod
    from tensorflowonspark_tpu.models.transformer import (
        Transformer, TransformerConfig)

    d = FLAGSHIP_ENGINE
    n_slots = n_slots or d["n_slots"]
    n_prompts = prompts or d["prompts"]
    prompt_len = prompt_len or d["prompt_len"]
    max_new = max_new or d["max_new"]
    chunk = prefill_chunk or d["prefill_chunk"]
    rows = d["prefill_rows"] if prefill_rows is None else prefill_rows
    max_seq = max_seq or d["max_seq"]
    cfg = TransformerConfig(**dict(FLAGSHIP_LM_V2, max_seq_len=max_seq))
    model = Transformer(cfg)
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    if quantize:
        from tensorflowonspark_tpu import quantize as quantize_mod
        params = quantize_mod.quantize_tree(params, mode=quantize)
        params = quantize_mod.cast_float_leaves(params, cfg.dtype)
    batcher = serve_mod.ContinuousBatcher(
        model, params, n_slots=n_slots, read_chunk=4, prefill_chunk=chunk,
        prefill_rows=rows, engine=engine, pipeline_depth=pipeline_depth)
    rs = np.random.RandomState(0)
    prompts_list = [rs.randint(1, cfg.vocab_size,
                               prompt_len).astype("int32").tolist()
                    for _ in range(n_prompts)]
    return batcher, prompts_list, max_new


# The spec_tps segment workload (bench.py --segments): sustained decode
# through the ContinuousBatcher with speculation in each of its modes —
# "ngram" (model-free prompt-lookup drafting), "model" (a 4-layer
# scaled-down draft LM on the flagship dims), "off" (the plain-step
# baseline the other two are compared against).  Prompts are REPETITIVE
# (a short random motif tiled to prompt_len): prompt-lookup speculation
# pays off exactly when the continuation echoes the context, so this
# workload is where ngram drafting must beat spec-off — the acceptance
# rate and adaptive mean-k ride along as aux.  Greedy requests: the
# accept rate then measures draft quality alone, not sampling noise.
# Frozen like FLAGSHIP_ENGINE: changing any value invalidates spec_tps
# comparability.
FLAGSHIP_SPEC = dict(n_slots=8, prompts=16, prompt_len=64, max_new=96,
                     prefill_chunk=256, prefill_rows=4, max_seq=256,
                     draft_k=4, motif_len=8, draft_layers=4)


def make_spec_burst(mode="ngram", n_slots=None, prompts=None,
                    prompt_len=None, max_new=None, prefill_chunk=None,
                    prefill_rows=None, max_seq=None, draft_k=None):
    """Build the spec_tps segment workload: a ContinuousBatcher on the
    flagship-LM dims with ``mode`` speculation ("ngram" / "model" /
    "off") plus the repetitive prompt burst to submit.  Returns
    ``(batcher, prompts_list, max_new)``; the caller submits the burst
    greedily, drains every handle, computes tokens/s from wall clock,
    and reads acceptance/mean-k aux from ``batcher.stats()``.  Caller
    must ``batcher.stop()``."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from tensorflowonspark_tpu import serve as serve_mod
    from tensorflowonspark_tpu.models.transformer import (
        Transformer, TransformerConfig)

    d = FLAGSHIP_SPEC
    n_slots = n_slots or d["n_slots"]
    n_prompts = prompts or d["prompts"]
    prompt_len = prompt_len or d["prompt_len"]
    max_new = max_new or d["max_new"]
    chunk = prefill_chunk or d["prefill_chunk"]
    rows = d["prefill_rows"] if prefill_rows is None else prefill_rows
    max_seq = max_seq or d["max_seq"]
    draft_k = draft_k or d["draft_k"]
    cfg = TransformerConfig(**dict(FLAGSHIP_LM_V2, max_seq_len=max_seq))
    model = Transformer(cfg)
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    draft_model = draft_params = None
    if mode == "model":
        d_cfg = TransformerConfig(**dict(
            FLAGSHIP_LM_V2, max_seq_len=max_seq,
            n_layers=d["draft_layers"]))
        draft_model = Transformer(d_cfg)
        draft_params = draft_model.init(
            jax.random.key(1), jnp.zeros((1, 8), jnp.int32))["params"]
    batcher = serve_mod.ContinuousBatcher(
        model, params, n_slots=n_slots, read_chunk=4, prefill_chunk=chunk,
        prefill_rows=rows, spec_draft=mode, draft_model=draft_model,
        draft_params=draft_params, draft_k=draft_k)
    rs = np.random.RandomState(0)
    motif_len = d["motif_len"]
    prompts_list = []
    for _ in range(n_prompts):
        motif = rs.randint(1, cfg.vocab_size, motif_len)
        reps = prompt_len // motif_len + 1
        prompts_list.append(
            np.tile(motif, reps)[:prompt_len].astype("int32").tolist())
    return batcher, prompts_list, max_new


# The migrate_ms segment workload (bench.py --segments): one live paged
# session frozen mid-decode on a source ContinuousBatcher, shipped page-
# by-page through a real kvtransfer.PageServer socket on localhost, and
# resumed on a destination batcher — the disaggregated-serving handoff
# end to end (freeze gather, wire framing, page upload, table splice).
# Long prompt so the snapshot carries a realistic page count; the
# decode keeps running through the cut, so the segment can also report
# the client-visible stream stall.  Frozen like FLAGSHIP_ENGINE:
# changing any value invalidates migrate_ms comparability.
FLAGSHIP_MIGRATE = dict(n_slots=4, prompt_len=192, max_new=48,
                        prefill_chunk=256, kv_page_size=32, kv_pages=64,
                        max_seq=256)


def make_migrate_pair(n_slots=None, prompt_len=None, max_new=None,
                      prefill_chunk=None, kv_page_size=None,
                      kv_pages=None, max_seq=None):
    """Build the migrate_ms segment workload: source and destination
    ContinuousBatchers on the flagship-LM dims (both paged — migration
    ships occupied pages) plus the prompt to move.  Returns
    ``(src, dst, prompt, max_new)``; the caller submits to ``src``,
    freezes mid-decode, wires the snapshot across, resumes on ``dst``,
    and times the handoff.  Caller must stop BOTH batchers.  Prompt
    content is random garbage for the same reasons as
    :func:`make_prefill_burst`."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from tensorflowonspark_tpu import serve as serve_mod
    from tensorflowonspark_tpu.models.transformer import (
        Transformer, TransformerConfig)

    d = FLAGSHIP_MIGRATE
    n_slots = n_slots or d["n_slots"]
    prompt_len = prompt_len or d["prompt_len"]
    max_new = max_new or d["max_new"]
    chunk = prefill_chunk or d["prefill_chunk"]
    page = kv_page_size or d["kv_page_size"]
    pages = kv_pages or d["kv_pages"]
    max_seq = max_seq or d["max_seq"]
    cfg = TransformerConfig(**dict(FLAGSHIP_LM_V2, max_seq_len=max_seq))
    model = Transformer(cfg)
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    def mk():
        return serve_mod.ContinuousBatcher(
            model, params, n_slots=n_slots, read_chunk=1,
            prefill_chunk=chunk, kv_page_size=page, kv_pages=pages)

    src, dst = mk(), mk()
    rs = np.random.RandomState(0)
    prompt = rs.randint(1, cfg.vocab_size,
                        prompt_len).astype("int32").tolist()
    return src, dst, prompt, max_new


# The sched_ms segment workload (bench.py --segments): a paged batcher
# saturated by long batch-class sessions while short interactive
# requests arrive on top — the mixed-priority contention story the
# preemption controller exists for.  With preemption on, interactive
# pressure parks the longest-remaining batch session (freeze → host-side
# snapshot → resume when pressure drops); the segment reports interactive
# p95 queueing delay with the controller on vs off.  Paged KV so parking
# exercises the real page-pool accounting.  Frozen like FLAGSHIP_ENGINE:
# changing any value invalidates sched_ms comparability.
FLAGSHIP_SCHED = dict(n_slots=4, batch_sessions=4, batch_prompt_len=64,
                      batch_max_new=96, inter_sessions=8,
                      inter_prompt_len=32, inter_max_new=4,
                      prefill_chunk=256, kv_page_size=32, kv_pages=64,
                      max_seq=256, preempt_ms=5.0)


def make_sched_burst(preempt=True, n_slots=None, prefill_chunk=None,
                     kv_page_size=None, kv_pages=None, max_seq=None,
                     preempt_ms=None):
    """Build the sched_ms segment workload: one paged ContinuousBatcher
    (preemption controller armed when ``preempt``) plus the two prompt
    populations.  Returns ``(batcher, batch_prompts, batch_max_new,
    inter_prompts, inter_max_new)``; the caller saturates the slots with
    the batch population, trickles the interactive one on top, drains
    everything, and reads per-class queueing delay from
    ``batcher.stats()``.  Caller must ``batcher.stop()``.  Prompts are
    distinct random garbage for the same reasons as
    :func:`make_prefill_burst`."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from tensorflowonspark_tpu import serve as serve_mod
    from tensorflowonspark_tpu.models.transformer import (
        Transformer, TransformerConfig)

    d = FLAGSHIP_SCHED
    n_slots = n_slots or d["n_slots"]
    chunk = prefill_chunk or d["prefill_chunk"]
    page = kv_page_size or d["kv_page_size"]
    pages = kv_pages or d["kv_pages"]
    max_seq = max_seq or d["max_seq"]
    preempt_ms = d["preempt_ms"] if preempt_ms is None else preempt_ms
    cfg = TransformerConfig(**dict(FLAGSHIP_LM_V2, max_seq_len=max_seq))
    model = Transformer(cfg)
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    batcher = serve_mod.ContinuousBatcher(
        model, params, n_slots=n_slots, read_chunk=1,
        prefill_chunk=chunk, kv_page_size=page, kv_pages=pages,
        preempt_ms=preempt_ms if preempt else 0.0,
        park_capacity=d["batch_sessions"])
    rs = np.random.RandomState(0)

    def burst(n, length):
        return [rs.randint(1, cfg.vocab_size,
                           length).astype("int32").tolist()
                for _ in range(n)]

    batch_prompts = burst(d["batch_sessions"], d["batch_prompt_len"])
    inter_prompts = burst(d["inter_sessions"], d["inter_prompt_len"])
    return (batcher, batch_prompts, d["batch_max_new"],
            inter_prompts, d["inter_max_new"])


# The warm_ttft_ms segment workload (bench.py --segments): 8 returning
# conversations against a paged batcher with the host-DRAM page tier
# armed.  Cold pass prefills every prompt from scratch and retires, so
# each conversation's full-prefix pages demote to the host tier; the
# device prefix cache is then evicted so the warm pass can ONLY be
# served by host->device promotion.  The segment reports mean TTFT for
# the warm pass vs the cold pass — the cross-turn prefill-skip win the
# hierarchical kv cache exists for.  Long prompts (6 full 32-token
# pages) so the skipped prefill dominates TTFT.  Frozen like
# FLAGSHIP_ENGINE: changing any value invalidates warm_ttft_ms
# comparability.
FLAGSHIP_WARM = dict(n_slots=4, conversations=8, prompt_len=192,
                     max_new=8, prefill_chunk=256, kv_page_size=32,
                     kv_pages=96, host_cache_mb=256, max_seq=256)


def make_warm_burst(n_slots=None, conversations=None, prompt_len=None,
                    max_new=None, prefill_chunk=None, kv_page_size=None,
                    kv_pages=None, host_cache_mb=None, max_seq=None):
    """Build the warm_ttft_ms segment workload: one paged
    ContinuousBatcher with the host tier armed, plus the conversation
    prompts.  Returns ``(batcher, prompts_list, max_new)``; the caller
    runs the burst cold (timing per-request TTFT), flushes the tier,
    evicts the device prefix cache, re-runs the SAME burst warm, and
    compares.  Caller must ``batcher.stop()``.  Prompts are distinct
    random garbage for the same reasons as :func:`make_prefill_burst` —
    prefix reuse here is exact-key, so garbage reuses as well as text."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from tensorflowonspark_tpu import serve as serve_mod
    from tensorflowonspark_tpu.models.transformer import (
        Transformer, TransformerConfig)

    d = FLAGSHIP_WARM
    n_slots = n_slots or d["n_slots"]
    n_conv = conversations or d["conversations"]
    prompt_len = prompt_len or d["prompt_len"]
    max_new = max_new or d["max_new"]
    chunk = prefill_chunk or d["prefill_chunk"]
    page = kv_page_size or d["kv_page_size"]
    pages = kv_pages or d["kv_pages"]
    cache_mb = host_cache_mb or d["host_cache_mb"]
    max_seq = max_seq or d["max_seq"]
    cfg = TransformerConfig(**dict(FLAGSHIP_LM_V2, max_seq_len=max_seq))
    model = Transformer(cfg)
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    batcher = serve_mod.ContinuousBatcher(
        model, params, n_slots=n_slots, read_chunk=1,
        prefill_chunk=chunk, kv_page_size=page, kv_pages=pages,
        host_cache_mb=cache_mb)
    rs = np.random.RandomState(0)
    prompts_list = [rs.randint(1, cfg.vocab_size,
                               prompt_len).astype("int32").tolist()
                    for _ in range(n_conv)]
    return batcher, prompts_list, max_new


# The job_tps segment workload (bench.py --segments): an offline bulk-
# inference job (jobs.JobManager — the TFoS data pump) draining a jsonl
# record file through a paged ContinuousBatcher as batch-class work,
# while a trickle of interactive requests rides on top.  The segment
# reports sustained records/s at full engine utilization plus the
# interactive p95 latency with the job running vs idle — the WFQ story
# at fleet scale: batch jobs soak every spare slot, interactive latency
# holds.  Preemption armed (same controller FLAGSHIP_SCHED prices).
# Frozen like FLAGSHIP_ENGINE: changing any value invalidates job_tps
# comparability.
FLAGSHIP_JOB = dict(n_slots=4, records=64, record_prompt_len=32,
                    record_max_new=4, partitions=4, workers=3,
                    checkpoint_every=16, inter_probes=8,
                    inter_prompt_len=32, inter_max_new=4,
                    prefill_chunk=256, kv_page_size=32, kv_pages=64,
                    max_seq=256, preempt_ms=5.0)


def make_job_burst(n_slots=None, records=None, record_prompt_len=None,
                   prefill_chunk=None, kv_page_size=None, kv_pages=None,
                   max_seq=None, preempt_ms=None):
    """Build the job_tps segment workload: one paged ContinuousBatcher
    (preemption armed) plus the two prompt populations.  Returns
    ``(batcher, record_prompts, record_max_new, inter_prompts,
    inter_max_new)``; the caller spools ``record_prompts`` into a jsonl
    input file, runs a real :class:`jobs.JobManager` over it with a
    dispatch callable driving THIS batcher, and probes interactive
    latency while the job drains.  Caller must ``batcher.stop()``.
    Prompts are distinct random garbage for the same reasons as
    :func:`make_prefill_burst`."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from tensorflowonspark_tpu import serve as serve_mod
    from tensorflowonspark_tpu.models.transformer import (
        Transformer, TransformerConfig)

    d = FLAGSHIP_JOB
    n_slots = n_slots or d["n_slots"]
    records = records or d["records"]
    rec_len = record_prompt_len or d["record_prompt_len"]
    chunk = prefill_chunk or d["prefill_chunk"]
    page = kv_page_size or d["kv_page_size"]
    pages = kv_pages or d["kv_pages"]
    max_seq = max_seq or d["max_seq"]
    preempt_ms = d["preempt_ms"] if preempt_ms is None else preempt_ms
    cfg = TransformerConfig(**dict(FLAGSHIP_LM_V2, max_seq_len=max_seq))
    model = Transformer(cfg)
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    batcher = serve_mod.ContinuousBatcher(
        model, params, n_slots=n_slots, read_chunk=1,
        prefill_chunk=chunk, kv_page_size=page, kv_pages=pages,
        preempt_ms=preempt_ms)
    rs = np.random.RandomState(0)

    def burst(n, length):
        return [rs.randint(1, cfg.vocab_size,
                           length).astype("int32").tolist()
                for _ in range(n)]

    record_prompts = burst(records, rec_len)
    inter_prompts = burst(d["inter_probes"], d["inter_prompt_len"])
    return (batcher, record_prompts, d["record_max_new"],
            inter_prompts, d["inter_max_new"])


# The long_ttft_ms segment workload (bench.py --segments): one 32k-token
# mega-prompt streamed through the long-context admission lane while a
# short interactive burst rides on top.  Armed, the prompt admits
# immediately but prefills chunk-by-chunk under the lane's per-round
# quota (pages allocated per chunk, the page table growing from its
# 8-entry seed as the stream advances, cold prefix pages demoted to the
# host tier when the pool runs dry); disarmed, the same prompt is a
# normal admission that reserves its full page run up front and hogs
# the prefill budget.  The segment reports mega-prompt TTFT plus the
# interactive p95 queueing delay both ways — the lane's story is the
# interactive p95 holding while the monster streams.  The pool is sized
# a hair over the mega-prompt's own run so the interactive burst's
# retired prefix pages MUST be reclaimed through the overflow valve.
# Frozen like FLAGSHIP_ENGINE: changing any value invalidates
# long_ttft_ms comparability.
FLAGSHIP_LONG = dict(n_slots=4, long_prompt_len=32768, long_max_new=8,
                     long_prompt_threshold=4096, inter_sessions=8,
                     inter_prompt_len=32, inter_max_new=4,
                     prefill_chunk=256, kv_page_size=32, kv_pages=1040,
                     host_cache_mb=64, max_seq=32800)


def make_long_burst(armed=True, n_slots=None, long_prompt_len=None,
                    prefill_chunk=None, kv_page_size=None, kv_pages=None,
                    host_cache_mb=None, max_seq=None,
                    long_prompt_threshold=None):
    """Build the long_ttft_ms segment workload: one paged
    ContinuousBatcher (mega-prompt lane armed when ``armed`` — disarmed
    = threshold 0, the prompt admits as ordinary work) plus the
    mega-prompt and the interactive population.  Returns ``(batcher,
    long_prompt, long_max_new, inter_prompts, inter_max_new)``; the
    caller submits the mega-prompt, trickles the interactive burst on
    top, drains everything, and reads TTFT / per-class queueing delay /
    growth and demotion counters from ``batcher.stats()``.  Caller must
    ``batcher.stop()``.  Prompts are distinct random garbage for the
    same reasons as :func:`make_prefill_burst`."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from tensorflowonspark_tpu import serve as serve_mod
    from tensorflowonspark_tpu.models.transformer import (
        Transformer, TransformerConfig)

    d = FLAGSHIP_LONG
    n_slots = n_slots or d["n_slots"]
    long_len = long_prompt_len or d["long_prompt_len"]
    chunk = prefill_chunk or d["prefill_chunk"]
    page = kv_page_size or d["kv_page_size"]
    pages = kv_pages or d["kv_pages"]
    cache_mb = host_cache_mb or d["host_cache_mb"]
    max_seq = max_seq or d["max_seq"]
    threshold = (long_prompt_threshold or d["long_prompt_threshold"]
                 if armed else 0)
    cfg = TransformerConfig(**dict(FLAGSHIP_LM_V2, max_seq_len=max_seq))
    model = Transformer(cfg)
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    batcher = serve_mod.ContinuousBatcher(
        model, params, n_slots=n_slots, read_chunk=1,
        prefill_chunk=chunk, kv_page_size=page, kv_pages=pages,
        host_cache_mb=cache_mb, long_prompt_threshold=threshold)
    rs = np.random.RandomState(0)

    def burst(n, length):
        return [rs.randint(1, cfg.vocab_size,
                           length).astype("int32").tolist()
                for _ in range(n)]

    long_prompt = burst(1, long_len)[0]
    inter_prompts = burst(d["inter_sessions"], d["inter_prompt_len"])
    return (batcher, long_prompt, d["long_max_new"],
            inter_prompts, d["inter_max_new"])


def make_flagship_step(batch_size=None, seq_len=None, config="v2",
                       optimizer=None):
    """Build the flagship-LM training step exactly as the driver metric
    runs it: returns (step, state, tokens, n_params).  Donated state —
    call as ``state, m = step(state, tokens, rng)``.
    ``config``: "v2" (rmsnorm, the round-5 headline) or "v1" (the frozen
    round-3 layernorm config, kept for the transition round's aux row).
    ``optimizer``: None -> FLAGSHIP_OPTIMIZER (adamw_fused, the round-6
    headline); "adamw" -> the optax reference (transition aux row);
    "sgd0" -> zero-lr momentum-less SGD, the near-free update whose step
    time isolates the optimizer segment (bench.py's opt_ms)."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from tensorflowonspark_tpu.models.transformer import (
        Transformer, TransformerConfig, lm_loss)
    from tensorflowonspark_tpu.optim import make_optimizer
    from tensorflowonspark_tpu.parallel import train as train_mod

    cfg_kw = dict(FLAGSHIP_LM_V2 if config == "v2" else FLAGSHIP_LM)
    if seq_len:
        cfg_kw["max_seq_len"] = seq_len
    B = batch_size or FLAGSHIP_BATCH
    S = cfg_kw["max_seq_len"]
    cfg = TransformerConfig(**cfg_kw)
    model = Transformer(cfg)
    tokens = jnp.asarray(
        np.random.RandomState(0).randint(0, cfg.vocab_size, (B, S + 1)),
        jnp.int32)
    params = model.init(jax.random.key(0), tokens[:, :S])["params"]
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))

    def loss_fn(p, batch, rng):
        return lm_loss(model.apply({"params": p}, batch[:, :-1]),
                       batch[:, 1:])

    name = optimizer or FLAGSHIP_OPTIMIZER
    if name == "sgd0":
        # momentum=None (not 0.0): optax.sgd keeps a full trace state for
        # any non-None momentum, which would put optimizer bandwidth back
        # into the "no optimizer" segment baseline
        opt, _ = make_optimizer("sgd", learning_rate=0.0, momentum=None)
    else:
        opt, _ = make_optimizer(name, learning_rate=3e-4,
                                mu_dtype=FLAGSHIP_MU_DTYPE)
    state = train_mod.create_train_state(params, opt)
    step = train_mod.make_train_step(loss_fn, opt, donate=True)
    return step, state, tokens, n_params
