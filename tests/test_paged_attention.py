"""Parity tests for the Pallas paged flash-decode kernel.

The kernel (ops/paged_attention.py, run in interpret mode on the CPU
tier so the REAL kernel body executes) must match the gather reference
— `paged_attention_reference`, shaped exactly like the einsum read body
in models/transformer._paged_attention_body — across the matrix the
serving layer actually produces: bf16 and int8 kv, GQA and MHA, ragged
row lengths, rows mid-page, empty rows, S>1 prefill chunks, and any
split-K factor.  A model-level test then drives the full
_paged_attention_body with paged_attn_impl="kernel" vs "einsum" and
checks logits + greedy tokens agree (and that the kernel branch really
fired).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tensorflowonspark_tpu.ops.paged_attention import (
    paged_attention, paged_attention_reference)


def _make_case(seed, B, S, H, n_kv, Dh, page, max_pages, lengths,
               kv_dtype="float32", q_dtype=None, extra_pages=3):
    """Random q/pool/table for `lengths` (list of B per-row token
    counts).  The page table is a shuffled slice of a larger pool so
    in-place reads genuinely map through the table (identity tables
    would hide gather bugs); unoccupied entries alias the last pool
    page, standing in for the serving layer's sink."""
    rng = np.random.RandomState(seed)
    NP = B * max_pages + extra_pages
    q = rng.randn(B, S, H, Dh).astype(np.float32)
    if kv_dtype == "int8":
        k = rng.randint(-127, 128, (NP, page, n_kv, Dh)).astype(np.int8)
        v = rng.randint(-127, 128, (NP, page, n_kv, Dh)).astype(np.int8)
        ks = rng.uniform(0.005, 0.02, (NP, page, n_kv)).astype(np.float32)
        vs = rng.uniform(0.005, 0.02, (NP, page, n_kv)).astype(np.float32)
        scales = (jnp.asarray(ks), jnp.asarray(vs))
    else:
        k = rng.randn(NP, page, n_kv, Dh).astype(kv_dtype)
        v = rng.randn(NP, page, n_kv, Dh).astype(kv_dtype)
        scales = (None, None)
    perm = rng.permutation(NP - 1)  # never the sink stand-in
    sink = NP - 1
    table = np.full((B, max_pages), sink, np.int32)
    off = 0
    for b, n in enumerate(lengths):
        used = max(0, -(-int(n) // page))
        table[b, :used] = perm[off:off + used]
        off += used
    qd = q_dtype or ("float32" if kv_dtype == "int8" else kv_dtype)
    return (jnp.asarray(q, qd), jnp.asarray(k), jnp.asarray(v),
            jnp.asarray(table), jnp.asarray(lengths, jnp.int32), scales)


def _check(case, atol, **kw):
    q, k, v, table, lengths, (ks, vs) = case
    out = paged_attention(q, k, v, table, lengths,
                          key_scales=ks, value_scales=vs, **kw)
    ref = paged_attention_reference(q, k, v, table, lengths,
                                    key_scales=ks, value_scales=vs)
    assert out.shape == q.shape and out.dtype == q.dtype
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=atol)
    return out


@pytest.mark.parametrize("H,n_kv", [(4, 2), (4, 4)],
                         ids=["gqa", "mha"])
@pytest.mark.parametrize("kv_dtype,atol", [
    ("float32", 1e-5), ("bfloat16", 2e-2), ("int8", 1e-5),
], ids=["f32", "bf16", "int8kv"])
def test_kernel_matches_reference_ragged(H, n_kv, kv_dtype, atol):
    # lengths cover: empty row, one mid-page row (17 of page 16), a
    # page-boundary row, and a full row
    case = _make_case(0, B=4, S=1, H=H, n_kv=n_kv, Dh=32, page=16,
                      max_pages=4, lengths=[0, 17, 32, 64],
                      kv_dtype=kv_dtype)
    out = _check(case, atol)
    # the empty row is defined to be exactly zero, not just close
    assert not np.asarray(out[0]).any()


def test_split_k_invariance():
    case = _make_case(1, B=2, S=1, H=4, n_kv=2, Dh=32, page=16,
                      max_pages=4, lengths=[23, 64])
    q, k, v, table, lengths, _ = case
    one = paged_attention(q, k, v, table, lengths, k_splits=1)
    four = paged_attention(q, k, v, table, lengths, k_splits=4)
    np.testing.assert_allclose(np.asarray(one), np.asarray(four),
                               atol=1e-6)


def test_prefill_chunk_queries_see_causal_prefix():
    # S=4 chunk: query s sees keys <= lengths - S + s (the chunk's own
    # earlier positions included) — the slot-prefill visibility rule
    case = _make_case(2, B=2, S=4, H=4, n_kv=2, Dh=32, page=16,
                      max_pages=4, lengths=[4, 39])
    _check(case, 1e-5)


def test_single_page_pool_and_row_within_first_page():
    # max_pages=1 forces n_splits=1/n_per=1; lengths < page exercises
    # the masked tail of a partially written page
    case = _make_case(3, B=2, S=1, H=2, n_kv=2, Dh=32, page=16,
                      max_pages=1, lengths=[5, 16])
    _check(case, 1e-5)


def test_rejects_bad_shapes():
    q, k, v, table, lengths, _ = _make_case(
        4, B=1, S=1, H=4, n_kv=2, Dh=32, page=16, max_pages=2,
        lengths=[8])
    with pytest.raises(ValueError, match="multiple of kv heads"):
        paged_attention(q[:, :, :3], k, v, table, lengths)
    with pytest.raises(ValueError, match="need key_scales"):
        paged_attention(q, k.astype(jnp.int8), v.astype(jnp.int8),
                        table, lengths)
    with pytest.raises(ValueError, match="only meaningful for int8"):
        paged_attention(q, k, v, table, lengths,
                        key_scales=jnp.ones((3, 16, 2)),
                        value_scales=jnp.ones((3, 16, 2)))


def test_model_body_kernel_vs_einsum(monkeypatch):
    """Drive the REAL _paged_attention_body both ways: same params,
    same prompt, paged_attn_impl='kernel' vs 'einsum' — prefill logits
    allclose and greedy decode tokens identical.  A spy asserts the
    kernel branch actually traced (a silently-disabled kernel would
    otherwise make this einsum-vs-einsum)."""
    from tensorflowonspark_tpu.models import decode
    from tensorflowonspark_tpu.models import transformer as tf_mod
    from tensorflowonspark_tpu.models.transformer import (
        Transformer, TransformerConfig)

    # the kernel entry point is a module-scope binding of transformer.py
    # now (hoisted from _paged_attention_body), so the spy patches THAT
    # binding — the tracing below reads it through the module global
    traced = {"kernel": False}
    real = tf_mod.paged_attention

    def spy(*a, **kw):
        traced["kernel"] = True
        return real(*a, **kw)

    monkeypatch.setattr(tf_mod, "paged_attention", spy)

    # distinctive dims so the lru-cached jits can't be a stale trace
    # from another test file (the spy must see THIS tracing)
    cfg = TransformerConfig(
        vocab_size=80, d_model=48, n_heads=4, n_kv_heads=2, n_layers=2,
        d_ff=96, max_seq_len=32, dtype="float32", rope=True,
        attention_impl="dense")
    model = Transformer(cfg)
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    prompt = list(np.random.RandomState(7).randint(0, 80, size=11))
    page, n_pages = 8, 9          # max_pages=4 per row; page 8 = sink

    results = {}
    for impl in ("kernel", "einsum"):
        slot_model, cache = decode.init_paged_slot_cache(
            model, 2, page, n_pages, paged_attn_impl=impl)
        set_table = decode._jitted_set_row_page_table(slot_model)
        # row 0: shuffled pages; row 1 (unoccupied): all-sink
        cache = set_table(cache, jnp.asarray(0, jnp.int32),
                          jnp.asarray([3, 1, 6, 0], jnp.int32))
        cache = set_table(cache, jnp.asarray(1, jnp.int32),
                          jnp.full((4,), 8, jnp.int32))
        prefill = decode._jitted_slot_prefill(slot_model)
        step = decode._jitted_slot_step(slot_model)
        padded = prompt + [0] * (16 - len(prompt))
        logits, cache = prefill(
            params, cache, jnp.asarray([padded], jnp.int32),
            jnp.asarray(0, jnp.int32), jnp.asarray(0, jnp.int32),
            jnp.asarray(len(prompt), jnp.int32))
        toks = jnp.zeros((2,), jnp.int32).at[0].set(
            jnp.argmax(logits[0]).astype(jnp.int32))
        temps = jnp.zeros((2,), jnp.float32)
        seeds = jnp.zeros((2,), jnp.int32)
        ords = jnp.ones((2,), jnp.int32)
        seq = [int(toks[0])]
        for _ in range(6):
            toks, cache, ords = step(params, cache, toks, temps, seeds,
                                     ords)
            seq.append(int(toks[0]))
        results[impl] = (np.asarray(logits, np.float32), seq)

    assert traced["kernel"], "paged_attn_impl='kernel' never reached " \
        "the kernel (gating bug would make this test vacuous)"
    np.testing.assert_allclose(results["kernel"][0],
                               results["einsum"][0], atol=1e-4)
    assert results["kernel"][1] == results["einsum"][1]
