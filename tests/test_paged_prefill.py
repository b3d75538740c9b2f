"""Parity tests for the Pallas paged-prefill kernels.

The kernel pair (ops/paged_prefill.py, run in interpret mode on the CPU
tier so the REAL kernel bodies execute) must match the einsum blend
write + gathered full-view read from
models/transformer._paged_attention_body — replicated verbatim here as
`_blend_ref` — across the matrix the serving layer produces: f32/bf16
and int8 kv pools, GQA and MHA, ragged multi-row bursts whose starts are
fresh (0), page-aligned, and page-straddling, prefix-cache skip offsets,
pad rows aimed at the sink, and bucket-pad overshoot.  Pool bytes must
be EXACT (the write kernel replicates the blend's routing, including
int8 requantization); attention outputs are allclose at dtype tolerance.
The sink page is excluded from pool comparisons — concurrent sink
stores race where the blend sums, and sink bytes are garbage by
contract (masked on every read) — and pad-row outputs are excluded for
the same reason (the model scatter-drops them).

A model-level test then drives the full _paged_attention_body with
paged_prefill_impl="kernel" vs "blend" and checks prefill logits, greedy
tokens, and non-sink pool bytes agree (and that the kernel branch really
fired).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tensorflowonspark_tpu.ops.paged_prefill import paged_prefill


def _blend_ref(q, k, v, pages_key, pages_value, table, starts,
               key_scales=None, value_scales=None):
    """The S>1 blend path of models/transformer._paged_attention_body,
    replicated verbatim (einsum one-hot write, gathered [B, L] view
    read) as the oracle the kernels must match."""
    from tensorflowonspark_tpu.models.transformer import (
        _kv_dequantize, _kv_quantize)
    from tensorflowonspark_tpu.parallel.ring_attention import _kv_repeat

    B, S, n_kv, Dh = k.shape
    NP, P = pages_key.shape[:2]
    max_pages = table.shape[1]
    L = max_pages * P
    dtype = k.dtype
    quant = pages_key.dtype == jnp.int8
    store = jnp.int8 if quant else dtype
    idx = starts
    pos = idx[:, None] + jnp.arange(S)[None, :]
    block = jnp.clip(pos // P, 0, max_pages - 1)
    phys = jnp.take_along_axis(table, block, axis=1)
    oh_p = (jnp.arange(NP)[None, None, :]
            == phys[:, :, None]).astype(dtype)
    oh_o = (jnp.arange(P)[None, None, :]
            == (pos % P)[:, :, None]).astype(dtype)
    if quant:
        k_st, k_sc = _kv_quantize(k)
        v_st, v_sc = _kv_quantize(v)
    else:
        k_st, v_st = k.astype(dtype), v.astype(dtype)
    upd_k = jnp.einsum("bsn,bso,bshd->nohd", oh_p, oh_o,
                       k_st.astype(dtype))
    upd_v = jnp.einsum("bsn,bso,bshd->nohd", oh_p, oh_o,
                       v_st.astype(dtype))
    wmask = (jnp.einsum("bsn,bso->no", oh_p, oh_o)
             > 0)[:, :, None, None]
    new_pk = jnp.where(wmask, upd_k.astype(store), pages_key)
    new_pv = jnp.where(wmask, upd_v.astype(store), pages_value)
    new_ks = new_vs = None
    if quant:
        smask = wmask[..., 0]
        new_ks = jnp.where(smask, jnp.einsum(
            "bsn,bso,bsh->noh", oh_p.astype(jnp.float32),
            oh_o.astype(jnp.float32), k_sc), key_scales)
        new_vs = jnp.where(smask, jnp.einsum(
            "bsn,bso,bsh->noh", oh_p.astype(jnp.float32),
            oh_o.astype(jnp.float32), v_sc), value_scales)
    kb = jnp.take(new_pk, table, axis=0)
    vb = jnp.take(new_pv, table, axis=0)
    if quant:
        kb = _kv_dequantize(kb, jnp.take(new_ks, table, axis=0), dtype)
        vb = _kv_dequantize(vb, jnp.take(new_vs, table, axis=0), dtype)
    kf, vf = _kv_repeat(q, kb.reshape(B, L, n_kv, Dh),
                        vb.reshape(B, L, n_kv, Dh))
    scale = 1.0 / jnp.sqrt(jnp.asarray(Dh, jnp.float32))
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, kf).astype(jnp.float32)
    logits = logits * scale
    visible = (jnp.arange(L)[None, None, :]
               <= (idx[:, None, None] + jnp.arange(S)[None, :, None]))
    logits = jnp.where(visible[:, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, vf)
    return out, (new_pk, new_pv, new_ks, new_vs)


def _make_case(seed, H, n_kv, kv_dtype="float32", act_dtype=None,
               S=12, P=8, max_pages=4, Dh=16, starts=(0, 8, 12, 0),
               pad_rows=(3,), extra_pages=3):
    """Ragged multi-row burst: starts cover a fresh row (0), a
    page-aligned context (8), and a page-straddling one (12); pad rows
    carry the all-sink table the serving layer gives them.  Real pages
    are a shuffled slice of a larger pool (identity tables would hide
    routing bugs); unallocated tails alias the sink."""
    rng = np.random.RandomState(seed)
    B = len(starts)
    NP = B * max_pages - len(pad_rows) * max_pages + extra_pages
    act = act_dtype or ("float32" if kv_dtype == "int8" else kv_dtype)
    q = jnp.asarray(rng.randn(B, S, H, Dh), act)
    k = jnp.asarray(rng.randn(B, S, n_kv, Dh), act)
    v = jnp.asarray(rng.randn(B, S, n_kv, Dh), act)
    if kv_dtype == "int8":
        pk = jnp.asarray(
            rng.randint(-127, 128, (NP, P, n_kv, Dh)), jnp.int8)
        pv = jnp.asarray(
            rng.randint(-127, 128, (NP, P, n_kv, Dh)), jnp.int8)
        ks = jnp.asarray(rng.uniform(0.005, 0.02, (NP, P, n_kv)),
                         jnp.float32)
        vs = jnp.asarray(rng.uniform(0.005, 0.02, (NP, P, n_kv)),
                         jnp.float32)
    else:
        pk = jnp.asarray(rng.randn(NP, P, n_kv, Dh), kv_dtype)
        pv = jnp.asarray(rng.randn(NP, P, n_kv, Dh), kv_dtype)
        ks = vs = None
    sink = NP - 1
    perm = rng.permutation(NP - 1)  # never the sink
    table = np.full((B, max_pages), sink, np.int32)
    off = 0
    for b, st in enumerate(starts):
        if b in pad_rows:
            continue                # pad rows keep the all-sink table
        used = min(max_pages, -(-(int(st) + S) // P))
        table[b, :used] = perm[off:off + used]
        off += used
    return (q, k, v, pk, pv, jnp.asarray(table),
            jnp.asarray(starts, jnp.int32), ks, vs, sink, pad_rows)


def _check(case, atol, pools_exact=True):
    q, k, v, pk, pv, table, starts, ks, vs, sink, pad_rows = case
    out, pools = paged_prefill(q, k, v, pk, pv, table, starts,
                               key_scales=ks, value_scales=vs)
    ref_out, ref_pools = _blend_ref(q, k, v, pk, pv, table, starts,
                                    key_scales=ks, value_scales=vs)
    assert out.shape == q.shape and out.dtype == q.dtype
    nonsink = np.arange(pk.shape[0]) != sink
    for got, want in zip(pools, ref_pools):
        if want is None:
            assert got is None
            continue
        assert got.shape == want.shape and got.dtype == want.dtype
        if pools_exact:
            np.testing.assert_array_equal(np.asarray(got)[nonsink],
                                          np.asarray(want)[nonsink])
        else:
            np.testing.assert_allclose(
                np.asarray(got, np.float32)[nonsink],
                np.asarray(want, np.float32)[nonsink], atol=atol)
    live = [b for b in range(q.shape[0]) if b not in pad_rows]
    np.testing.assert_allclose(np.asarray(out, np.float32)[live],
                               np.asarray(ref_out, np.float32)[live],
                               atol=atol)
    return out, pools


@pytest.mark.parametrize("H,n_kv", [(4, 2), (4, 4)],
                         ids=["gqa", "mha"])
@pytest.mark.parametrize("kv_dtype,act_dtype,atol", [
    ("float32", None, 2e-5), ("bfloat16", None, 3e-2),
    ("int8", "float32", 2e-5), ("int8", "bfloat16", 3e-2),
], ids=["f32", "bf16", "int8kv", "int8kv-bf16"])
def test_kernel_matches_blend_ragged_burst(H, n_kv, kv_dtype, act_dtype,
                                           atol):
    case = _make_case(0, H=H, n_kv=n_kv, kv_dtype=kv_dtype,
                      act_dtype=act_dtype)
    _check(case, atol)


def test_prefix_skip_unaligned_start():
    # prefix-cache skip: the row resumes mid-page (start=17) — the
    # straddled page's stale tail must be masked and the fresh chunk
    # positions must come from the activations
    case = _make_case(1, H=4, n_kv=2, S=8, starts=(17,), pad_rows=())
    _check(case, 2e-5)


def test_page_boundary_chunk_wider_than_page():
    # S wider than two pages: one chunk touches W = ceil(S/P)+1 = 4
    # logical blocks, interior ones fully overwritten
    case = _make_case(2, H=4, n_kv=2, S=20, starts=(0, 7),
                      pad_rows=())
    _check(case, 2e-5)


def test_bucket_pad_overshoot_clips_into_last_block():
    # bucket-pad overshoot: start+S runs past the table, positions clip
    # into the LAST logical block and collide — the blend SUMS
    # collisions, and the kernel's one-hot matmul must reproduce that
    # exactly.  Output parity is meaningless here (overshoot positions
    # are pad, the model never reads them), so compare pools only.
    case = _make_case(3, H=4, n_kv=2, S=12, starts=(28,), pad_rows=())
    q, k, v, pk, pv, table, starts, ks, vs, sink, _ = case
    _, pools = paged_prefill(q, k, v, pk, pv, table, starts)
    _, ref_pools = _blend_ref(q, k, v, pk, pv, table, starts)
    nonsink = np.arange(pk.shape[0]) != sink
    for got, want in zip(pools[:2], ref_pools[:2]):
        np.testing.assert_array_equal(np.asarray(got)[nonsink],
                                      np.asarray(want)[nonsink])


def test_rejects_bad_shapes():
    q, k, v, pk, pv, table, starts, _, _, _, _ = _make_case(
        4, H=4, n_kv=2, starts=(0,), pad_rows=())
    with pytest.raises(ValueError, match="multiple of kv heads"):
        paged_prefill(q[:, :, :3], k, v, pk, pv, table, starts)
    with pytest.raises(ValueError, match="must be"):
        paged_prefill(q, k[:, :4], v[:, :4], pk, pv, table, starts)
    with pytest.raises(ValueError, match="need key_scales"):
        paged_prefill(q, k, v, pk.astype(jnp.int8), pv.astype(jnp.int8),
                      table, starts)
    with pytest.raises(ValueError, match="only meaningful for int8"):
        paged_prefill(q, k, v, pk, pv, table, starts,
                      key_scales=jnp.ones((11, 8, 2)),
                      value_scales=jnp.ones((11, 8, 2)))


def _pool_bytes(cache, sink):
    """Every paged pool leaf (payload + scales) with the sink page
    zeroed, keyed by its flattened path, for byte comparison."""
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]:
        name = "/".join(str(p) for p in path)
        if "pages_" in name:
            a = np.asarray(leaf).copy()
            a[sink] = 0
            out[name] = a
    assert out
    return out


def test_model_body_kernel_vs_blend(monkeypatch):
    """Drive the REAL _paged_attention_body both ways: same params,
    same prompt, paged_prefill_impl='kernel' vs 'blend' — prefill
    logits allclose, greedy decode tokens identical, and the non-sink
    pool contents allclose.  A spy asserts the kernel branch actually
    traced (a silently-disabled kernel would otherwise make this
    blend-vs-blend)."""
    from tensorflowonspark_tpu.models import decode
    from tensorflowonspark_tpu.models import transformer as tf_mod
    from tensorflowonspark_tpu.models.transformer import (
        Transformer, TransformerConfig)

    traced = {"kernel": False}
    real = tf_mod.paged_prefill

    def spy(*a, **kw):
        traced["kernel"] = True
        return real(*a, **kw)

    monkeypatch.setattr(tf_mod, "paged_prefill", spy)

    # distinctive dims so the lru-cached jits can't be a stale trace
    # from another test file (the spy must see THIS tracing)
    cfg = TransformerConfig(
        vocab_size=72, d_model=64, n_heads=4, n_kv_heads=2, n_layers=2,
        d_ff=80, max_seq_len=32, dtype="float32", rope=True,
        attention_impl="dense")
    model = Transformer(cfg)
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    prompt = list(np.random.RandomState(11).randint(0, 72, size=11))
    page, n_pages = 8, 9          # max_pages=4 per row; page 8 = sink

    results = {}
    for impl in ("kernel", "blend"):
        traced["kernel"] = False
        slot_model, cache = decode.init_paged_slot_cache(
            model, 2, page, n_pages, paged_prefill_impl=impl)
        set_table = decode._jitted_set_row_page_table(slot_model)
        cache = set_table(cache, jnp.asarray(0, jnp.int32),
                          jnp.asarray([5, 2, 7, 0], jnp.int32))
        cache = set_table(cache, jnp.asarray(1, jnp.int32),
                          jnp.full((4,), 8, jnp.int32))
        prefill = decode._jitted_slot_prefill(slot_model)
        step = decode._jitted_slot_step(slot_model)
        padded = prompt + [0] * (16 - len(prompt))
        logits, cache = prefill(
            params, cache, jnp.asarray([padded], jnp.int32),
            jnp.asarray(0, jnp.int32), jnp.asarray(0, jnp.int32),
            jnp.asarray(len(prompt), jnp.int32))
        fired = traced["kernel"]
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
        results[impl] = (np.asarray(logits, np.float32), seq,
                         _pool_bytes(cache, sink=8), fired)

    assert results["kernel"][3], \
        "paged_prefill_impl='kernel' never reached the kernel (gating " \
        "bug would make this test vacuous)"
    assert not results["blend"][3], \
        "paged_prefill_impl='blend' must not trace the kernel"
    np.testing.assert_allclose(results["kernel"][0],
                               results["blend"][0], atol=1e-4)
    assert results["kernel"][1] == results["blend"][1]
    kp, bp = results["kernel"][2], results["blend"][2]
    assert kp.keys() == bp.keys()
    for name in kp:
        # layer >0 pools cannot be byte-exact across impls: their k/v
        # projections consume the PREVIOUS layer's attention output,
        # which carries f32 rounding differences between the two read
        # paths.  Byte-exactness of the write itself is pinned at the
        # ops level (test_kernel_matches_blend_ragged_burst).
        np.testing.assert_allclose(kp[name], bp[name], atol=1e-5)
