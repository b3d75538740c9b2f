"""The sub-tile schedule of the flash kernels, as arithmetic: which sub-tiles
of a resident block a call computes and which of them carry the mask
(`ops/flash_attention.py`: `_span`, `subtile_counts`, the `flash.subtiles.*`
counters).  Pure Python against a brute-force reading of the mask, in the fast
tier; the kernels' parity with the dense reference over the same schedules is
in `test_ops.py` (interpret mode, the slow tier)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorflowonspark_tpu.ops.flash_attention import flash_attention


def _brute_counts(S, block, sub, causal, window):
    """Sub-tiles of the padded square that hold a visible pair, and those of
    them that also hold an invisible one, from the mask itself."""
    padded = -(-S // block) * block
    i = np.arange(padded)[:, None]
    j = np.arange(padded)[None, :]
    seen = (i < S) & (j < S)
    if causal:
        seen &= i >= j
    if window is not None:
        seen &= i - j < window
    tq, tk = sub
    tiles = seen.reshape(padded // tq, tq, padded // tk, tk)
    some, every = tiles.any(axis=(1, 3)), tiles.all(axis=(1, 3))
    return int(some.sum()), int((some & ~every).sum()), some.size, some, every


SCHEDULES = [
    (1024, 1024, (256, 256), True, None),
    (1024, 1024, (128, 128), True, None),
    (1024, 1024, (512, 512), True, None),
    (1024, 1024, (256, 128), True, 256),
    (1024, 1024, (256, 256), True, 300),
    (1024, 1024, (256, 256), False, None),
    (1536, 512, (256, 256), True, 256),
    (1100, 1024, (256, 256), True, None),
    (1100, 1024, (128, 256), False, 77),
    (2048, 1024, (256, 256), True, 1000),
    (8192, 1024, (256, 256), True, 1024),
    (8192, 1024, (256, 256), True, None),
    (200, 64, (64, 64), True, 50),
    (3000, 1024, (256, 512), False, 1),
]


@pytest.mark.parametrize("S,block,sub,causal,window", SCHEDULES)
def test_flash_subtile_counts_match_the_mask(S, block, sub, causal, window):
    """`subtile_counts` (what the node counts as `flash.subtiles.*`) and
    the two walks the kernels take, against a brute-force reading of the
    mask: no sub-tile with a visible pair is left out, none without one is
    computed, and exactly those an edge crosses carry the mask."""
    from tensorflowonspark_tpu.ops.flash_attention import (
        _k_span, _q_span, subtile_counts)

    computed, masked, square, some, every = _brute_counts(
        S, block, sub, causal, window)
    assert subtile_counts(S, S, block, block, sub, causal, window) == (
        computed, masked, square)
    nr, nc = block // sub[0], block // sub[1]
    by_keys = np.zeros_like(some, dtype=int)     # 0 skipped, 1 masked, 2 plain
    by_queries = np.zeros_like(some, dtype=int)
    for blk in range(-(-S // block)):
        for row in range(some.shape[0]):
            lo, lo_m, hi_m, hi = _k_span(row * sub[0], blk * block, sub, nc,
                                         S, S, causal, window)
            assert 0 <= lo <= lo_m <= hi_m <= hi <= nc
            by_keys[row, blk * nc + lo:blk * nc + hi] = 1
            by_keys[row, blk * nc + lo_m:blk * nc + hi_m] = 2
        for col in range(some.shape[1]):
            lo, lo_m, hi_m, hi = _q_span(col * sub[1], blk * block, sub, nr,
                                         S, S, causal, window)
            assert 0 <= lo <= lo_m <= hi_m <= hi <= nr
            by_queries[blk * nr + lo:blk * nr + hi, col] = 1
            by_queries[blk * nr + lo_m:blk * nr + hi_m, col] = 2
    want = some.astype(int) + (some & every)
    np.testing.assert_array_equal(by_keys, want)
    np.testing.assert_array_equal(by_queries, want)


def test_flash_subtile_counts_of_the_benchmark_shapes():
    from tensorflowonspark_tpu.ops.flash_attention import subtile_counts
    # GPT-2 large, S=1024 in one block: 10 of 16 at 256, 4 of them masked
    assert subtile_counts(1024, 1024, 1024, 1024, (256, 256)) == (10, 4, 16)
    assert subtile_counts(1024, 1024, 1024, 1024, (512, 512)) == (3, 2, 4)
    assert subtile_counts(1024, 1024, 1024, 1024, (128, 128)) == (36, 8, 64)
    # the sparse-expert cell, S=8192: the full layer 528 of 1024 (576 in the
    # 36 resident blocks), a window layer 20 a query block but the first's 10
    assert subtile_counts(8192, 8192, 1024, 1024, (256, 256)) == (
        528, 32, 1024)
    assert subtile_counts(8192, 8192, 1024, 1024, (256, 256), True, 1024) == (
        150, 60, 1024)


def test_flash_counts_its_subtiles_once_a_traced_call():
    from tensorflowonspark_tpu import trace
    from tensorflowonspark_tpu.ops.flash_attention import (
        _flash_bwd_impl, _flash_fwd_impl, _pick_subtile, subtile_counts)

    # the implementations are jitted: a call that finds its trace cached
    # (one layer after another of a model) is not traced, nor counted
    _flash_fwd_impl.clear_cache()
    _flash_bwd_impl.clear_cache()
    names = [f"flash.subtiles.{k}" for k in ("computed", "masked", "square")]
    before = [trace.counters().get(n) or 0 for n in names]
    q, k, v = (jax.random.normal(key, (1, 1024, 1, 64))
               for key in jax.random.split(jax.random.key(0), 3))
    fn = jax.jit(jax.grad(lambda *a: jnp.sum(flash_attention(
        *a, causal=True, interpret=True)), (0, 1, 2)))
    fn.lower(q, k, v)                       # traced, never run
    got = [trace.counters().get(n) - b for n, b in zip(names, before)]
    one = subtile_counts(1024, 1024, 1024, 1024, _pick_subtile(1024, 1024))
    # the forward, dq and dkv: three kernels, each over the same schedule
    assert got == [3 * n for n in one]
    assert got[0] < got[2]                  # the causal skip fires at S=1024
