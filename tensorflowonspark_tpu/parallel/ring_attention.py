"""Ring attention: exact attention over sequence shards with O(S/N) memory
per device and K/V blocks rotated around the mesh axis via `lax.ppermute`.

Long-context machinery is absent from the reference (SURVEY.md §5
"Long-context / sequence parallelism: absent"); here it is first-class: the
sequence axis of q/k/v is sharded over a mesh axis (context parallelism) and
each device computes its queries against every K/V block as the blocks flow
around the ring, maintaining a numerically-stable online softmax
(flash-attention style running max/denominator), so the result is EXACTLY
dense attention.

Collectives ride ICI: each step's ppermute is a neighbor exchange, which is
the optimal pattern on a TPU torus.

Two local-compute paths:
- `use_flash=True` (default on TPU): each ring step runs the pallas flash
  kernel on (q_local, k_blk, v_blk) and merges the per-block outputs by
  their logsumexp — ring handles the cross-device axis, the kernel the
  on-device blocks, and the [S/N, S/N] score tile never hits HBM.  Causal
  steps pick the right kernel mode per device via `lax.switch` (past block
  → non-causal, diagonal → causal, future → skipped with zero weight).
- `use_flash=False`: a pure-jnp online-softmax update (the CPU test mesh
  path, and the reference semantics the kernel path is tested against).
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax


def _online_update(o, m, l, logits, v_blk):
    """One block's contribution via streaming softmax.

    o: [B, Sq, H, D] accumulated (unnormalized) output
    m: [B, H, Sq]    running max
    l: [B, H, Sq]    running denominator
    logits: [B, H, Sq, Sk] this block's scores (f32, already masked)
    """
    m_blk = jnp.max(logits, axis=-1)                       # [B,H,Sq]
    m_new = jnp.maximum(m, m_blk)
    # guard fully-masked rows: exp(-inf - -inf) -> use safe max
    alpha = jnp.exp(m - m_new)                              # rescale old
    p = jnp.exp(logits - m_new[..., None])                  # [B,H,Sq,Sk]
    l_new = l * alpha + jnp.sum(p, axis=-1)
    o_new = o * alpha.transpose(0, 2, 1)[..., None] + jnp.einsum(
        "bhqk,bkhd->bqhd", p.astype(v_blk.dtype), v_blk).astype(jnp.float32)
    return o_new, m_new, l_new


def _kv_repeat(q, k_blk, v_blk):
    """Broadcast narrow (GQA) k/v heads to the query head count — on-device,
    after the collectives moved only the narrow tensors."""
    H, H_kv = q.shape[2], k_blk.shape[2]
    if H == H_kv:
        return k_blk, v_blk
    if H % H_kv:
        raise ValueError(
            f"q heads {H} must be divisible by kv heads {H_kv}")
    rep = H // H_kv
    return jnp.repeat(k_blk, rep, axis=2), jnp.repeat(v_blk, rep, axis=2)


def _ring_jnp_local(q, k, v, axis_name, causal):
    """Body running under shard_map: q/k/v are the LOCAL sequence blocks.

    k/v may carry fewer (GQA) heads than q — they ride the ring narrow and
    are broadcast per step."""
    axis_size = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    B, Sq, H, D = q.shape
    scale = 1.0 / jnp.sqrt(jnp.asarray(D, jnp.float32))

    q32 = q
    o = jnp.zeros((B, Sq, H, D), jnp.float32)
    m = jnp.full((B, H, Sq), -jnp.inf, jnp.float32)
    l = jnp.zeros((B, H, Sq), jnp.float32)

    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    def step_fn(carry, step):
        o, m, l, k_blk, v_blk = carry
        # which global block is currently resident: blocks rotate forward,
        # so at `step` we hold block (my_idx - step) mod N
        blk_idx = (my_idx - step) % axis_size
        k_use, v_use = _kv_repeat(q, k_blk, v_blk)
        logits = jnp.einsum("bqhd,bkhd->bhqk", q32, k_use).astype(jnp.float32)
        logits = logits * scale
        if causal:
            Sk = k_blk.shape[1]
            q_pos = my_idx * Sq + jnp.arange(Sq)            # global q positions
            k_pos = blk_idx * Sk + jnp.arange(Sk)
            mask = q_pos[:, None] >= k_pos[None, :]
            logits = jnp.where(mask[None, None], logits, -1e30)
        o, m, l = _online_update(o, m, l, logits, v_use)
        k_next = lax.ppermute(k_blk, axis_name, perm)
        v_next = lax.ppermute(v_blk, axis_name, perm)
        return (o, m, l, k_next, v_next), None

    (o, m, l, _, _), _ = lax.scan(step_fn, (o, m, l, k, v),
                                  jnp.arange(axis_size))
    out = o / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def _ring_flash_local(q, k, v, axis_name, causal, interpret):
    """Ring body whose per-step local compute is the pallas flash kernel.

    Per step the kernel returns (out_blk normalized within the block,
    lse_blk); blocks merge by logsumexp weights — algebraically identical
    to the online update, so the result stays exact.
    """
    from tensorflowonspark_tpu.ops.flash_attention import (
        flash_attention_with_lse)
    axis_size = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    B, Sq, H, D = q.shape

    attn = functools.partial(flash_attention_with_lse, interpret=interpret)
    o = jnp.zeros((B, Sq, H, D), jnp.float32)   # lse-weighted accumulator
    m = jnp.full((B, H, Sq), -jnp.inf, jnp.float32)  # running max lse
    l = jnp.zeros((B, H, Sq), jnp.float32)      # running total weight

    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    def step_fn(carry, step):
        o, m, l, k_blk, v_blk = carry
        blk_idx = (my_idx - step) % axis_size
        # GQA kv stays NARROW all the way into the kernel (round 5: the
        # flash kernel indexes kv blocks per q-head group itself) — the
        # repeated kv no longer materializes even locally
        k_use, v_use = k_blk, v_blk

        if causal:
            # 0: past block (fully visible), 1: diagonal (causal within),
            # 2: future block (fully masked — contribute zero weight)
            case = jnp.where(blk_idx < my_idx, 0,
                             jnp.where(blk_idx == my_idx, 1, 2))
            out_blk, lse_blk = lax.switch(
                case,
                [lambda q, k, v: attn(q, k, v, causal=False),
                 lambda q, k, v: attn(q, k, v, causal=True),
                 lambda q, k, v: (jnp.zeros_like(q),
                                  jnp.full((B, H, Sq), -jnp.inf,
                                           jnp.float32))],
                q, k_use, v_use)
        else:
            out_blk, lse_blk = attn(q, k_use, v_use, causal=False)

        # merge by lse: out_blk carries weight exp(lse_blk)
        m_new = jnp.maximum(m, lse_blk)
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        w = jnp.where(jnp.isfinite(lse_blk), jnp.exp(lse_blk - m_safe), 0.0)
        o = (o * alpha.transpose(0, 2, 1)[..., None]
             + out_blk.astype(jnp.float32)
             * w.transpose(0, 2, 1)[..., None])
        l = l * alpha + w
        k_next = lax.ppermute(k_blk, axis_name, perm)
        v_next = lax.ppermute(v_blk, axis_name, perm)
        return (o, m_new, l, k_next, v_next), None

    (o, m, l, _, _), _ = lax.scan(step_fn, (o, m, l, k, v),
                                  jnp.arange(axis_size))
    out = o / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def _ring_attention_local(q, k, v, axis_name, causal, use_flash=None,
                          interpret=None):
    if use_flash is None:
        use_flash = jax.default_backend() == "tpu"
    if use_flash:
        return _ring_flash_local(q, k, v, axis_name, causal, interpret)
    return _ring_jnp_local(q, k, v, axis_name, causal)


def ring_attention(q, k, v, axis_name="tp", causal=True, mesh=None,
                   use_flash=None, interpret=None, batch_axes=None):
    """Exact attention with q/k/v sequence-sharded over `axis_name`.

    Call either (a) inside an existing shard_map/jit context where
    `axis_name` is bound — then this runs the local body directly — or
    (b) at top level with `mesh` provided (concrete, or abstract under
    jit), in which case it wraps itself in shard_map with the sequence dim
    of [B, S, H, D] sharded over the axis and the batch dim over
    `batch_axes` (None = replicated).
    """
    if mesh is None:
        return _ring_attention_local(q, k, v, axis_name, causal,
                                     use_flash=use_flash,
                                     interpret=interpret)

    from jax.sharding import PartitionSpec as P
    spec = P(batch_axes, axis_name, None, None)
    fn = functools.partial(_ring_attention_local, axis_name=axis_name,
                           causal=causal, use_flash=use_flash,
                           interpret=interpret)
    return jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)
