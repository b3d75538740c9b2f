"""Ulysses-style sequence parallelism: all-to-all head/sequence re-sharding.

The second long-context strategy next to ring attention (the reference has
neither — SURVEY.md §5 "Long-context / sequence parallelism: absent").
Activations travel the network sequence-sharded [B, S/N, H, D]; around the
attention core two `lax.all_to_all` collectives swap the sharded axis so
attention sees full sequences with H/N local heads:

    [B, S/N, H, D] --all2all--> [B, S, H/N, D] --attn--> --all2all--> back

Each all-to-all moves only 1/N of the activation bytes per device and rides
ICI; the attention core itself is the unsharded on-device kernel, so this
composes directly with the pallas flash kernel (ops/flash_attention) — in
contrast to ring attention, which pays N neighbor exchanges of K/V but
never materializes the full sequence on any device.  Rule of thumb: Ulysses
when heads >= N and HBM fits S (cheaper collectives, full-power kernel);
ring when S alone exceeds HBM.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax


def _ulysses_local(q, k, v, axis_name, causal, attn_fn, narrow_ok=False):
    """Body under shard_map: q/k/v are [B, S/N, H, D] local blocks.
    ``narrow_ok``: the attention core accepts GQA-narrow kv directly
    (the default flash/reference cores do since round 5), so the local
    post-all-to-all repeat is skipped; custom ``attn_fn``s keep it."""
    axis_size = lax.psum(1, axis_name)

    def seq_to_heads(x):
        # [B, S/N, H, D] -> [B, S, H/N, D]: split heads over the axis,
        # concatenate the gathered sequence blocks
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    def heads_to_seq(x):
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    H_q, H_kv = q.shape[2], k.shape[2]
    if H_q % axis_size:
        raise ValueError(
            f"n_heads={H_q} must be divisible by the ulysses axis "
            f"size {axis_size}")
    if H_q != H_kv:
        # GQA: exchange kv as narrow as the axis allows — pre-repeat only
        # until the axis divides the head count (bytes moved scale with
        # pre/rep), broadcast the rest locally after the all-to-all.  The
        # jnp.repeat ordering keeps kv group g aligned with the q heads
        # that land on the same device.
        if H_q % H_kv:
            raise ValueError(
                f"n_heads={H_q} must be divisible by n_kv_heads={H_kv}")
        rep = H_q // H_kv
        pre = next(p for p in range(1, rep + 1)
                   if rep % p == 0 and (H_kv * p) % axis_size == 0)
        if pre > 1:
            k = jnp.repeat(k, pre, axis=2)
            v = jnp.repeat(v, pre, axis=2)
    qg, kg, vg = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    if not narrow_ok:
        # a custom attention core may not understand GQA-narrow kv;
        # the contiguous head split keeps group g's kv on the same
        # device as its q heads, so the local repeat mapping is exact
        from tensorflowonspark_tpu.parallel.ring_attention import _kv_repeat
        kg, vg = _kv_repeat(qg, kg, vg)
    out = attn_fn(qg, kg, vg, causal)
    return heads_to_seq(out)


def _default_attn(q, k, v, causal):
    """Full-sequence attention core: pallas flash on TPU, dense elsewhere."""
    from tensorflowonspark_tpu.ops import default_interpret
    from tensorflowonspark_tpu.ops.flash_attention import (
        attention_reference, flash_attention)
    if default_interpret():
        return attention_reference(q, k, v, causal=causal)
    return flash_attention(q, k, v, causal=causal)


def ulysses_attention(q, k, v, axis_name="tp", causal=True, mesh=None,
                      attn_fn=None, batch_axes=None):
    """Exact attention with q/k/v sequence-sharded over `axis_name`.

    Same calling contract as ring_attention: either inside an existing
    shard_map/jit context where `axis_name` is bound, or at top level with
    `mesh` given (concrete or abstract under jit) — then it wraps itself in
    shard_map with the sequence dim of [B, S, H, D] sharded over the axis
    and the batch dim over `batch_axes` (None = replicated).
    """
    narrow_ok = attn_fn is None      # the default cores take GQA-narrow kv
    attn_fn = attn_fn or _default_attn
    if mesh is None:
        return _ulysses_local(q, k, v, axis_name, causal, attn_fn,
                              narrow_ok=narrow_ok)

    from jax.sharding import PartitionSpec as P
    spec = P(batch_axes, axis_name, None, None)
    fn = functools.partial(_ulysses_local, axis_name=axis_name,
                           causal=causal, attn_fn=attn_fn,
                           narrow_ok=narrow_ok)
    return jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)
