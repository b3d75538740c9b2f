"""Fused unembedding + softmax cross-entropy for LM training.

The naive path (reference analog: per-example ``tf.nn.sparse_softmax_cross_
entropy_with_logits`` over a full logits tensor, e.g. reference
examples/mnist/keras models) materializes float32 logits ``[B, S, V]``
TWICE per step (forward values + backward grads).  At LM scale this is
gigabytes of HBM traffic per step — for a 32k vocab and B8xS1024, ~1 GB
forward + ~2 GB one-hot/grad machinery — and on TPU the step becomes
HBM-bound precisely at its final matmul.

`fused_unembed_xent` takes the PRE-unembedding hidden states and the
lm_head kernel and computes the loss in sequence chunks under `lax.scan`:
each chunk's logits tile lives only in registers/VMEM-scale working set,
the softmax statistics are reduced on the fly, and the backward pass
RECOMPUTES each chunk's logits instead of saving them (classic
rematerialization — trade ~1 extra chunk matmul for the full logits
round trip).  Peak extra memory is one ``[chunk, V]`` float32 tile plus
the float32 kernel-gradient accumulator.

Step time against the materialized-logits `lm_loss` on this chip: not
measured (the scan serializes the head matmul, and the backward
recompute competes with the logits round-trip it saves).  Treat this op
as a MEMORY feature: it removes the [B, S, V] float32 logits tensor from both
passes, which is what lets long-sequence / large-vocab configs fit on a
chip at all.

Sharding note: the chunk loop gathers gold logits by target id, which
assumes the vocab dimension is unsharded in this function's frame.  Under
a vocab-sharded (tp) lm_head keep using `models.transformer.lm_loss`
(gather-free one-hot einsum, partitions cleanly); this op is the
single-device / data-parallel fast path — exactly the layouts the
benchmark's cells and the examples train in.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax

from tensorflowonspark_tpu import trace


def _chunk_stats(h_c, kernel, tgt_c, mask_c):
    """Loss pieces for one chunk: (sum((logz - gold) * mask), logits fn)."""
    logits = jnp.dot(h_c, kernel, preferred_element_type=jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, tgt_c[:, None], axis=-1)[:, 0]
    return jnp.sum((logz - gold) * mask_c)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def fused_unembed_xent(hidden, kernel, targets, chunk_size=512,
                       ignore_id=-1):
    """Mean softmax cross entropy of ``hidden @ kernel`` against ``targets``
    without materializing the logits.

    hidden:  [B, S, D] (any float dtype; matmul accumulates float32)
    kernel:  [D, V] lm_head kernel (``params["lm_head"]["kernel"]``)
    targets: [B, S] int ids; positions equal to ``ignore_id`` are masked
    chunk_size: tokens per scanned tile (static)

    Matches `models.transformer.lm_loss(model(tokens), targets)` to float32
    tolerance (see tests/test_xent.py) while cutting the step's HBM
    traffic by the full forward+backward logits volume.
    """
    loss, _ = _fwd(hidden, kernel, targets, chunk_size, ignore_id)
    return loss


def _pad_chunks(flat_h, flat_t, chunk_size, ignore_id):
    T = flat_h.shape[0]
    n_chunks = -(-T // chunk_size)
    pad = n_chunks * chunk_size - T
    if pad:
        flat_h = jnp.pad(flat_h, ((0, pad), (0, 0)))
        flat_t = jnp.pad(flat_t, (0, pad), constant_values=ignore_id)
    return flat_h, flat_t, n_chunks


@trace.loss_scope("unembed_xent")
def _fwd(hidden, kernel, targets, chunk_size, ignore_id):
    B, S, D = hidden.shape
    flat_h = hidden.reshape(B * S, D)
    flat_t = targets.reshape(B * S)
    flat_h, flat_t, n_chunks = _pad_chunks(flat_h, flat_t, chunk_size,
                                           ignore_id)
    h_c = flat_h.reshape(n_chunks, chunk_size, D)
    t_c = flat_t.reshape(n_chunks, chunk_size)

    def body(acc, xs):
        h, t = xs
        mask = (t != ignore_id).astype(jnp.float32)
        s = _chunk_stats(h, kernel, jnp.maximum(t, 0), mask)
        return (acc[0] + s, acc[1] + jnp.sum(mask)), None

    (total, count), _ = lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (h_c, t_c))
    count = jnp.maximum(count, 1.0)
    return total / count, (hidden, kernel, targets, count)


@trace.loss_scope("unembed_xent")
def _bwd(chunk_size, ignore_id, res, g):
    hidden, kernel, targets, count = res
    B, S, D = hidden.shape
    V = kernel.shape[1]
    flat_h = hidden.reshape(B * S, D)
    flat_t = targets.reshape(B * S)
    flat_h, flat_t, n_chunks = _pad_chunks(flat_h, flat_t, chunk_size,
                                           ignore_id)
    h_c = flat_h.reshape(n_chunks, chunk_size, D)
    t_c = flat_t.reshape(n_chunks, chunk_size)
    scale = g / count

    def body(dk_acc, xs):
        h, t = xs
        mask = (t != ignore_id).astype(jnp.float32)
        tt = jnp.maximum(t, 0)
        logits = jnp.dot(h, kernel, preferred_element_type=jnp.float32)
        p = jax.nn.softmax(logits, axis=-1)
        # d/dlogits of (logz - gold) = softmax - onehot
        dlogits = (p - jax.nn.one_hot(tt, V, dtype=jnp.float32))
        dlogits = dlogits * (mask * scale)[:, None]
        dh = jnp.dot(dlogits.astype(kernel.dtype), kernel.T,
                     preferred_element_type=jnp.float32)
        dk_acc = dk_acc + jnp.dot(h.astype(jnp.float32).T, dlogits,
                                  preferred_element_type=jnp.float32)
        return dk_acc, dh

    dk, dh_c = lax.scan(body, jnp.zeros((D, V), jnp.float32), (h_c, t_c))
    dh = dh_c.reshape(n_chunks * chunk_size, D)[:B * S]
    return (dh.reshape(B, S, D).astype(hidden.dtype),
            dk.astype(kernel.dtype), None)


fused_unembed_xent.defvjp(_fwd, _bwd)
