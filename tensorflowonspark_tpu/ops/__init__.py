"""TPU kernel ops (Pallas).

The reference delegates all tensor math to TensorFlow and ships no kernels
of its own (SURVEY.md §1 "delegates all actual tensor math ... to TensorFlow
itself"); in a TPU-native framework the hot ops are first-class: hand-tiled
Pallas kernels that stream blocks HBM→VMEM and keep the MXU busy, with an
interpret-mode path so the same kernels are testable on the CPU mesh.

- flash_attention : blocked online-softmax attention, O(S) memory per core
- fused_unembed_xent : chunked lm_head matmul + cross entropy, no
  materialized logits (XLA scan, not Pallas — the MXU matmul is already
  optimal; the win is memory, see ops/xent.py)
- adamw_fused / lion_fused : single-pass optimizer updates — read
  grad/param/moments once, write param/moments once, clip scale inlined
  (see ops/fused_optim.py; surfaced via optim.make_optimizer)
- grouped_matmul : rows sorted by expert times the experts a chip holds
  (`[M, K] x [G, K, N]`, group sizes from the routing) — forward and both
  gradients, no work for rows past the last group (see
  ops/grouped_matmul.py; the dropless `MoEMLP` path,
  TransformerConfig.moe_router)
- paged_attention : flash-decode over the paged serving kv pool — page
  table scalar-prefetched, only occupied pages read (in place, no
  logical-view gather), online softmax + split-K LSE combine, int8
  dequant fused into the page read (see ops/paged_attention.py;
  the default paged read path, TransformerConfig.paged_attn_impl)
- paged_prefill : chunked prefill over the same pool — the chunk's k/v
  store page-granular and IN PLACE (input_output_aliases, int8
  requantization fused into the page store), then one online softmax
  over [occupied context pages || chunk]; O(chunk) traffic, no dense
  [B, max_seq] kv view (see ops/paged_prefill.py; the default S>1
  paged path, TransformerConfig.paged_prefill_impl)
- gate_norm : the Mamba-2 mixer's skip term, gate and norm by groups —
  one read of y, x, z and one write a pass, float32 inside the kernel,
  the backward recomputing the norm in VMEM (see ops/gate_norm.py; beside
  the SSD scan's kernels, ops/ssd.py, under `attention_impl`)
- quant_matmul : weight-stationary matmul over int8 / nibble-packed
  int4 kernels — weight tiles dequantize in VMEM (per-channel or
  per-group scales), the dense bf16/f32 kernel never exists in HBM
  (see ops/quant_matmul.py; the QuantDense decode path,
  TransformerConfig.quant_matmul_impl)
"""
from tensorflowonspark_tpu.ops.flash_attention import flash_attention
from tensorflowonspark_tpu.ops.fused_optim import adamw_fused, lion_fused
from tensorflowonspark_tpu.ops.grouped_matmul import grouped_matmul
from tensorflowonspark_tpu.ops.paged_attention import paged_attention
from tensorflowonspark_tpu.ops.paged_prefill import paged_prefill
from tensorflowonspark_tpu.ops.quant_matmul import quant_matmul
from tensorflowonspark_tpu.ops.xent import fused_unembed_xent

__all__ = ["flash_attention", "fused_unembed_xent", "adamw_fused",
           "lion_fused", "grouped_matmul", "paged_attention",
           "paged_prefill", "quant_matmul"]


def default_interpret():
    """Pallas kernels run natively on TPU, in interpret mode elsewhere
    (the CPU test mesh), so one code path covers both."""
    import jax
    return jax.default_backend() != "tpu"
