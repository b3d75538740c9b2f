"""Transformer LM — the flagship distributed model family.

Net-new relative to the reference (which is DP-only, SURVEY.md §2.3): this
model is built so that the framework's sharding rules
(parallel/sharding.DEFAULT_RULES) give Megatron-style tensor parallelism by
name — column-parallel query/key/value and mlp.wi, row-parallel attn.out and
mlp.wo, vocab-sharded embedding/lm_head — and XLA inserts the tp collectives
from the shardings alone.  Long-context support comes from ring attention
(parallel/ring_attention.py) engaged when sequence shards are placed on the
tp axis; MoE layers shard experts over the ep (=dp) axis.

TPU notes: bfloat16 activations, f32 layernorm/softmax accumulators, static
shapes everywhere, einsum formulations that map onto the MXU.
"""
import dataclasses
import functools
import itertools
import logging
import math
from typing import Any, Optional

import flax.linen as nn
from flax import struct
import jax
import jax.numpy as jnp

from tensorflowonspark_tpu import trace
from tensorflowonspark_tpu.parallel.ring_attention import _kv_repeat
from tensorflowonspark_tpu.ops.paged_attention import paged_attention
from tensorflowonspark_tpu.ops.paged_prefill import paged_prefill
from tensorflowonspark_tpu.ops.quant_matmul import quant_matmul

logger = logging.getLogger(__name__)


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_heads: int = 8
    n_kv_heads: Optional[int] = None  # GQA: kv heads < query heads (1 = MQA)
    n_layers: int = 6
    d_ff: int = 2048
    max_seq_len: int = 2048
    causal: bool = True
    dtype: str = "bfloat16"
    rope: bool = False            # rotary position embeddings instead of
    # a learned absolute pos_embed table
    learned_positions: bool = True  # without rope: a learned pos_embed
    # table (False: no positions at all, order comes from the mixers)
    rope_theta: float = 10000.0
    head_dim: Optional[int] = None  # None = d_model // n_heads; set where
    # n_heads * head_dim is not d_model (out is [n_heads*head_dim, d_model])
    layer_types: Optional[tuple] = None  # one kind a layer, "full_attention",
    # "sliding_attention", "conv" or "kda" (None = all full); a sliding layer
    # sees the last `sliding_window` keys only and rotates by
    # `rope_local_theta`; a conv layer mixes the sequence with `ShortConv`, a
    # kda layer with `KimiDeltaMixer`, not attention
    sliding_window: Optional[int] = None
    layer_pattern: Optional[str] = None  # one character a layer, each layer
    # holding ONE part under one norm, `x + part(norm(x))` (Nemotron-H's
    # `hybrid_override_pattern`): "M" a Mamba-2 mixer, "*" attention, "E"
    # sparse experts (MoEMLP); None = every layer holds a mixer
    # (`layer_types`) and a feed-forward part
    conv_kernel: int = 3          # taps of a conv layer's depthwise filter
    # (and of a Mamba-2 mixer's, over x, B and C, with a bias; and of a kda
    # layer's three, over q, k and v, without)
    ssm_heads: int = 0            # Mamba-2 mixer: heads of `ssm_head_dim`
    # lanes of x, each reading the group h // (heads / groups) of the
    # `ssm_groups` groups of B and C of `ssm_state` lanes (`Mamba2Mixer`)
    ssm_head_dim: int = 0
    ssm_groups: int = 1
    ssm_state: int = 0
    kda_heads: int = 0            # Kimi Delta Attention (`KimiDeltaMixer`):
    # heads of `kda_head_dim` key and value lanes, each with a state of
    # `kda_head_dim` x `kda_head_dim`; the decay's and the output gate's
    # low rank is `kda_head_dim` too
    kda_head_dim: int = 0
    qk_norm: bool = False         # RMSNorm over head_dim on every query and
    # key head (a scale of head_dim each), before the rotation
    kv_lora_rank: Optional[int] = None  # latent attention (MLA): keys and
    # values come up from a latent of this rank (`kv_a` [D, rank +
    # qk_rope_head_dim], RMSNorm on the latent, `kv_b` [rank, H * (nope +
    # v)]), queries through one of `q_lora_rank` (`q_a`, RMSNorm, `q_b`
    # [rank, H * (nope + rope)]; None: `query` [D, H * (nope + rope)]
    # straight from the input); a head scores `[q_nope | q_rope]` against
    # `[k_nope | k_rope]`, where `k_rope` is ONE vector a token shared by
    # all heads (rotated with its query's lanes under `rope`, as it is
    # without), under a scale of (nope + rope) ** -0.5, and its values are
    # `v_head_dim` wide (`out` is [H * v, D])
    q_lora_rank: Optional[int] = None
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0     # only these lanes are rotated
    v_head_dim: int = 0
    rope_interleave: bool = False  # latent attention: a rotated pair is
    # lanes (2i, 2i+1), not (i, i + half)
    rope_local_theta: Optional[float] = None  # sliding layers' base (plain
    # rotary; None = rope_theta)
    rope_yarn_factor: float = 1.0  # full layers: YaRN scaling of rope_theta's
    # frequencies (1.0 = plain rotary); the four fields below are its
    # published parameters, `rope_attention_factor` multiplies cos and sin
    rope_yarn_original_max: int = 0
    rope_yarn_beta_fast: float = 32.0
    rope_yarn_beta_slow: float = 1.0
    rope_attention_factor: float = 1.0
    num_experts: int = 0          # 0 = dense MLP; >0 = MoE with EP sharding
    moe_every: int = 2            # every k-th layer is MoE (when enabled)
    moe_router: str = "dense"     # dense (every token through every expert,
    # exact, test-friendly) | topk (GShard-style capacity dispatch) |
    # dropless (top-k, rows sorted by expert through ops.grouped_matmul:
    # no capacity, no token dropped; one chip, raises under a mesh)
    moe_top_k: int = 1            # experts per token under the topk router
    moe_capacity_factor: float = 1.25  # per-expert slots = factor*k*T/E
    moe_d_ff: Optional[int] = None  # an expert's width (None = d_ff)
    moe_dense_layers: int = 0     # the first n layers keep the dense MLP
    # (of width d_ff) whatever `moe_every` says of them
    moe_scoring: str = "softmax"  # dropless: softmax over the router's
    # logits | sigmoid of each (weights over their sum + 1e-6)
    moe_expert_bias: bool = False  # dropless: a leaf `expert_bias` [E] is
    # added to the scores for the CHOICE of the k experts only; the weights
    # come from the scores alone, so its gradient is zero and an optimizer
    # without weight decay leaves it where it is
    moe_shared_experts: int = 0   # dropless: n shared experts, one MLP of
    # n x moe_d_ff that every token passes, added to the routed sum;
    # with `moe_experts_held` every chip computes it in full, alike
    moe_routed_scale: float = 1.0  # dropless: the routed sum's factor
    # (the weights over their sum, times this)
    moe_experts_held: Optional[int] = None  # dropless: this chip's share of
    # an expert-parallel layer: experts [offset, offset + held) live here,
    # the router scores all `num_experts`, and what the absent experts
    # would have added is left out of the layer's output (None = all)
    moe_expert_offset: int = 0
    tie_embeddings: bool = False  # the head reads the embedding's table
    # (`x @ E^T`): no `lm_head` leaf; under `return_hidden` the caller
    # hands `E^T` to ops.xent.fused_unembed_xent
    mtp_modules: int = 0          # multi-token-prediction modules behind
    # the last block (training only): module k reads the hidden state
    # before it (before the last norm) and the embedding of the token k+1
    # ahead, `proj([RMSNorm(h) | RMSNorm(e)])` [2D, D], one more block, a
    # last norm of its own, and shares the table and the head; under
    # `return_hidden` the model returns `(hidden, (hidden_1, ...))`
    mtp_loss_weight: float = 0.0  # `next_token_losses`: the weight of the
    # modules' mean cross entropy beside the next token's
    remat: bool = False           # rematerialise each block in the backward
    # pass, keeping its input and, where attention ran in the Pallas kernels,
    # their output `[B, S, H*Dv]` and row statistics `f32[B, H, S]`
    # (`remat_block`): the recomputed forward holds no attention kernel
    ring_attention_axis: Optional[str] = None  # e.g. "tp" to enable CP
    ulysses_axis: Optional[str] = None  # all-to-all sequence parallelism
    sp_axis: Optional[str] = None  # Megatron-SP: shard residual stream's
    # sequence dim over this axis between blocks (usually "tp")
    attention_impl: str = "auto"  # auto | flash (pallas) | dense; the
    # Mamba-2 mixers' SSD scan follows it (ops/ssd.py kernels | jax.numpy)
    use_bias: bool = False        # bias terms on qkv/out/mlp denses
    # (True matches GPT-2-family checkpoints; see convert.py)
    ln_eps: float = 1e-6          # layernorm epsilon (GPT-2 ckpts: 1e-5)
    norm_type: str = "layernorm"  # layernorm | rmsnorm (LLaMA-family:
    # scale-only, no mean subtraction — one statistics reduce per norm
    # instead of two, which is exactly the flagship profile's non-matmul
    # tail; convergence-equivalent for pre-LN decoders)
    norm_style: str = "pre"       # pre-LN (GPT/LLaMA) | post-LN (BERT)
    activation: str = "gelu_tanh"  # gelu_tanh | gelu_exact | relu | silu
    # | relu2 (relu squared)
    mlp_style: str = "plain"      # plain (wo(act(wi x))) | gated (LLaMA
    # GLU: wo(act(wi_gate x) * (wi_up x)); SwiGLU with activation='silu')
    decode: bool = False          # autoregressive mode: kv cache of
    # max_seq_len (narrow n_kv_heads — the GQA HBM win), incremental steps
    decode_slots: bool = False    # continuous-batching decode: cache_index
    # is PER ROW [B] (vmapped cache writes, per-row rope positions and
    # visibility), so each batch row is an independent serving slot that
    # requests can join/leave at token boundaries (serve.ContinuousBatcher)
    kv_page_size: int = 0         # >0 (with decode_slots): PAGED kv cache —
    # kv lives in a shared pool of kv_pages pages of this many tokens;
    # each row maps logical blocks to pool pages via a per-row page_table
    # (vLLM-style).  Rows then consume pool pages proportional to their
    # ACTUAL sequence need instead of reserving max_seq_len each — the
    # capacity win that lets n_slots exceed the dense-cache HBM limit.
    kv_pages: int = 0             # pool size (pages) when kv_page_size > 0
    kv_table_pages: int = 0       # >0: INITIAL per-row page_table width
    # (pages); the serving layer grows tables geometrically in pow2
    # steps (decode._jitted_grow_page_table) as prefill chunks land, so
    # a short chat row never pays table bytes for a max_seq_len-capable
    # mapping.  0 = full width (max_seq_len // kv_page_size), the static
    # layout every pre-growth caller gets by default.  Attention derives
    # the LIVE width from the page_table leaf itself, so a grown cache
    # costs one fresh trace per pow2 width — O(log) compiles, like
    # `_jitted_set_row_page_table`'s per-width retraces.
    kv_dtype: str = "auto"        # decode kv-cache storage: "auto" = the
    # activation dtype; "int8" = quantized cache (int8 payload +
    # per-(token, head) f32 scales over head_dim, quantize-on-write /
    # dequant-on-read fused into the attention reads) — ~2x less
    # resident kv vs bf16 (~4x vs f32), the same trade as weight-only
    # int8 but for the cache, composing with slots and paging
    paged_attn_impl: str = "kernel"  # paged decode READ path: "kernel"
    # = the Pallas flash-decode kernel (ops/paged_attention.py — walks
    # the page table in place via scalar prefetch, visits only occupied
    # pages, online softmax + split-K LSE combine, int8 dequant fused
    # into the page read); "einsum" = the reference full-gather body
    # (kept for parity tests and as the fallback under an active mesh,
    # where an unpartitionable pallas custom call cannot run)
    quant_matmul_impl: str = "kernel"  # quantized weight matmul path:
    # "kernel" = the Pallas fused-dequant matmul (ops/quant_matmul.py —
    # int8/int4 weight tiles dequantize in VMEM, the dense kernel never
    # exists in HBM); "dequant" = inline ``q.astype(dtype) * scale``
    # under the trace (XLA fuses it into the consumer — the parity
    # oracle, and the fallback under an active mesh like paged_attn_impl).
    # Only consulted when the param tree holds quantized leaves
    # (quantize.qdense_view); float trees always take the plain Dense path.
    paged_prefill_impl: str = "kernel"  # paged prefill (S>1) WRITE+READ
    # path: "kernel" = the Pallas paged-prefill kernels
    # (ops/paged_prefill.py — the chunk's k/v store page-granular and IN
    # PLACE into the pool via input_output_aliases, int8 requantization
    # and scale-page writes fused into the store; the read is online
    # softmax over [occupied context pages || chunk] with no dense
    # [B, max_seq] kv view) — per-chunk traffic scales with the CHUNK,
    # not the pool; "blend" = the reference one-hot einsum blend +
    # full-gather read (O(pool) write / O(max_seq) read per chunk, kept
    # for parity tests and as the mesh fallback like paged_attn_impl)

    def __post_init__(self):
        if isinstance(self.layer_types, list):   # from a JSON file
            object.__setattr__(self, "layer_types", tuple(self.layer_types))
        kinds = self.layer_types or ()
        if kinds and len(kinds) != self.n_layers:
            raise ValueError(f"layer_types names {len(kinds)} layers, "
                             f"n_layers={self.n_layers}")
        bad = set(kinds) - set(LAYER_KINDS)
        if bad:
            raise ValueError(f"layer_types {sorted(bad)} not in {LAYER_KINDS}")
        if SLIDING in kinds and not self.sliding_window:
            raise ValueError("sliding_attention layers need sliding_window")
        pattern = self.layer_pattern or ""
        if pattern and (kinds or len(pattern) != self.n_layers
                        or set(pattern) - set(PARTS)):
            raise ValueError(
                f"layer_pattern {pattern!r} needs n_layers={self.n_layers} "
                f"characters of {PARTS!r} and no layer_types")
        if "E" in pattern and not self.num_experts:
            raise ValueError("layer_pattern 'E' layers need num_experts")
        if MAMBA in pattern and not (self.ssm_heads and self.ssm_head_dim
                                     and self.ssm_state):
            raise ValueError("Mamba layers need ssm_heads, ssm_head_dim and "
                             "ssm_state")
        if KDA in kinds and not (self.kda_heads and self.kda_head_dim):
            raise ValueError("kda layers need kda_heads and kda_head_dim")
        if ((CONV in kinds or KDA in kinds or MAMBA in pattern)
                and (self.ring_attention_axis or self.ulysses_axis)):
            raise NotImplementedError(
                "conv, kda and Mamba layers run along a sequence that is "
                "whole on the device: not with sequence-parallel attention")
        latent = self.kv_lora_rank is not None
        if latent and not (self.qk_nope_head_dim and self.qk_rope_head_dim
                           and self.v_head_dim):
            raise ValueError(
                "kv_lora_rank (latent attention) needs qk_nope_head_dim, "
                "qk_rope_head_dim and v_head_dim, and q_lora_rank or None "
                "(the query straight from the input)")
        if latent and (self.ring_attention_axis or self.ulysses_axis
                       or set(kinds) - {FULL, KDA}):
            raise NotImplementedError(
                "latent attention with layer kinds or sequence-parallel "
                "attention")
        missing = [name for name, on in (
            ("kv_lora_rank", latent),
            ("q_lora_rank", self.q_lora_rank is not None),
            ("qk_nope_head_dim", bool(self.qk_nope_head_dim)),
            ("qk_rope_head_dim", bool(self.qk_rope_head_dim)),
            ("v_head_dim", bool(self.v_head_dim)),
            ("rope_interleave", self.rope_interleave),
            ("moe_shared_experts", bool(self.moe_shared_experts)),
            ("layer_pattern", bool(pattern)),
            ("learned_positions=False", not self.learned_positions),
            ("moe_routed_scale", self.moe_routed_scale != 1.0),
            ("mtp_modules", bool(self.mtp_modules)),
            ("mtp_loss_weight", bool(self.mtp_loss_weight)),
            ("layer_types", bool(kinds)),
            ("kda_heads", bool(self.kda_heads)),
            ("sliding_window", bool(self.sliding_window)),
            ("qk_norm", self.qk_norm),
            ("moe_experts_held", self.moe_experts_held is not None),
            ("moe_scoring='sigmoid'", self.moe_scoring == "sigmoid"),
            ("moe_expert_bias", self.moe_expert_bias),
            ("moe_router='dropless'", self.moe_router == "dropless"
             and self.num_experts > 0)) if on]
        if self.decode and missing:
            raise NotImplementedError(
                f"decode=True with {', '.join(missing)}: the kv cache keeps "
                "no window, no latent, no conv layer's last rows and no "
                "Mamba or KDA state, its incremental attention no layer "
                "kinds and no query/key norms, routing has no incremental "
                "form here and a prediction module no consumer (ROADMAP "
                "R1/R5)")


FULL, SLIDING, CONV, KDA = ("full_attention", "sliding_attention", "conv",
                            "kda")
LAYER_KINDS = (FULL, SLIDING, CONV, KDA)
MAMBA = "M"
PARTS = "M*E"         # `layer_pattern`: Mamba-2, attention, experts


def rope_inv_freq(head_dim, theta, yarn_factor=1.0, original_max=0,
                  beta_fast=32.0, beta_slow=1.0):
    """`theta ** (-2m / head_dim)` for m in [0, head_dim / 2); with
    `yarn_factor` > 1 the YaRN blend: pairs that turn more than `beta_fast`
    times over `original_max` positions keep their frequency, pairs that
    turn less than `beta_slow` times have it divided by the factor, a
    linear ramp between."""
    import math

    half = head_dim // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    if yarn_factor == 1.0:
        return freqs

    def pair_of(turns):
        return head_dim * math.log(original_max / (2 * math.pi * turns)) / (
            2 * math.log(theta))

    low = max(math.floor(pair_of(beta_fast)), 0)
    high = min(math.ceil(pair_of(beta_slow)), head_dim - 1)
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return (1.0 - ramp) * freqs + ramp * freqs / yarn_factor


def apply_rope(x, positions, theta=10000.0, inv_freq=None, factor=1.0,
               interleave=False):
    """Rotary position embedding over [..., S, H, D] (split-half pairing;
    `interleave`: pair m is lanes (2m, 2m+1), and the rotated pairs come
    back de-interleaved, every first lane and then every second, as the
    published latent-attention models do it: a query and a key rotated
    this way have the dot product the in-place rotation gives).

    `positions`: [S] (or [B, S]) absolute token positions; q·k after
    rotation depends only on relative position, so RoPE composes with
    sequence-parallel attention (rotation happens before the CP dispatch,
    on globally-indexed activations).  `inv_freq` [D/2] replaces
    `theta`'s frequencies (`rope_inv_freq`); `factor` multiplies cos and
    sin (YaRN's attention factor: the logits carry its square).
    """
    D = x.shape[-1]
    if D % 2:
        raise ValueError(f"head_dim={D} must be even for RoPE")
    half = D // 2
    freqs = rope_inv_freq(D, theta) if inv_freq is None else inv_freq
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., S, half]
    cos = jnp.cos(angles)[..., None, :]                        # [..., S, 1, half]
    sin = jnp.sin(angles)[..., None, :]
    if factor != 1.0:
        cos, sin = cos * factor, sin * factor
    x1, x2 = ((x[..., 0::2], x[..., 1::2]) if interleave
              else (x[..., :half], x[..., half:]))
    x1, x2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin,
                            x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


class QuantDense(nn.Module):
    """``nn.Dense`` drop-in whose kernel may arrive QUANTIZED.

    Param names, shapes and initializers match ``nn.Dense`` exactly
    ("kernel" [+ "bias"], lecun_normal f32 masters), so checkpoints,
    the name-matched sharding rules (parallel/sharding.py), LoRA banks
    and the init RNG stream are unchanged — a float tree behaves
    bit-for-bit like ``nn.Dense``.  At apply time a kernel that is a
    quantize.py leaf (int8 ``{"q", "scale"}`` dict or ``Int4Weight``)
    is consumed in its quantized form: ``impl="kernel"`` routes through
    ``ops.quant_matmul`` (weight tiles dequantize in VMEM — taken when
    the TPU pallas extension imported and no mesh is ambient, since a
    pallas custom call cannot be partitioned by GSPMD); otherwise the
    leaf dequantizes inline under the trace (``q.astype(dtype) *
    scale``, for XLA to fuse into the consuming matmul — the
    pre-kernel behavior, kept as the parity oracle and the sharded
    fallback, mirroring ``paged_attn_impl``).

    The quantized kernel is fetched via ``get_variable`` rather than
    ``self.param`` — flax shape-validates declared params against their
    stored value, and a quantized leaf is a container, not an array.
    """
    features: int
    use_bias: bool = False
    dtype: Optional[Any] = None
    impl: str = "kernel"

    @nn.compact
    def __call__(self, x):
        from tensorflowonspark_tpu import quantize

        if self.impl not in ("kernel", "dequant"):
            raise ValueError(f"quant_matmul_impl={self.impl!r} not in "
                             "('kernel', 'dequant')")
        qleaf = None
        if (not self.is_initializing()
                and self.has_variable("params", "kernel")):
            stored = self.get_variable("params", "kernel")
            if quantize.is_quantized_leaf(stored):
                qleaf = stored
        kernel = None
        if qleaf is None:
            kernel = self.param("kernel", nn.initializers.lecun_normal(),
                                (x.shape[-1], self.features), jnp.float32)
        bias = (self.param("bias", nn.initializers.zeros,
                           (self.features,), jnp.float32)
                if self.use_bias else None)
        if qleaf is None:  # float kernel: exact nn.Dense semantics
            x, kernel, bias = nn.dtypes.promote_dtype(
                x, kernel, bias, dtype=self.dtype)
            y = jax.lax.dot_general(
                x, kernel, (((x.ndim - 1,), (0,)), ((), ())))
        else:
            # the dtype promote_dtype would have picked for a float tree
            dtype = (jnp.promote_types(jnp.result_type(x), jnp.float32)
                     if self.dtype is None else jnp.dtype(self.dtype))
            x = x.astype(dtype)
            if self.impl == "kernel" and _ambient_mesh() is None:
                y = quant_matmul(x, qleaf)
            else:
                w = quantize.dequantize_leaf(qleaf, dtype)
                y = jax.lax.dot_general(
                    x, w, (((x.ndim - 1,), (0,)), ((), ())))
            bias = None if bias is None else bias.astype(dtype)
        if bias is not None:
            y = y + jnp.reshape(bias, (1,) * (y.ndim - 1) + (-1,))
        return y


class Attention(nn.Module):
    cfg: TransformerConfig
    layer_type: str = FULL        # this layer's kind (cfg.layer_types[i])

    def _proj(self, name, features, x, dtype):
        """One attention projection, with an optional PER-ROW LoRA delta.

        When the caller passes a ``lora`` variable collection (multi-
        adapter serving, serve.ContinuousBatcher), this module's subtree
        holds banks ``{name}_a [L, d_in, r]`` / ``{name}_b [L, r, d_out]``
        (scale pre-folded into b) plus ``ids [B]`` mapping each batch row
        to its bank index; row ``n`` computes ``x_n @ W + (x_n @
        A[ids_n]) @ B[ids_n]`` — N tenants share one batched step
        (S-LoRA-style; net-new beyond the reference).  Index 0 is the
        null adapter (all-zero b), so un-adapted rows are EXACTLY the
        base model.  Without the collection this is a plain Dense."""
        y = QuantDense(features, use_bias=self.cfg.use_bias, name=name,
                       dtype=dtype, impl=self.cfg.quant_matmul_impl)(x)
        if (not self.is_initializing()
                and self.has_variable("lora", f"{name}_a")):
            a = self.get_variable("lora", f"{name}_a")
            b = self.get_variable("lora", f"{name}_b")
            ids = self.get_variable("lora", "ids")
            a = jnp.take(a, ids, axis=0)            # [B, d_in, r]
            b = jnp.take(b, ids, axis=0)            # [B, r, d_out]
            # S is arbitrary: 1 for plain decode, k for a speculative
            # verify block — per-row adapters apply identically at any
            # width, which is what lets LoRA compose with speculation
            delta = jnp.einsum("bsd,bdr,bro->bso", x.astype(jnp.float32),
                               a.astype(jnp.float32), b.astype(jnp.float32))
            y = y + delta.astype(y.dtype)
        return y

    @nn.compact
    def __call__(self, x, mask=None):
        cfg = self.cfg
        if cfg.kv_lora_rank is not None:
            return self._latent(x, mask)
        trace.counters().inc("mixer.calls.attention")   # once a traced call
        dtype = jnp.dtype(cfg.dtype)
        head_dim = cfg.head_dim or cfg.d_model // cfg.n_heads
        sliding = self.layer_type == SLIDING
        window = cfg.sliding_window if sliding else None
        n_kv = cfg.n_heads if cfg.n_kv_heads is None else cfg.n_kv_heads
        if n_kv < 1:
            raise ValueError(f"n_kv_heads={n_kv} must be >= 1 (or None)")
        if cfg.n_heads % n_kv:
            raise ValueError(
                f"n_heads={cfg.n_heads} must be divisible by "
                f"n_kv_heads={n_kv}")
        q = self._proj("query", cfg.n_heads * head_dim, x, dtype)
        k = self._proj("key", n_kv * head_dim, x, dtype)
        v = self._proj("value", n_kv * head_dim, x, dtype)
        B, S = x.shape[0], x.shape[1]
        q = q.reshape(B, S, cfg.n_heads, head_dim)
        k = k.reshape(B, S, n_kv, head_dim)
        v = v.reshape(B, S, n_kv, head_dim)
        if cfg.qk_norm:     # per head, each with its own scale of head_dim
            q = nn.RMSNorm(name="q_norm", dtype=jnp.float32,
                           epsilon=cfg.ln_eps)(q).astype(dtype)
            k = nn.RMSNorm(name="k_norm", dtype=jnp.float32,
                           epsilon=cfg.ln_eps)(k).astype(dtype)
        decoding = cfg.decode and (
            self.has_variable("cache", "cached_key")
            or self.has_variable("cache", "pages_key"))
        cache_index = None
        if decoding:
            cache_index = self.get_variable("cache", "cache_index")

        if cfg.rope:
            pos = jnp.arange(S)
            if decoding:
                if cfg.decode_slots:     # per-row positions: [B, S]
                    pos = cache_index[:, None] + pos[None, :]
                else:
                    pos = pos + cache_index  # absolute positions of the new
                # tokens; cached keys were rotated at their own positions
            cp_axis = cfg.ring_attention_axis or cfg.ulysses_axis
            if cp_axis:
                # under an enclosing shard_map the activations are the LOCAL
                # sequence shard; rotate with global token positions
                if cp_axis in _bound_axes(_ambient_mesh()):
                    pos = pos + jax.lax.axis_index(cp_axis) * S
            # rotary parameters by layer kind: plain on the window layers,
            # YaRN with its attention factor on the full ones (at its
            # defaults the plain `rope_theta` frequencies, factor 1)
            if sliding:
                rope = dict(inv_freq=rope_inv_freq(
                    head_dim, cfg.rope_local_theta or cfg.rope_theta))
            else:
                rope = dict(factor=cfg.rope_attention_factor,
                            inv_freq=rope_inv_freq(
                    head_dim, cfg.rope_theta, cfg.rope_yarn_factor,
                    cfg.rope_yarn_original_max, cfg.rope_yarn_beta_fast,
                    cfg.rope_yarn_beta_slow))
            q = apply_rope(q, pos, **rope)
            k = apply_rope(k, pos, **rope)

        flash = _flash_wanted(cfg, mask)
        if cfg.ring_attention_axis and cfg.ulysses_axis:
            raise ValueError(
                "ring_attention_axis and ulysses_axis are mutually "
                "exclusive context-parallel strategies")
        if cfg.decode:
            if cfg.ring_attention_axis or cfg.ulysses_axis:
                raise NotImplementedError(
                    "decode mode with sequence-parallel attention is not "
                    "supported; decode on a tp/dp mesh instead")
            if not cfg.causal:
                raise NotImplementedError(
                    "decode mode is autoregressive (causal) generation; "
                    "causal=False has no incremental form")
            out = self._decode_attention(q, k, v, mask)
        elif cfg.ring_attention_axis or cfg.ulysses_axis:
            if window:
                raise NotImplementedError(
                    "sliding_attention layers are not supported with "
                    "sequence-parallel attention")
            if mask is not None:
                raise NotImplementedError(
                    "key-padding masks are not supported with "
                    "sequence-parallel attention; pad/pack sequences to "
                    "full length, or unset ring_attention_axis/"
                    "ulysses_axis to use non-sequence-parallel attention")
            # GQA kv stay NARROW through the CP collectives (the bandwidth
            # win: ring ppermutes / ulysses all-to-alls move n_kv/n_heads of
            # the bytes); the local cores broadcast to full heads on-device
            out = _seqpar_dispatch(q, k, v, cfg)
        else:
            if flash:
                # GQA-native kernel: narrow k/v go straight in (no
                # repeated kv in HBM, dk/dv come back narrow)
                out = _flash_dispatch(q, k, v, cfg, window)
            else:
                _count_remat(cfg, False)
                # dense path: broadcast back to full heads for the
                # attention cores (the narrow projection already saved
                # the params + kv-cache HBM; XLA fuses the repeat)
                k, v = _kv_repeat(q, k, v)
                if mask is not None and cfg.attention_impl == "flash":
                    # arbitrary key-padding masks aren't implemented in the
                    # pallas kernel; an explicit 'flash' request must not
                    # silently lose its O(S) memory promise
                    logging.getLogger(__name__).warning(
                        "attention_impl='flash' with a key-padding mask "
                        "falls back to dense O(S^2) attention")
                out = dot_product_attention(q, k, v, causal=cfg.causal,
                                            mask=mask, window=window)
        out = out.reshape(B, S, cfg.n_heads * head_dim)
        return self._proj("out", cfg.d_model, out, dtype)

    def _latent(self, x, mask):
        """Latent attention (MLA; `TransformerConfig.kv_lora_rank`).  On
        the chip the scores and the values go through
        `ops.flash_attention.flash_attention_latent`, which reads the one
        shared key a token as it is (rotated under `rope`, as it comes
        without: Kimi Linear's MLA has no positions); under a key-padding
        mask, a mesh or `attention_impl='dense'` the dense core computes
        the same over the key written out a head."""
        cfg = self.cfg
        trace.counters().inc("mixer.calls.latent")      # once a traced call
        dtype = jnp.dtype(cfg.dtype)
        H, rank = cfg.n_heads, cfg.kv_lora_rank
        dn, dr, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim)
        B, S = x.shape[0], x.shape[1]

        def norm(name, h):
            return nn.RMSNorm(name=name, dtype=jnp.float32,
                              epsilon=cfg.ln_eps)(h).astype(dtype)

        if cfg.q_lora_rank is None:
            q = self._proj("query", H * (dn + dr), x, dtype)
        else:
            q = self._proj("q_b", H * (dn + dr), norm(
                "q_a_norm", self._proj("q_a", cfg.q_lora_rank, x, dtype)),
                dtype)
        q = q.reshape(B, S, H, dn + dr)
        kv_a = self._proj("kv_a", rank + dr, x, dtype)
        ckv = norm("kv_a_norm", kv_a[..., :rank])
        kv = self._proj("kv_b", H * (dn + dv), ckv, dtype).reshape(
            B, S, H, dn + dv)
        k_nope, v = kv[..., :dn], kv[..., dn:]
        if cfg.rope:
            rope = dict(inv_freq=rope_inv_freq(dr, cfg.rope_theta),
                        interleave=cfg.rope_interleave)
            pos = jnp.arange(S)
            q = jnp.concatenate(
                [q[..., :dn], apply_rope(q[..., dn:], pos, **rope)], axis=-1)
            k_rope = apply_rope(kv_a[:, :, None, rank:], pos, **rope)[:, :, 0]
        else:                   # no positions: the lanes as they come
            k_rope = kv_a[..., rank:]
        flash = _flash_wanted(cfg, mask) and _ambient_mesh() is None
        _count_remat(cfg, flash)
        if flash:
            from tensorflowonspark_tpu.ops.flash_attention import (
                flash_attention_latent)
            out = flash_attention_latent(q, k_nope, k_rope, v,
                                         causal=cfg.causal)
        else:
            k = jnp.concatenate([k_nope, jnp.broadcast_to(
                k_rope[:, :, None, :], (B, S, H, dr))], axis=-1)
            out = dot_product_attention(q, k, v, causal=cfg.causal,
                                        mask=mask)
        return self._proj("out", cfg.d_model, out.reshape(B, S, H * dv),
                          dtype)

    def _decode_attention(self, q, k, v, mask):
        """Incremental attention against the kv cache.

        The cache holds max_seq_len slots of the NARROW n_kv_heads k/v (the
        GQA memory win); new tokens are written at cache_index via
        dynamic_update_slice — static shapes, so one compiled step serves
        the whole generation.  Works uniformly for prefill (S>1) and
        single-token steps: key j is visible to query s iff j <= index + s.

        CONTRACT: the caller must keep total decoded length within
        cfg.max_seq_len (models/decode.generate enforces this).  Past it,
        dynamic_update_slice clamps the write index and results are
        silently wrong — a data-dependent bound cannot raise under jit.
        """
        cfg = self.cfg
        if mask is not None:
            raise NotImplementedError(
                "key-padding masks are not supported in decode mode")
        if cfg.kv_dtype not in ("auto", "int8"):
            # one check for BOTH cache layouts (the paged body below is
            # only reachable from here)
            raise ValueError(
                f"kv_dtype={cfg.kv_dtype!r} not in ('auto', 'int8')")
        B, S, n_kv, Dh = k.shape
        L = cfg.max_seq_len
        dtype = k.dtype
        if cfg.kv_page_size:
            if not cfg.decode_slots:
                raise ValueError("kv_page_size requires decode_slots=True "
                                 "(pages are a serving-slot feature)")
            if L % cfg.kv_page_size:
                raise ValueError(
                    f"max_seq_len={L} must be a multiple of "
                    f"kv_page_size={cfg.kv_page_size}")
            if cfg.kv_pages < 1:
                raise ValueError("kv_page_size > 0 requires kv_pages >= 1")
            if cfg.paged_attn_impl not in ("kernel", "einsum"):
                raise ValueError(
                    f"paged_attn_impl={cfg.paged_attn_impl!r} not in "
                    "('kernel', 'einsum')")
            if cfg.paged_prefill_impl not in ("kernel", "blend"):
                raise ValueError(
                    f"paged_prefill_impl={cfg.paged_prefill_impl!r} not "
                    "in ('kernel', 'blend')")
            return _paged_attention_body(self, q, k, v)
        quant = cfg.kv_dtype == "int8"
        store = jnp.int8 if quant else dtype
        ck = self.variable("cache", "cached_key", jnp.zeros,
                           (B, L, n_kv, Dh), store)
        cv = self.variable("cache", "cached_value", jnp.zeros,
                           (B, L, n_kv, Dh), store)
        if quant:
            # per-(token, head) scales of the int8 kv store
            cks = self.variable("cache", "cached_key_scale", jnp.zeros,
                                (B, L, n_kv), jnp.float32)
            cvs = self.variable("cache", "cached_value_scale", jnp.zeros,
                                (B, L, n_kv), jnp.float32)
        ci = self.variable("cache", "cache_index",
                           lambda: jnp.zeros(
                               (B,) if cfg.decode_slots else (), jnp.int32))
        if self.is_initializing():
            kf, vf = _kv_repeat(q, k, v)
            return dot_product_attention(q, kf, vf, causal=cfg.causal)
        idx = ci.value
        if quant:
            k_st, k_sc = _kv_quantize(k)
            v_st, v_sc = _kv_quantize(v)
        else:
            k_st, v_st = k.astype(dtype), v.astype(dtype)
        if cfg.decode_slots:
            # per-row write positions (continuous batching: every row is
            # an independent slot at its own sequence position).  The
            # write is a one-hot masked blend, NOT a batched scatter: a
            # vmapped dynamic_update_slice lowers to scatter, which
            # measured ~4x slower per decode pass on TPU; the blend is
            # pure elementwise+reduce over the cache (HBM-bandwidth
            # bound, XLA-fusable) and costs ~1 ms at serving shapes.
            pos = idx[:, None] + jnp.arange(S)[None, :]        # [B, S]
            onehot = (jnp.arange(L)[None, None, :]
                      == pos[:, :, None])                      # [B, S, L]
            write_mask = onehot.any(axis=1)[:, :, None, None]  # [B, L,1,1]
            # ONE payload blend for both storages: int8 payloads blend
            # at the ACTIVATION dtype (±127 is exact in bf16/f32; a
            # wider blend would double the write traffic that dominates
            # this op) and the trailing
            # astype(store) is a no-op when store == dtype
            oh = onehot.astype(dtype)
            ck.value = jnp.where(write_mask, jnp.einsum(
                "bsl,bshd->blhd", oh,
                k_st.astype(dtype)).astype(store), ck.value)
            cv.value = jnp.where(write_mask, jnp.einsum(
                "bsl,bshd->blhd", oh,
                v_st.astype(dtype)).astype(store), cv.value)
            if quant:                 # the (small) scales blend in f32
                ohf = onehot.astype(jnp.float32)
                smask = write_mask[..., 0]                     # [B, L, 1]
                cks.value = jnp.where(smask, jnp.einsum(
                    "bsl,bsh->blh", ohf, k_sc), cks.value)
                cvs.value = jnp.where(smask, jnp.einsum(
                    "bsl,bsh->blh", ohf, v_sc), cvs.value)
        else:
            ck.value = jax.lax.dynamic_update_slice(
                ck.value, k_st, (0, idx, 0, 0))
            cv.value = jax.lax.dynamic_update_slice(
                cv.value, v_st, (0, idx, 0, 0))
            if quant:
                cks.value = jax.lax.dynamic_update_slice(
                    cks.value, k_sc, (0, idx, 0))
                cvs.value = jax.lax.dynamic_update_slice(
                    cvs.value, v_sc, (0, idx, 0))
        ci.value = idx + S
        if quant:
            kf, vf = _kv_repeat(q,
                                _kv_dequantize(ck.value, cks.value, dtype),
                                _kv_dequantize(cv.value, cvs.value, dtype))
        else:
            kf, vf = _kv_repeat(q, ck.value, cv.value)
        scale = 1.0 / jnp.sqrt(jnp.asarray(Dh, jnp.float32))
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, kf).astype(jnp.float32)
        logits = logits * scale
        if cfg.decode_slots:
            visible = (jnp.arange(L)[None, None, :]
                       <= (idx[:, None, None]
                           + jnp.arange(S)[None, :, None]))   # [B, S, L]
            logits = jnp.where(visible[:, None], logits, -1e30)
        else:
            visible = (jnp.arange(L)[None, :]
                       <= (idx + jnp.arange(S))[:, None])     # [S, L]
            logits = jnp.where(visible[None, None], logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, vf)


def causal_conv(g, taps, bias=None):
    """A causal depthwise filter over `g` [B, S, C]: `taps` [C, L], one
    tap vector a channel, the last tap on the position itself, zeros left
    of the row's start, then `bias` [C] where given.  L shifted
    multiply-adds in `g`'s type: a depthwise convolution of C groups is no
    MXU work, and XLA fuses the sum with what surrounds it."""
    S, L = g.shape[1], taps.shape[1]
    s = g * taps[:, L - 1]
    for back in range(1, min(L, S)):     # position t reads t - back
        s = s + jnp.pad(g[:, :S - back],
                        ((0, 0), (back, 0), (0, 0))) * taps[:, L - 1 - back]
    return s if bias is None else s + bias


class ShortConv(nn.Module):
    """Gated short convolution, a conv layer's sequence mixer (LFM2):
    `[b, c, z] = split3(in_proj(x))`, `g = b * z`, a causal depthwise
    filter of `conv_kernel` taps over `g` (`taps` [D, L], `causal_conv`),
    `out_proj(c * s)`."""
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, mask=None):
        cfg = self.cfg
        if mask is not None:
            raise NotImplementedError(
                "key-padding masks are not supported in conv layers")
        trace.counters().inc("mixer.calls.conv")        # once a traced call
        dtype = jnp.dtype(cfg.dtype)
        D, L = cfg.d_model, cfg.conv_kernel

        def proj(name, features, h):
            return QuantDense(features, use_bias=cfg.use_bias, name=name,
                              dtype=dtype, impl=cfg.quant_matmul_impl)(h)

        b, c, z = jnp.split(proj("in_proj", 3 * D, x), 3, axis=-1)
        taps = self.param("taps", nn.initializers.lecun_normal(),
                          (D, L), jnp.float32).astype(dtype)
        return proj("out_proj", D, c * causal_conv(b * z, taps))


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """Mamba-2's: the inverse softplus of steps drawn log-uniform over
    [0.001, 0.1], floored at 1e-4."""
    dt = jnp.maximum(jnp.exp(jax.random.uniform(
        key, shape, dtype, math.log(1e-3), math.log(1e-1))), 1e-4)
    return dt + jnp.log(-jnp.expm1(-dt))


def _a_log_init(key, shape, dtype=jnp.float32):
    """Mamba-2's: A = -(1, 2, ..., heads)."""
    return jnp.log(jnp.arange(1, shape[0] + 1, dtype=dtype))


class Mamba2Mixer(nn.Module):
    """Mamba-2 mixer (Dao & Gu, arXiv:2405.21060; Nemotron-H's layer
    `M`): `[z | xBC | dt] = in_proj(x)`; `xBC = silu(causal_conv(xBC) +
    bias)` (`conv_kernel` taps); `[x | B | C] = xBC`, x as `ssm_heads`
    heads of `ssm_head_dim`, B and C as `ssm_groups` groups of
    `ssm_state`; `dt = softplus(dt + dt_bias)`, `A = -exp(A_log)`;
    `y = SSD(x, dt, A, B, C) + D x` (`ops.ssd`: head h reads group
    h // (heads / groups)); `y = RMSNorm(y * silu(z))` over groups of
    heads x head_dim / groups lanes, one scale of heads x head_dim;
    `out_proj(y)`.  The SSD scan and the skip, gate and norm
    (`ops.gate_norm`) take their Pallas kernels where `attention_impl`
    asks for them (`auto` on the chip) and no mesh is set, the same
    arithmetic in `jax.numpy` elsewhere."""
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, mask=None):
        from tensorflowonspark_tpu.ops.gate_norm import gate_norm
        from tensorflowonspark_tpu.ops.ssd import ssd

        cfg = self.cfg
        if mask is not None:
            raise NotImplementedError(
                "key-padding masks are not supported in Mamba layers")
        trace.counters().inc("mixer.calls.mamba")       # once a traced call
        dtype = jnp.dtype(cfg.dtype)
        H, P, G, N = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups,
                      cfg.ssm_state)
        inner, width = H * P, H * P + 2 * G * N
        nb, S, _ = x.shape
        f32 = jnp.float32

        def proj(name, features, h):
            return QuantDense(features, use_bias=cfg.use_bias, name=name,
                              dtype=dtype, impl=cfg.quant_matmul_impl)(h)

        zxbcdt = proj("in_proj", inner + width + H, x)
        _, xbc, dt = jnp.split(zxbcdt, [inner, inner + width], axis=-1)
        taps = self.param("conv_taps", nn.initializers.lecun_normal(),
                          (width, cfg.conv_kernel), f32).astype(dtype)
        bias = self.param("conv_bias", nn.initializers.zeros, (width,),
                          f32).astype(dtype)
        dt_bias = self.param("dt_bias", _dt_bias_init, (H,), f32)
        a_log = self.param("A_log", _a_log_init, (H,), f32)
        skip = self.param("D", nn.initializers.ones, (H,), f32)
        scale = self.param("norm", nn.initializers.ones, (inner,), f32)
        with jax.named_scope("conv"):
            xbc = nn.silu(causal_conv(xbc, taps, bias))
        xs, b, c = jnp.split(xbc, [inner, inner + G * N], axis=-1)
        kernel = _flash_wanted(cfg, mask) and _ambient_mesh() is None
        with jax.named_scope("ssd"):
            y = ssd(xs.reshape(nb, S, H, P),
                    jax.nn.softplus(dt.astype(f32) + dt_bias),
                    -jnp.exp(a_log), b.reshape(nb, S, G, N),
                    c.reshape(nb, S, G, N), kernel=kernel)
        with jax.named_scope("gate_norm"):       # z: in_proj's first lanes
            y = gate_norm(y.reshape(nb, S, inner), xs, zxbcdt, skip, scale,
                          G, cfg.ln_eps, kernel=kernel)
        return proj("out_proj", cfg.d_model, y)


def _kda_a_log_init(key, shape, dtype=jnp.float32):
    """Kimi Linear's: A drawn uniform over [1, 16], a value a head."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


class KimiDeltaMixer(nn.Module):
    """Kimi Delta Attention (Kimi Linear, arXiv:2510.26692), a `kda`
    layer's sequence mixer, `kda_heads` heads of `kda_head_dim` (P) lanes:
    `q, k, v = silu(causal_conv(x W))` each (`conv_kernel` taps, no bias),
    q and k L2-normalised a head (eps 1e-6); `beta = sigmoid(x W_b)` a
    head; the decay a key channel, `g = -exp(A_log) softplus(f_b(f_a(x)) +
    dt_bias)` (`f_a` to P lanes); `o = KDA(q, k, v, g, beta)`;
    `o = RMSNorm(o) * w * sigmoid(g_b(g_a(x)))` a head (`w` of P, `g_b`
    with a bias); `o_proj(o)`.  `ops.kda` takes q, k, v and g as the convs
    and the decay leave them, `[B, S, H * P]`, and beta `[B, H, S]`, and
    gives `RMSNorm(o) * w` in the same layout: the L2 norms, beta's scaling
    and the norm a head are its own, so the mixer makes no view a head.
    The convs' output goes to it in float32, as XLA computes it: rounded
    to bf16 before the norms, it took the training further from float32.
    The recurrence takes its Pallas kernels where `attention_impl` asks
    for them (`auto` on the chip) and no mesh is set, the same chunked
    algorithm in `jax.numpy` elsewhere."""
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, mask=None):
        from tensorflowonspark_tpu.ops.kda import kda

        cfg = self.cfg
        if mask is not None:
            raise NotImplementedError(
                "key-padding masks are not supported in kda layers")
        trace.counters().inc("mixer.calls.kda")         # once a traced call
        dtype = jnp.dtype(cfg.dtype)
        H, P = cfg.kda_heads, cfg.kda_head_dim
        inner = H * P
        f32 = jnp.float32

        def proj(name, features, h, bias=False):
            return QuantDense(features, use_bias=bias, name=name,
                              dtype=dtype, impl=cfg.quant_matmul_impl)(h)

        qkv = [proj(f"{n}_proj", inner, x) for n in "qkv"]
        taps = [self.param(f"{n}_conv", nn.initializers.lecun_normal(),
                           (inner, cfg.conv_kernel), f32).astype(dtype)
                for n in "qkv"]
        with jax.named_scope("conv"):
            q, k, v = (nn.silu(causal_conv(h, w)).astype(f32)
                       for h, w in zip(qkv, taps))
        with jax.named_scope("decay"):
            a_log = self.param("A_log", _kda_a_log_init, (H,), f32)
            dt_bias = self.param("dt_bias", _dt_bias_init, (inner,), f32)
            g = -jnp.repeat(jnp.exp(a_log), P) * jax.nn.softplus(
                proj("f_b", inner, proj("f_a", P, x)).astype(f32) + dt_bias)
            beta = jax.nn.sigmoid(proj("b_proj", H, x).astype(f32)
                                  ).transpose(0, 2, 1)          # [B, H, S]
        w = self.param("out_norm", nn.initializers.ones, (P,), f32)
        kernel = _flash_wanted(cfg, mask) and _ambient_mesh() is None
        with jax.named_scope("scan"):
            o = kda(q, k, v, g, beta, w, cfg.ln_eps, kernel=kernel,
                    dtype=dtype)
        with jax.named_scope("out_norm"):
            gate = proj("g_b", inner, proj("g_a", P, x), bias=True)
            o = (o * jax.nn.sigmoid(gate.astype(f32))).astype(dtype)
        return proj("o_proj", cfg.d_model, o)


def _kv_quantize(x):
    """[..., Dh] -> (int8 payload, f32 scale [...]): symmetric per-vector
    quantization over head_dim — the decode kv-cache's int8 storage form
    (`TransformerConfig.kv_dtype`).  Scale overhead is 4/Dh bytes per
    int8 byte (~3% at Dh=128)."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q8 = jnp.clip(jnp.round(xf / scale[..., None]), -127,
                  127).astype(jnp.int8)
    return q8, scale


def _kv_dequantize(q8, scale, dtype):
    """Rebuild compute-dtype kv from the int8 store; under jit XLA fuses
    this into the attention einsum's operand read (the full-width cache
    never materializes in HBM — the same fusion argument as weight-only
    int8, decode._params_view)."""
    return (q8.astype(jnp.float32) * scale[..., None]).astype(dtype)


def _paged_attention_body(attn_self, q, k, v):
    """Paged continuous-batching decode attention (vLLM-style layout,
    blend-write discipline).

    kv lives in a SHARED pool ``pages_key/pages_value [kv_pages,
    page, n_kv, Dh]``; each row owns the pool pages its per-row
    ``page_table [B, table_pages]`` names (the serving layer allocates
    them from a free list at admission and returns them at retirement —
    serve.ContinuousBatcher).  The table starts ``cfg.kv_table_pages``
    wide (0 = the full ``max_seq_len // page`` width) and the serving
    layer widens it geometrically as rows outgrow it; the width read
    below always comes from the leaf, so every pow2 width is one trace.  Prefill chunks (S > 1) default to the
    Pallas paged-prefill kernels (``cfg.paged_prefill_impl ==
    "kernel"``, ops/paged_prefill.py): page-granular in-place pool
    stores + one online softmax over [occupied context pages || chunk],
    O(chunk) traffic with the blend below kept as the parity reference
    and the mesh fallback.  Decode steps (S == 1) and the "blend"
    impl follow the slot-cache rule (one-hot masked blend, never a
    scatter).
    Reads go through ``cfg.paged_attn_impl``: "kernel" (the default)
    runs the Pallas flash-decode kernel, which walks each row's page
    table in place and touches only its OCCUPIED pages — per-token read
    bytes scale with the row's true length, not max_seq (see
    docs/source/performance.rst for the bytes-per-token math);
    "einsum" gathers each row's pages back into the logical
    [B, L, n_kv, Dh] view and runs a full-length masked softmax —
    O(max_seq)/token, kept as the parity reference and as the fallback
    under an active mesh (pallas is a custom call GSPMD cannot
    partition — the _flash_dispatch discipline).

    CONTRACT: a row's table must name valid pool pages for every
    position it will touch before those positions are written (admission
    allocates ceil(need/page) up front), and every OTHER entry —
    unallocated tails, retired rows — must alias a caller-reserved
    garbage SINK page: tail blocks DO receive writes (bucket-padded
    prefill overshoot, the post-retirement garbage steps of a freed
    row), so a tail defaulting to a real page would corrupt its owner.
    serve.ContinuousBatcher reserves pool page `kv_pages` as the sink.
    Reads of sink garbage are hidden by the visibility mask for every
    live row.
    """
    cfg = attn_self.cfg
    B, S, n_kv, Dh = k.shape
    P, NP = cfg.kv_page_size, cfg.kv_pages
    cap_pages = cfg.max_seq_len // P
    init_pages = (min(cfg.kv_table_pages, cap_pages)
                  if cfg.kv_table_pages else cap_pages)
    dtype = k.dtype
    quant = cfg.kv_dtype == "int8"    # validated by _decode_attention,
    store = jnp.int8 if quant else dtype   # the sole caller
    pk = attn_self.variable("cache", "pages_key", jnp.zeros,
                            (NP, P, n_kv, Dh), store)
    pv = attn_self.variable("cache", "pages_value", jnp.zeros,
                            (NP, P, n_kv, Dh), store)
    if quant:
        pks = attn_self.variable("cache", "pages_key_scale", jnp.zeros,
                                 (NP, P, n_kv), jnp.float32)
        pvs = attn_self.variable("cache", "pages_value_scale", jnp.zeros,
                                 (NP, P, n_kv), jnp.float32)
    table = attn_self.variable(
        "cache", "page_table",
        lambda: jnp.zeros((B, init_pages), jnp.int32))
    ci = attn_self.variable("cache", "cache_index",
                            lambda: jnp.zeros((B,), jnp.int32))
    if attn_self.is_initializing():
        kf, vf = _kv_repeat(q, k, v)
        return dot_product_attention(q, kf, vf, causal=cfg.causal)
    # The live table width comes from the LEAF, never the config: the
    # serving layer grows tables in pow2 steps as long prompts land
    # (decode._jitted_grow_page_table splices sink-padded tails on), and
    # each width is one fresh trace of this body.  The Pallas kernels
    # below are already width-polymorphic (ops/paged_attention.py and
    # ops/paged_prefill.py read `table.shape[1]`).
    max_pages = table.value.shape[1]
    L = max_pages * P
    idx = ci.value
    if (S > 1 and cfg.paged_prefill_impl == "kernel"
            and _ambient_mesh() is None):
        # Pallas paged-prefill kernels (ops/paged_prefill.py): the
        # chunk's k/v store page-granular IN PLACE into the pool
        # (int8 requantization fused, bit-identical to the blend's
        # bytes), then one online softmax over [occupied context pages
        # || chunk] — per-chunk traffic scales with the chunk, never
        # the pool, and no dense [B, max_seq] kv view exists.  S == 1
        # decode keeps the blend write + flash-decode read below
        # (split-K pays off there; a one-token page store does not).
        out, new_pools = paged_prefill(
            q, k, v, pk.value, pv.value, table.value, idx,
            key_scales=pks.value if quant else None,
            value_scales=pvs.value if quant else None)
        pk.value, pv.value = new_pools[0], new_pools[1]
        if quant:
            pks.value, pvs.value = new_pools[2], new_pools[3]
        ci.value = idx + S
        return out
    pos = idx[:, None] + jnp.arange(S)[None, :]              # [B, S]
    block = jnp.clip(pos // P, 0, max_pages - 1)
    phys = jnp.take_along_axis(table.value, block, axis=1)   # [B, S]
    # int8 payloads blend at the ACTIVATION dtype (±127 is exact in
    # bf16/f32; a wider blend would double the write traffic that
    # dominates this op) and store back narrow; scales blend in f32
    oh_p = (jnp.arange(NP)[None, None, :]
            == phys[:, :, None]).astype(dtype)               # [B, S, NP]
    oh_o = (jnp.arange(P)[None, None, :]
            == (pos % P)[:, :, None]).astype(dtype)          # [B, S, P]
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
             > 0)[:, :, None, None]                          # [NP, P, 1, 1]
    pk.value = jnp.where(wmask, upd_k.astype(store), pk.value)
    pv.value = jnp.where(wmask, upd_v.astype(store), pv.value)
    if quant:
        smask = wmask[..., 0]                                # [NP, P, 1]
        pks.value = jnp.where(smask, jnp.einsum(
            "bsn,bso,bsh->noh", oh_p.astype(jnp.float32),
            oh_o.astype(jnp.float32), k_sc), pks.value)
        pvs.value = jnp.where(smask, jnp.einsum(
            "bsn,bso,bsh->noh", oh_p.astype(jnp.float32),
            oh_o.astype(jnp.float32), v_sc), pvs.value)
    ci.value = idx + S
    if cfg.paged_attn_impl == "kernel" and _ambient_mesh() is None:
        # in-place page walk: lengths = the post-write cache_index (the
        # kernel derives the visibility rule j <= idx + s from it)
        return paged_attention(
            q, pk.value, pv.value, table.value, idx + S,
            key_scales=pks.value if quant else None,
            value_scales=pvs.value if quant else None)
    # reference read: each row's logical kv view, gathered from its pages
    kb = jnp.take(pk.value, table.value, axis=0)  # [B, mp, P, n_kv, Dh]
    vb = jnp.take(pv.value, table.value, axis=0)
    if quant:
        kb = _kv_dequantize(kb, jnp.take(pks.value, table.value, axis=0),
                            dtype)
        vb = _kv_dequantize(vb, jnp.take(pvs.value, table.value, axis=0),
                            dtype)
    kf, vf = _kv_repeat(q, kb.reshape(B, L, n_kv, Dh),
                        vb.reshape(B, L, n_kv, Dh))
    scale = 1.0 / jnp.sqrt(jnp.asarray(Dh, jnp.float32))
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, kf).astype(jnp.float32)
    logits = logits * scale
    visible = (jnp.arange(L)[None, None, :]
               <= (idx[:, None, None]
                   + jnp.arange(S)[None, :, None]))          # [B, S, L]
    logits = jnp.where(visible[:, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, vf)


def _flash_wanted(cfg, mask):
    """Whether `attention_impl` asks for the Pallas kernels here: `flash`,
    or `auto` on the chip; never under a key-padding mask."""
    if cfg.attention_impl not in ("auto", "flash", "dense"):
        raise ValueError(
            f"attention_impl={cfg.attention_impl!r} not in "
            "('auto', 'flash', 'dense')")
    return mask is None and (cfg.attention_impl == "flash" or (
        cfg.attention_impl == "auto" and jax.default_backend() == "tpu"))


def _count_remat(cfg, kernels):
    """Under `remat`, once a traced mixer: `remat.attention.saved` where it
    went to the Pallas kernels, whose output and row statistics the block's
    policy keeps (`remat_block`), `remat.attention.rerun` where it took the
    dense core, which the backward pass computes again.  The
    sequence-parallel mixers (ring, Ulysses) count as neither."""
    if cfg.remat:
        trace.counters().inc(
            "remat.attention." + ("saved" if kernels else "rerun"))


def _ambient_mesh():
    """The mesh set by `jax.set_mesh`, or None when there is none."""
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


def _bound_axes(mesh):
    """Mesh axes already bound manual by an enclosing shard_map."""
    return mesh.manual_axes if mesh is not None else ()


def _seqpar_dispatch(q, k, v, cfg):
    """Route to ring / Ulysses context-parallel attention.

    Both collectives need their mesh axis *bound* (shard_map).  Two call
    shapes work: the whole model already under shard_map with the axis
    manual (detected via the ambient mesh's `manual_axes`) — call the local
    body directly; or the model under plain jit with a mesh active — wrap
    just the attention core in shard_map here, sequence over the CP axis,
    batch over whichever dp/fsdp axes divide it.
    """
    axis = cfg.ring_attention_axis or cfg.ulysses_axis
    impl_kwargs = {}
    if cfg.ring_attention_axis:
        from tensorflowonspark_tpu.parallel.ring_attention import (
            ring_attention as fn)
        if cfg.attention_impl == "dense":
            impl_kwargs["use_flash"] = False
    else:
        from tensorflowonspark_tpu.parallel.ulysses import (
            ulysses_attention as fn)
        if cfg.attention_impl == "dense":
            impl_kwargs["attn_fn"] = (
                lambda q, k, v, causal: dot_product_attention(
                    q, k, v, causal=causal))

    mesh = _ambient_mesh()
    in_mesh = mesh is not None and axis in mesh.axis_names
    bound = in_mesh and axis in _bound_axes(mesh)
    if bound or not in_mesh:
        # axis already bound by an enclosing shard_map (or no mesh at all,
        # in which case the collective will raise an unbound-axis error
        # rather than silently computing something else)
        return fn(q, k, v, axis_name=axis, causal=cfg.causal, **impl_kwargs)

    if q.shape[1] % mesh.shape[axis]:
        raise ValueError(
            f"seq_len={q.shape[1]} must be divisible by the {axis!r} axis "
            f"size {mesh.shape[axis]} for context-parallel attention")
    manual = _bound_axes(mesh)
    batch_axes = tuple(
        a for a in ("dp", "fsdp")
        if a in mesh.axis_names and a != axis and a not in manual
        and mesh.shape[a] > 1)
    import numpy as _np
    if batch_axes and q.shape[0] % int(_np.prod(
            [mesh.shape[a] for a in batch_axes])):
        logging.getLogger(__name__).warning(
            "batch=%d not divisible by mesh axes %s (sizes %s); context-"
            "parallel attention will replicate the batch over them — every "
            "member recomputes full-batch attention", q.shape[0], batch_axes,
            [mesh.shape[a] for a in batch_axes])
        batch_axes = ()
    return fn(q, k, v, axis_name=axis, causal=cfg.causal, mesh=mesh,
              batch_axes=batch_axes or None, **impl_kwargs)


def _flash_dispatch(q, k, v, cfg, window=None):
    """Route to the pallas flash kernel (`window`: a sliding layer's).

    `pallas_call` is a custom call GSPMD cannot partition, so under an
    active mesh the kernel must be wrapped in shard_map — batch over dp,
    heads over tp (the same layout the column-parallel qkv sharding rules
    produce).  Falls back to dense attention when the shard axes don't
    divide the batch/head dims.
    """
    from tensorflowonspark_tpu.ops.flash_attention import flash_attention
    from tensorflowonspark_tpu.parallel.ring_attention import _kv_repeat
    mesh = _ambient_mesh()
    if mesh is None:
        _count_remat(cfg, True)
        return flash_attention(q, k, v, causal=cfg.causal, window=window)
    axes = mesh.axis_names

    def _divides(axis, dim):
        return axis in axes and dim % mesh.shape[axis] == 0

    # tp must divide BOTH head dims (the kernel takes narrow GQA k/v;
    # shard_map splits q and kv heads by the same axis).  When tp divides
    # the q heads but not the narrow kv heads (tp > n_kv), repeat kv to
    # full width first — the round-4 layout — so flash still runs
    # instead of silently dropping to dense O(S^2) attention.
    dp = "dp" if _divides("dp", q.shape[0]) else None
    if (_divides("tp", q.shape[2]) and not _divides("tp", k.shape[2])
            and "tp" in axes and mesh.shape["tp"] > 1):
        k, v = _kv_repeat(q, k, v)
    tp = ("tp" if _divides("tp", q.shape[2]) and _divides("tp", k.shape[2])
          else None)
    # dense fallback when a >1-sized mesh axis can't shard its dim: a
    # replicated in_spec there would all-gather the sharded activations and
    # recompute attention redundantly on every member of that axis
    for name, got in (("dp", dp), ("tp", tp)):
        if got is None and name in axes and mesh.shape[name] > 1:
            _count_remat(cfg, False)
            kf, vf = _kv_repeat(q, k, v)   # dense core needs full heads
            return dot_product_attention(q, kf, vf, causal=cfg.causal,
                                         window=window)
    import functools
    from jax.sharding import PartitionSpec as P

    _count_remat(cfg, True)
    spec = P(dp, None, tp, None)
    local = functools.partial(flash_attention, causal=cfg.causal,
                              window=window)
    return jax.shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)


def dot_product_attention(q, k, v, causal=True, mask=None, window=None):
    """Standard attention with f32 softmax accumulation.

    [B, S, H, D] inputs; einsum layouts chosen so the two matmuls land on
    the MXU as [S, D] x [D, S] and [S, S] x [S, D] per (batch, head).
    `mask` is an optional [B, S_k] key-validity mask (True = attend),
    BERT-style padding.  `window`: query i sees key j only if i - j <
    window (with `causal`, the last `window` keys up to itself).
    """
    head_dim = q.shape[-1]
    scale = 1.0 / jnp.sqrt(head_dim).astype(jnp.float32)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        S_q, S_k = q.shape[1], k.shape[1]
        cmask = jnp.tril(jnp.ones((S_q, S_k), dtype=bool))
        logits = jnp.where(cmask[None, None], logits, -1e30)
    if window is not None:
        near = (jnp.arange(q.shape[1])[:, None]
                - jnp.arange(k.shape[1])[None, :]) < window
        logits = jnp.where(near[None, None], logits, -1e30)
    if mask is not None:
        logits = jnp.where(mask[:, None, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _activation(x, name):
    if name == "gelu_tanh":
        return nn.gelu(x, approximate=True)
    if name == "gelu_exact":
        return nn.gelu(x, approximate=False)
    if name == "relu":
        return nn.relu(x)
    if name == "silu":
        return nn.silu(x)
    if name == "relu2":
        return jnp.square(nn.relu(x))
    raise ValueError(f"activation={name!r} not in "
                     "('gelu_tanh', 'gelu_exact', 'relu', 'silu', 'relu2')")


class DenseMLP(nn.Module):
    """Feed-forward block; ``cfg.mlp_style`` picks the form:
    ``plain``  — wo(act(wi(x))), the GPT/BERT shape;
    ``gated``  — wo(act(wi_gate(x)) * wi_up(x)), the LLaMA-family
    GLU shape (SwiGLU when activation='silu').  The gate/up kernels keep
    the ``wi`` name prefix so the Megatron column-parallel sharding rule
    applies unchanged (parallel/sharding.py DEFAULT_RULES)."""
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        if cfg.mlp_style not in ("plain", "gated"):
            raise ValueError(
                f"mlp_style={cfg.mlp_style!r} not in ('plain', 'gated')")
        impl = cfg.quant_matmul_impl
        if cfg.mlp_style == "gated":
            g = QuantDense(cfg.d_ff, use_bias=cfg.use_bias, name="wi_gate",
                           dtype=dtype, impl=impl)(x)
            u = QuantDense(cfg.d_ff, use_bias=cfg.use_bias, name="wi_up",
                           dtype=dtype, impl=impl)(x)
            h = _activation(g, cfg.activation) * u
        else:
            h = QuantDense(cfg.d_ff, use_bias=cfg.use_bias, name="wi",
                           dtype=dtype, impl=impl)(x)
            h = _activation(h, cfg.activation)
        return QuantDense(cfg.d_model, use_bias=cfg.use_bias,
                          name="wo", dtype=dtype, impl=impl)(h)


_ROW_CHUNK = 8192     # rows a pass of `_take_first`'s loop moves
_SUM_CHUNK = 512      # tokens a pass of `_sum_held_rows`'s loops sums
_SUM_LOOPS = 3        # loops (distinct bodies) of `_sum_held_rows` at most


def _take_first(src, idx, n):
    """`src[idx]` [M, D], of which only the first `n` rows are wanted.
    Where M is whole chunks the rows are moved a chunk at a time in a loop
    of `ceil(n / chunk)` passes and the chunks after them stay zero, so a
    row buffer sized for the worst routing costs what the routing fills
    (not differentiable: used inside custom forward and backward rules)."""
    m = idx.shape[0]
    if m % _ROW_CHUNK:
        return jnp.take(src, idx, axis=0)

    def move(i, buf):
        ids = jax.lax.dynamic_slice(idx, (i * _ROW_CHUNK,), (_ROW_CHUNK,))
        return jax.lax.dynamic_update_slice(
            buf, jnp.take(src, ids, axis=0), (i * _ROW_CHUNK, 0))

    return jax.lax.fori_loop(
        0, (n + _ROW_CHUNK - 1) // _ROW_CHUNK, move,
        jnp.zeros((m, src.shape[1]), src.dtype))


def _sum_plan(T, k, share, chunk):
    """`(chunk, widths)` of `_sum_held_rows`'s loops, from static shapes
    alone: chunks of `chunk` tokens where T is whole chunks (else one of
    all T); the widths are k and the `_SUM_LOOPS - 1` below it that
    gather the fewest rows where each token holds each of its k picks with
    probability `share` (the held experts' share: a uniform router's
    counts)."""
    chunk = chunk if T % chunk == 0 else T
    # chunks whose first token holds s held picks or more, s = 0 .. k + 1
    reach = [math.ceil(T * sum(
        math.comb(k, j) * share ** j * (1 - share) ** (k - j)
        for j in range(s, k + 1)) / chunk) for s in range(k + 2)]

    def rows(widths):
        return sum(w * (reach[below + 1] - reach[w + 1])
                   for below, w in zip((0,) + widths, widths))

    return chunk, min((low + (k,) for low in itertools.combinations(
        range(1, k), min(k, _SUM_LOOPS) - 1)), key=rows)


@struct.dataclass
class _HeldPicks:
    """Each token's held picks, found once in the `route` scope for the
    two sums back to the tokens (integers, about 1 MB a layer), and the
    static plan of their loops (`_sum_plan`).  `rows[r, i]`: the row of
    the r-th held pick of the i-th token in order of falling count (row 0
    past its count); `c[i]`: that token's count; `m[r]`: the tokens with
    more than r held picks; `rank[t, j]`: the slot of token t's pick j (`k`
    where it is absent); `sigma`: that order of the tokens (stable), `inv`
    its inverse."""
    chunk: int = struct.field(pytree_node=False)
    widths: tuple = struct.field(pytree_node=False)
    rows: jax.Array
    c: jax.Array
    m: jax.Array
    rank: jax.Array
    sigma: jax.Array
    inv: jax.Array


def _to_slots(a, rank):
    """`a` [T, k] with each token's held picks moved to their slots (a
    select and a sum over one-hot slots: no gather); 0 past them."""
    slot = rank[:, :, None] == jnp.arange(a.shape[1])
    return jnp.sum(jnp.where(slot, a[:, :, None], 0), axis=1)


@functools.partial(jax.jit, static_argnames=("share", "chunk"))
def _hold(pos, local, share, chunk):
    """The `_HeldPicks` of a routing: `pos` [T, k] rows of the pairs,
    `local` [T, k] which picks fell on held experts, `share` the held
    experts' share of all, `chunk` tokens a pass of the sums' loops."""
    T, k = pos.shape
    plan = _sum_plan(T, k, share, chunk)
    c = jnp.sum(local, axis=1, dtype=jnp.int32)                     # [T]
    rank = jnp.where(local, jnp.cumsum(local, axis=1, dtype=jnp.int32) - 1,
                     k)
    sigma = jnp.argsort(-c, stable=True).astype(jnp.int32)
    inv = jnp.zeros_like(sigma).at[sigma].set(
        jnp.arange(T, dtype=jnp.int32), unique_indices=True)
    m = jnp.sum(c[None, :] > jnp.arange(k)[:, None], axis=1, dtype=jnp.int32)
    both = jnp.take(jnp.concatenate([_to_slots(pos, rank), c[:, None]], 1),
                    sigma, axis=0)
    return _HeldPicks(*plan, both[:, :k].T, both[:, k], m, rank, sigma, inv)


def _sum_loops(held):
    """`[(width, first, end)]`: each loop of `_sum_held_rows` takes the
    chunks `[first, end)`, those whose first token holds more held picks
    than the width below and at most its own."""
    k = held.rows.shape[0]

    def reach(s):       # chunks whose first token holds s or more
        return 0 if s > k else (held.m[s - 1] + held.chunk - 1) // held.chunk

    return [(w, reach(w + 1), reach(below + 1))
            for below, w in zip((0,) + held.widths, held.widths)]


def _summed_rows(held):
    """Rows `_sum_held_rows` gathers: each chunk's tokens at its width."""
    return sum(w * (end - first)
               for w, first, end in _sum_loops(held)) * held.chunk


@jax.jit
def _sum_held_rows(src, w, held):
    """`sum_j w[t, j] * src[pos[t, j]]` over the held picks of each token
    (`w` [T, k] in pick order, or None for weights of 1), summed in
    float32: [T, D] in `src`'s type.  The tokens, in order of falling
    count, go in chunks (`_sum_loops`); a loop of width s gathers `[s,
    chunk, D]` rows a chunk (slot-major: `[chunk, s, D]` pads s to the
    tile's sublanes and took twice as long on the chip), selects them
    where the token has that many (a select: rows past the last group were
    never written and may hold anything), weights and sums them in one
    pass and writes its `[chunk, D]`; one gather by `inv` puts the tokens
    back in order.  Jitted on its own: traced and lowered once a distinct
    shape, not once a layer and pass (XLA inlines the calls again)."""
    T, size = held.rows.shape[1], held.chunk
    if w is not None:                                 # [k, T]: slot-major
        w = jnp.take(_to_slots(w, held.rank), held.sigma, axis=0).T

    def sums(s):
        def body(i, out):
            start = i * size
            rows = jnp.take(src, jax.lax.dynamic_slice(
                held.rows, (0, start), (s, size)), axis=0)  # [s, size, D]
            have = jax.lax.dynamic_slice(held.c, (start,), (size,))
            rows = jnp.where((jnp.arange(s)[:, None] < have)[..., None],
                             rows, 0).astype(jnp.float32)
            if w is not None:
                rows = rows * jax.lax.dynamic_slice(
                    w, (0, start), (s, size))[..., None]
            return jax.lax.dynamic_update_slice(
                out, jnp.sum(rows, axis=0).astype(out.dtype), (start, 0))
        return body

    out = jnp.zeros((T, src.shape[1]), src.dtype)
    for width, first, end in _sum_loops(held):
        out = jax.lax.fori_loop(first, end, sums(width), out)
    return jnp.take(out, held.inv, axis=0)


@jax.custom_vjp
def _moe_dispatch(xt, order, n, held):
    """`xt[order // k]` for the `n` held picks: the token of every (token,
    pick) pair, rows sorted by expert.  Its backward is a gather too: each
    token adds up the rows of its own held picks (`_sum_held_rows`, which
    reads those rows only), where XLA's would be a scatter-add."""
    return _take_first(xt, order // held.rows.shape[0], n)


def _moe_dispatch_fwd(xt, order, n, held):
    return _moe_dispatch(xt, order, n, held), held


def _moe_dispatch_bwd(held, g):
    return _sum_held_rows(g, None, held), None, None, None


_moe_dispatch.defvjp(_moe_dispatch_fwd, _moe_dispatch_bwd)


@jax.custom_vjp
def _moe_combine(out, w, order, pos, local, held):
    """`sum_j w[t, j] * out[pos[t, j]]` over a token's held picks, summed
    in float32: [T, D] in `out`'s type; it reads the rows of held picks
    only (`_sum_held_rows`).  Backward by gathers, as `_moe_dispatch`."""
    return _sum_held_rows(out, w, held)


def _moe_combine_fwd(out, w, order, pos, local, held):
    return _moe_combine(out, w, order, pos, local, held), (out, w, order,
                                                           pos, local)


def _moe_combine_bwd(res, g):
    out, w, order, pos, local = res
    # in row order: the token's cotangent beside each of its rows, a plain
    # gather of all T x k (on the chip quicker than zeroing the buffer and
    # moving the chunks that hold the held picks); rows past the last
    # group were never computed: `dw` selects, and their weight is 0
    g_row = jnp.take(g, order // pos.shape[1], axis=0)
    dw_row = jnp.einsum("rd,rd->r", out.astype(jnp.float32),
                        g_row.astype(jnp.float32))
    dw = jnp.where(local, jnp.take(dw_row, pos), 0)
    w_row = jnp.take(jnp.where(local, w, 0).reshape(-1), order)    # [T*k]
    return ((g_row * w_row[:, None]).astype(out.dtype), dw, None, None,
            None, None)


_moe_combine.defvjp(_moe_combine_fwd, _moe_combine_bwd)


MOE_COUNTERS = ("moe.pairs.local", "moe.pairs.absent", "moe.load.max",
                "moe.load.mean", "moe.picks.moved", "moe.picks.kept",
                "moe.rows.summed", "moe.rows.skipped")


def moe_stats(intermediates):
    """`{counter: scalar}` of one step, from what its dropless MoE layers
    sowed (`moe_stats`, under `mutable=["intermediates"]`): `moe.pairs.local`
    and `moe.pairs.absent` (picks that fell on held and on absent experts),
    `moe.load.max` and `moe.load.mean` (tokens of the fullest held expert
    and of the mean one), `moe.picks.moved` and `moe.picks.kept` (picks of
    top-k(score + bias) that are not, and that are, among top-k(score): 0
    and all of them where there is no selection bias), `moe.rows.summed`
    and `moe.rows.skipped` (of the `T x k` pairs' rows, those the two sums
    back to the tokens gather, whole chunks at their loop's width, and the
    rest: `_summed_rows`), each summed over the layers.  A loss function
    returns them as its aux metrics and names them in its `counters`
    (`MOE_COUNTERS`): `parallel.train.make_train_step` then adds each step's
    to `trace.counters()` with no sync, and once a step (what the
    rematerialised blocks sow a second time is never read).  Nothing sowed
    (no such layer): `{}`."""
    stats = [leaf for path, leaf in
             jax.tree_util.tree_leaves_with_path(intermediates)
             if any(getattr(p, "key", None) == "moe_stats" for p in path)]
    if not stats:
        return {}
    return dict(zip(MOE_COUNTERS, jnp.sum(jnp.stack(stats), axis=0)))


class _ExpertKernel(nn.Module):
    """One stacked expert weight as a module of its own, so that its leaf
    is `<name>/kernel` in the tree and not a name with a slash in it (the
    dense and topk routers keep their older flat names, which `convert.py`
    and exported checkpoints carry); both spell the same path for the
    sharding rules."""
    shape: tuple

    @nn.compact
    def __call__(self):
        return self.param("kernel", nn.initializers.lecun_normal(),
                          self.shape)


class MoEMLP(nn.Module):
    """Mixture-of-experts MLP (Switch/GShard-style).

    Expert weights carry a leading [num_experts] dim that the sharding rules
    place on the ep axis.  Three routers, all static-shape: `dense` sends
    every token through every expert slot and masks (exact, the numerics
    reference); `topk` is GShard capacity dispatch where each expert
    computes a fixed C slots and overflow tokens fall back to the residual
    stream; `dropless` sorts the (token, pick) pairs by expert and runs
    them through `ops.grouped_matmul`: no capacity, no token dropped, and
    with `moe_experts_held` the chip's share of an expert-parallel layer.
    """
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        B, S, D = x.shape
        E = cfg.num_experts
        F = cfg.moe_d_ff or cfg.d_ff
        if cfg.moe_router not in ("dense", "topk", "dropless"):
            raise ValueError(f"moe_router={cfg.moe_router!r} not in "
                             "('dense', 'topk', 'dropless')")
        dropless = cfg.moe_router == "dropless"
        if cfg.moe_experts_held is not None and not dropless:
            raise ValueError("moe_experts_held (a share of the experts) "
                             "needs moe_router='dropless'")
        if cfg.moe_scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"moe_scoring={cfg.moe_scoring!r} not in "
                             "('softmax', 'sigmoid')")
        if not dropless and (cfg.moe_scoring != "softmax"
                             or cfg.moe_expert_bias
                             or cfg.moe_shared_experts
                             or cfg.moe_routed_scale != 1.0):
            raise ValueError("moe_scoring, moe_expert_bias, "
                             "moe_shared_experts and moe_routed_scale need "
                             "moe_router='dropless'")
        gate_logits = QuantDense(E, use_bias=False, name="router",
                                 impl=cfg.quant_matmul_impl)(
            x.astype(jnp.float32))
        probs = (jax.nn.sigmoid(gate_logits) if cfg.moe_scoring == "sigmoid"
                 else jax.nn.softmax(gate_logits, axis=-1))
        bias = (self.param("expert_bias", nn.initializers.zeros, (E,),
                           jnp.float32) if cfg.moe_expert_bias else None)

        def experts(name, shape):
            if dropless:
                kernel = _ExpertKernel(shape, name=name)()
                with jax.named_scope("cast"):
                    return kernel.astype(dtype)
            return self.param(f"{name}/kernel",
                              nn.initializers.lecun_normal(),
                              shape).astype(dtype)

        held = E if cfg.moe_experts_held is None else cfg.moe_experts_held
        wi = experts("experts_wi", (held, D, F))
        wo = experts("experts_wo", (held, F, D))
        # gated experts (Mixtral-shape): wi routes through the activation,
        # experts_up is the linear branch; both shard like experts_wi
        # (the sharding rule matches the experts_(wi|up) prefix)
        up = (experts("experts_up", (held, D, F))
              if cfg.mlp_style == "gated" else None)

        if dropless:
            # no balancing loss is sown here: the counters say how the
            # load fell (`moe_stats`)
            y = self._dropless_route(x, probs, bias, wi, up, wo)
            if cfg.moe_shared_experts:
                trace.counters().inc("moe.shared.calls")  # a traced call
                y = y + DenseMLP(dataclasses.replace(
                    cfg, d_ff=cfg.moe_shared_experts * F), name="shared")(x)
            return y

        def expert_mlp(xe):
            """xe: [E, ..., D] -> [E, ..., D], batched over the expert dim."""
            h = _activation(jnp.einsum("e...d,edf->e...f", xe, wi),
                            cfg.activation)
            if up is not None:
                h = h * jnp.einsum("e...d,edf->e...f", xe, up)
            return jnp.einsum("e...f,efd->e...d", h, wo)

        if cfg.moe_router == "dense":
            top_idx = jnp.argmax(probs, axis=-1)             # [B, S]
            top_p = jnp.take_along_axis(probs, top_idx[..., None], axis=-1)
            dispatch = jax.nn.one_hot(top_idx, E, dtype=dtype)  # [B, S, E]
            # every token through every expert slot, masked by routing
            xe = jnp.einsum("bsd,bse->ebsd", x, dispatch)
            y = jnp.einsum("ebsd->bsd", expert_mlp(xe)) * top_p.astype(dtype)
            frac_tokens = jnp.mean(dispatch.astype(jnp.float32), axis=(0, 1))
        else:
            y, frac_tokens = self._topk_route(x, probs, expert_mlp)
        # aux load-balancing loss (Switch): E * sum_e (frac_tokens * frac_prob)
        frac_probs = jnp.mean(probs, axis=(0, 1))
        aux = E * jnp.sum(frac_tokens * frac_probs)
        self.sow("intermediates", "moe_aux_loss", aux)
        return y

    def _dropless_route(self, x, probs, bias, wi, up, wo):
        """Top-k routing over all `num_experts`, computed for the experts
        held here.  The k experts are chosen by `probs + bias` where there
        is a selection bias, and weighted by `probs` alone, over their sum,
        times `moe_routed_scale`.
        Of a token's k picks those that fall on held experts
        are sorted by expert, run through the grouped matmul, weighted
        and summed back per token; the static row buffer is sized for the
        worst case (every pick held), and the kernel does no work for the
        rows past the last group, so no routing drops a token.  What the
        absent experts would have added is left out: with all experts
        held this is the whole layer.  `dispatch`'s forward moves the
        chunks of rows that hold the held picks (`_take_first`); the two
        sums back to the tokens, `combine`'s forward and `dispatch`'s
        backward, read the rows of held picks only (`_sum_held_rows`, over
        the `_HeldPicks` that `route` finds beside the sort)."""
        from tensorflowonspark_tpu.ops.grouped_matmul import grouped_matmul

        cfg = self.cfg
        B, S, D = x.shape
        E, k = cfg.num_experts, cfg.moe_top_k
        held, off = wi.shape[0], cfg.moe_expert_offset
        if not 1 <= k <= E:
            raise ValueError(f"moe_top_k={k} must be in [1, {E}]")
        if not 0 <= off <= E - held:
            raise ValueError(f"experts [{off}, {off + held}) are not among "
                             f"the {E} the router scores")
        T = B * S
        xt = x.reshape(T, D).astype(jnp.dtype(cfg.dtype))
        scores = probs.reshape(T, E)                               # f32
        with jax.named_scope("route"):
            topk_p, topk_idx = jax.lax.top_k(scores, k)
            moved = jnp.int32(0)
            if bias is not None:
                unbiased = topk_idx
                _, topk_idx = jax.lax.top_k(scores + bias, k)
                topk_p = jnp.take_along_axis(scores, topk_idx, axis=-1)
                moved = jnp.sum(~jnp.any(
                    topk_idx[:, :, None] == unbiased[:, None, :], axis=-1))
            if cfg.moe_scoring == "sigmoid":
                topk_p = topk_p / (jnp.sum(topk_p, axis=-1, keepdims=True)
                                   + 1e-6)
            elif k > 1:    # renormalised over the picks, as `topk` does
                topk_p = topk_p / jnp.maximum(
                    jnp.sum(topk_p, axis=-1, keepdims=True), 1e-9)
            if cfg.moe_routed_scale != 1.0:
                topk_p = topk_p * cfg.moe_routed_scale
            local = (topk_idx >= off) & (topk_idx < off + held)    # [T, k]
            # rows sorted by held expert, the absent picks behind them all
            key = jnp.where(local, topk_idx - off, held).reshape(T * k)
            # `order`: row -> pair; `pos`: pair -> row
            order = jnp.argsort(key, stable=True).astype(jnp.int32)
            pos = jnp.zeros((T * k,), jnp.int32).at[order].set(
                jnp.arange(T * k, dtype=jnp.int32),
                unique_indices=True).reshape(T, k)
            sizes = jnp.sum(key[:, None] == jnp.arange(held)[None, :],
                            axis=0, dtype=jnp.int32)               # [held]
            n_local = jnp.sum(sizes)
            picks = _hold(pos, local, held / E, _SUM_CHUNK)
            summed = _summed_rows(picks)
        with jax.named_scope("dispatch"):
            xs = _moe_dispatch(xt, order, n_local, picks)          # [T*k, D]
        with jax.named_scope("experts"):
            h = _activation(grouped_matmul(xs, wi, sizes), cfg.activation)
            if up is not None:
                h = h * grouped_matmul(xs, up, sizes)
            out = grouped_matmul(h, wo, sizes)                     # [T*k, D]
        with jax.named_scope("combine"):
            y = _moe_combine(out, topk_p, order, pos, local, picks)
        self.sow("intermediates", "moe_stats", jnp.stack([
            n_local, T * k - n_local, jnp.max(sizes), n_local / held,
            moved, T * k - moved, summed, T * k - summed]).astype(
                jnp.float32))                            # as MOE_COUNTERS
        # the choices themselves, for a look at how rounding moves them
        # (`benchmark/tests/moe_routes.py`); unread, they cost nothing
        self.sow("intermediates", "moe_picks", topk_idx)
        return y.reshape(B, S, D)

    def _topk_route(self, x, probs, expert_mlp):
        """GShard-style capacity dispatch: each token picks its top-k
        experts; each expert processes a STATIC number of slots C =
        ceil(capacity_factor * k * T / E).  Tokens claim slots by cumsum
        priority (all first choices before second choices); overflow tokens
        are dropped (their residual branch contributes zero — the residual
        connection still carries them).  Static shapes, sort-free, and
        compute per expert is C instead of the dense router's full T.
        """
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        B, S, D = x.shape
        E, k = cfg.num_experts, cfg.moe_top_k
        if not 1 <= k <= E:
            raise ValueError(f"moe_top_k={k} must be in [1, {E}]")
        T = B * S
        C = int(max(1, -(-cfg.moe_capacity_factor * k * T // E)))
        C = min(C, T)
        xt = x.reshape(T, D)
        pt = probs.reshape(T, E)                              # f32

        topk_p, topk_idx = jax.lax.top_k(pt, k)               # [T, k]
        if k > 1:
            # renormalize combine weights over the chosen experts (GShard
            # top-2 convention); k=1 keeps the raw probability as the scale
            # (Switch convention — and the router's gradient signal)
            topk_p = topk_p / jnp.maximum(
                jnp.sum(topk_p, axis=-1, keepdims=True), 1e-9)

        combine = jnp.zeros((T, E, C), jnp.float32)
        counts = jnp.zeros((E,), jnp.int32)
        for c in range(k):                                    # k is tiny
            onehot = jax.nn.one_hot(topk_idx[:, c], E, dtype=jnp.int32)
            pos = jnp.cumsum(onehot, axis=0) - 1 + counts[None, :]  # [T, E]
            counts = counts + jnp.sum(onehot, axis=0)
            keep = (onehot > 0) & (pos < C)
            slot = jax.nn.one_hot(jnp.where(keep, pos, -1), C,
                                  dtype=jnp.float32)          # [T, E, C]
            combine = combine + slot * topk_p[:, c, None, None]
        dispatch = (combine > 0).astype(dtype)                # [T, E, C]

        expert_in = jnp.einsum("td,tec->ecd", xt, dispatch)   # [E, C, D]
        expert_out = expert_mlp(expert_in)                    # [E, C, D]
        yt = jnp.einsum("ecd,tec->td", expert_out.astype(jnp.float32),
                        combine)
        # aux-loss token fractions come from the router's PRE-drop first
        # choices (Switch/GShard): post-capacity fractions saturate at C/T,
        # muting the balancing gradient exactly when the router collapses
        frac_tokens = jnp.mean(
            jax.nn.one_hot(topk_idx[:, 0], E, dtype=jnp.float32), axis=0)
        return yt.reshape(B, S, D).astype(dtype), frac_tokens


def _constrain_bsd(x, cfg, seq_axis, d_axis):
    """`with_sharding_constraint` on a [B, S, D] stream with batch over dp
    and the given mesh axes (or None) on the sequence/model dims; a no-op
    without an sp config or an active mesh (single-device runs)."""
    if not cfg.sp_axis:
        return x
    from jax.sharding import PartitionSpec as P
    try:
        return jax.lax.with_sharding_constraint(x, P("dp", seq_axis, d_axis))
    except Exception:
        # no mesh context (or mesh without dp/sp axes): run unconstrained —
        # logged because under a REAL mesh this silently disables the
        # sp sharding (and the embed-gather remat fix)
        logger.debug("sharding constraint skipped (no active mesh?)",
                     exc_info=True)
        return x


def _embed_out_constrain(x, cfg):
    """Pin the token-embed gather OUTPUT to its natural sharding: batch
    over dp, d_model over tp (matching the table's P(None, 'tp') layout).

    Without this, the first block's sp constraint (P(dp, sp, None))
    propagates back onto the gather itself, and XLA's SPMD partitioner
    cannot reshard a gather efficiently — it falls back to "involuntary
    full rematerialization" (replicate everything, then re-partition).
    Staging the layouts — gather at its natural spec, then the
    seq-shard/d-gather transition on a separate copy op — turns that into
    the ordinary Megatron-SP all-to-all at block entry."""
    return _constrain_bsd(x, cfg, None, cfg.sp_axis)


def _sp_constrain(x, cfg):
    """Megatron sequence parallelism: between blocks the residual stream is
    sharded over sequence on the sp axis, so the layernorms and elementwise
    work are divided N_tp-ways and XLA turns the tp allreduces into
    reduce-scatter + all-gather pairs at block entry/exit."""
    return _constrain_bsd(x, cfg, cfg.sp_axis, None)


def _make_ln(cfg, name):
    if cfg.norm_type not in ("layernorm", "rmsnorm"):
        raise ValueError(
            f"norm_type={cfg.norm_type!r} not in ('layernorm', 'rmsnorm')")
    if cfg.norm_type == "rmsnorm":
        return nn.RMSNorm(name=name, dtype=jnp.float32, epsilon=cfg.ln_eps)
    return nn.LayerNorm(name=name, dtype=jnp.float32, epsilon=cfg.ln_eps)


class Block(nn.Module):
    """One transformer block; ``cfg.norm_style`` picks the residual form:
    pre-LN ``x + f(ln(x))`` (GPT/LLaMA-style, the training-stable default)
    or post-LN ``ln(x + f(x))`` (original-BERT-style, needed for faithful
    BERT checkpoints — see convert.from_hf_bert).  With `part` (a character
    of `TransformerConfig.layer_pattern`) the block holds that one part,
    pre-LN under one norm: ``x + part(norm(x))``."""
    cfg: TransformerConfig
    use_moe: bool = False
    layer_type: str = FULL
    part: Optional[str] = None

    @nn.compact
    def __call__(self, x, mask=None):
        cfg = self.cfg
        if cfg.norm_style not in ("pre", "post"):
            raise ValueError(
                f"norm_style={cfg.norm_style!r} not in ('pre', 'post')")
        if self.part is not None:
            if cfg.norm_style != "pre":
                raise ValueError("a layer of one part is pre-LN")
            x = _sp_constrain(x, cfg)
            h = _make_ln(cfg, "norm")(x)
            if self.part == "E":
                return x + MoEMLP(cfg, name="moe")(h)
            mixer = (Mamba2Mixer(cfg, name="mamba") if self.part == MAMBA
                     else Attention(cfg, name="attn"))
            return x + mixer(h, mask=mask)
        ln1 = _make_ln(cfg, "ln1")
        ln2 = _make_ln(cfg, "ln2")
        if self.layer_type == CONV:
            attn = ShortConv(cfg, name="conv")
        elif self.layer_type == KDA:
            attn = KimiDeltaMixer(cfg, name="kda")
        else:
            attn = Attention(cfg, self.layer_type, name="attn")
        mlp = (MoEMLP(cfg, name="moe") if self.use_moe
               else DenseMLP(cfg, name="mlp"))
        x = _sp_constrain(x, cfg)
        if cfg.norm_style == "pre":
            x = x + attn(ln1(x), mask=mask)
            x = _sp_constrain(x, cfg)
            return x + mlp(ln2(x))
        dtype = jnp.dtype(cfg.dtype)
        x = ln1(x + attn(x, mask=mask)).astype(dtype)
        x = _sp_constrain(x, cfg)
        return ln2(x + mlp(x)).astype(dtype)


def remat_block():
    """`Block` as `remat=True` wraps it: recomputed in the backward pass
    from its input, but for attention's output and row log-sum-exp, which
    the forward rules of `ops.flash_attention` name `flash_out` and
    `flash_lse` and this policy saves, one `[B, S, H*Dv]` and one
    `f32[B, H, S]` a layer, so the backward's `flash_dq` / `flash_dkv`
    start from them and the forward kernel, the dearest call of the block,
    runs once a step and not twice.  A mixer on the dense core (a mask,
    `attention_impl='dense'`, latent attention under a mesh) names nothing
    and is recomputed whole; `remat.attention.saved` / `.rerun` count
    which a traced mixer was.  A Mamba-2 mixer's SSD kernels name their
    output and chunk states the same way (`ssd_out`, `ssd_states`), and a
    kda layer's kernels theirs (`kda_out`, `kda_states`), so the
    recomputed block holds no `ssd_fwd` or `kda_fwd` either."""
    return nn.remat(Block, policy=jax.checkpoint_policies
                    .save_only_these_names("flash_out", "flash_lse",
                                           "ssd_out", "ssd_states",
                                           "kda_out", "kda_states"))


class Transformer(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, tokens, return_hidden=False):
        """Token ids -> logits; ``return_hidden=True`` returns the post-ln_f
        hidden states instead, for losses that fuse the unembedding matmul
        (ops.xent.fused_unembed_xent) — the lm_head params still exist and
        receive their gradient through the fused op (with `tie_embeddings`
        the head is the embedding's table transposed, and the table gets
        the sum of both uses' gradients)."""
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        embed = nn.Embed(cfg.vocab_size, cfg.d_model, name="token_embed",
                         dtype=dtype)
        x = _embed_out_constrain(embed(tokens), cfg)
        if not cfg.rope and cfg.learned_positions:  # RoPE rotates q/k
            # inside attention instead
            pos_ids = jnp.arange(tokens.shape[1])
            if cfg.decode:
                # incremental steps look up absolute positions
                pi = self.variable(
                    "cache", "pos_index",
                    lambda: jnp.zeros(
                        (tokens.shape[0],) if cfg.decode_slots else (),
                        jnp.int32))
                if not self.is_initializing():
                    if cfg.decode_slots:   # per-row positions: [B, S]
                        pos_ids = pi.value[:, None] + pos_ids[None, :]
                    else:
                        pos_ids = (pos_ids + pi.value)[None]
                    pi.value = pi.value + tokens.shape[1]
            if pos_ids.ndim == 1:
                pos_ids = pos_ids[None]
            pos = nn.Embed(cfg.max_seq_len, cfg.d_model, name="pos_embed",
                           dtype=dtype)(pos_ids)
            x = x + pos
        block_cls = remat_block() if cfg.remat else Block
        if cfg.layer_pattern:
            for i, part in enumerate(cfg.layer_pattern):
                x = block_cls(cfg, part=part, name=f"layer_{i}")(x)
        else:
            for i in range(cfg.n_layers):
                # every k-th layer is MoE, counting so that moe_every=1
                # means every layer (k=2 keeps the old odd-layer placement);
                # the first `moe_dense_layers` stay dense
                use_moe = (cfg.num_experts > 0 and i >= cfg.moe_dense_layers
                           and i % cfg.moe_every == cfg.moe_every - 1)
                kind = cfg.layer_types[i] if cfg.layer_types else FULL
                x = block_cls(cfg, use_moe=use_moe, layer_type=kind,
                              name=f"layer_{i}")(x)
        ahead = ()
        if cfg.mtp_modules and (return_hidden or self.is_initializing()):
            ahead = self._predict_ahead(embed, tokens, x, block_cls)
        x = _make_ln(cfg, "ln_f")(x)
        hidden = (x.astype(dtype), ahead) if cfg.mtp_modules \
            else x.astype(dtype)
        if return_hidden and (cfg.tie_embeddings
                              or not self.is_initializing()):
            return hidden
        if cfg.tie_embeddings:
            return embed.attend(x.astype(dtype))
        logits = QuantDense(cfg.vocab_size, use_bias=False, name="lm_head",
                            dtype=dtype, impl=cfg.quant_matmul_impl)(x)
        # under `return_hidden` this was the init pass: lm_head now exists
        return hidden if return_hidden else logits

    def _predict_ahead(self, embed, tokens, z, block_cls):
        """The multi-token-prediction modules' hidden states, one a module
        (DeepSeek-V3 report, section 2.2).  Module k (from 1) at position t
        reads the state before it, `z[t]` (the last block's output, then
        module k-1's block's), and the embedding of token t + k:
        `Block(proj([RMSNorm(z[t]) | RMSNorm(e[t + k])]))`, then its own
        last norm.  The row's last k positions have no such token: they
        read zeros, see nothing later by causality, and
        `next_token_losses` masks them."""
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        sparse = cfg.num_experts > 0
        embedded = embed(tokens)
        out = []
        for k in range(1, cfg.mtp_modules + 1):
            name = f"mtp_{k - 1}"
            e = jnp.pad(embedded[:, k:], ((0, 0), (0, k), (0, 0)))
            m = jnp.concatenate(
                [_make_ln(cfg, f"{name}_hnorm")(z).astype(dtype),
                 _make_ln(cfg, f"{name}_enorm")(e).astype(dtype)], axis=-1)
            m = QuantDense(cfg.d_model, use_bias=False, name=f"{name}_proj",
                           dtype=dtype, impl=cfg.quant_matmul_impl)(m)
            z = block_cls(cfg, use_moe=sparse, name=f"{name}_block")(m)
            out.append(_make_ln(cfg, f"{name}_ln_f")(z).astype(dtype))
        return tuple(out)


LOSS_COUNTERS = ("loss.terms.next1", "loss.terms.next2")


def next_token_losses(hidden, ahead, kernel, rows, weight, chunk_size=512):
    """`(loss, terms)` of a model with multi-token-prediction modules:
    `CE(head(hidden[t]), rows[t + 1]) + weight x mean_k CE(head(ahead_k[t]),
    rows[t + 1 + k])`, every term by `ops.xent.fused_unembed_xent` over the
    ONE head `kernel` [D, V] (which so receives a gradient a term), module
    k's over the positions that have a token k + 1 ahead.  `hidden`,
    `ahead`: what `Transformer(..., return_hidden=True)` returns for
    `rows[:, :-1]`; `rows` [B, S + 1] the ids.  `terms` are the two
    summands as they are added, the second after its weight, under
    `LOSS_COUNTERS`' names: a loss function returns them among its aux
    metrics and names them in its `counters`, and
    `parallel.train.make_train_step` adds each step's to `trace.counters()`
    with no sync."""
    from tensorflowonspark_tpu.ops.xent import fused_unembed_xent

    first = fused_unembed_xent(hidden, kernel, rows[:, 1:], chunk_size)
    second = jnp.float32(0.0)
    for k, h in enumerate(ahead, 1):
        targets = jnp.pad(rows[:, 1 + k:], ((0, 0), (0, k)),
                          constant_values=-1)
        second = second + fused_unembed_xent(h, kernel, targets, chunk_size)
    second = second * (weight / max(len(ahead), 1))
    return first + second, dict(zip(LOSS_COUNTERS, (first, second)))


def lm_loss(logits, targets, ignore_id=-1):
    """Causal-LM cross entropy written gather-free (one-hot einsum) so a
    vocab-sharded lm_head works under jit sharding propagation."""
    vocab = logits.shape[-1]
    with trace.loss_scope("lm_loss"):
        logits = logits.astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        onehot = jax.nn.one_hot(jnp.maximum(targets, 0), vocab,
                                dtype=jnp.float32)
        gold = jnp.einsum("bsv,bsv->bs", logits, onehot)
        mask = (targets != ignore_id).astype(jnp.float32)
        return (jnp.sum((logz - gold) * mask)
                / jnp.maximum(jnp.sum(mask), 1.0))


def build_transformer(**kwargs):
    """Export-spec builder (``"module:callable"`` import path, see
    export.export_saved_model): rebuilds ``Transformer`` from JSON-able
    TransformerConfig fields, so exported decoder LMs can be rebuilt by
    the serving layer — including ``serve``'s :generate endpoint."""
    return Transformer(TransformerConfig(**kwargs))
