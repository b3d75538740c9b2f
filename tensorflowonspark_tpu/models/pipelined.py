"""Pipeline-parallel flagship LM: Transformer blocks over the ``pp`` axis.

Wires the GPipe schedule (parallel/pipeline.py) to the real model family:
embedding and head stay data-parallel; the block tower is partitioned into
`n_stages` contiguous stages whose parameters live on their stage's devices
(leading [n_stages] dim sharded over pp), and microbatches stream through
the ring.  Within a stage, layers run as a `lax.scan` over the stacked
per-layer params (one compiled block body regardless of depth).

Numerically identical to the sequential `Transformer` — `from_transformer`
re-slices a trained sequential checkpoint into the pipelined layout, and
the tests assert logits match exactly.  Net-new vs the reference (no model
parallelism there, SURVEY.md §2.3).
"""
import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from tensorflowonspark_tpu.models.transformer import (
    Block, TransformerConfig, remat_block)
from tensorflowonspark_tpu.parallel.pipeline import (
    pipeline_apply, stack_stage_params)


class _Embedder(nn.Module):
    """Token (+ learned positional) embedding — same submodule names as
    `Transformer`, so sequential checkpoints re-slice losslessly."""
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, tokens):
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        x = nn.Embed(cfg.vocab_size, cfg.d_model, name="token_embed",
                     dtype=dtype)(tokens)
        if not cfg.rope:
            pos = nn.Embed(cfg.max_seq_len, cfg.d_model, name="pos_embed",
                           dtype=dtype)(jnp.arange(tokens.shape[1])[None])
            x = x + pos
        return x


class _Head(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        x = nn.LayerNorm(name="ln_f", dtype=jnp.float32)(x)
        return nn.Dense(cfg.vocab_size, use_bias=False, name="lm_head",
                        dtype=jnp.dtype(cfg.dtype))(x)


@dataclasses.dataclass
class PipelinedLM:
    """Functional pipeline-parallel LM.

    cfg constraints: dense MLPs only (num_experts=0 — MoE alternation would
    make stages heterogeneous) and n_layers divisible by n_stages.
    """
    cfg: TransformerConfig
    n_stages: int

    def __post_init__(self):
        if self.cfg.num_experts:
            raise ValueError(
                "PipelinedLM requires num_experts=0 (uniform blocks); "
                "shard experts over ep instead")
        if self.cfg.decode:
            raise NotImplementedError(
                "decode mode is not supported in the pipelined LM; decode "
                "with the sequential Transformer on a dp/tp mesh")
        if self.cfg.n_layers % self.n_stages:
            raise ValueError(
                f"n_layers={self.cfg.n_layers} must be divisible by "
                f"n_stages={self.n_stages}")
        self._embed = _Embedder(self.cfg)
        self._head = _Head(self.cfg)
        block_cls = remat_block() if self.cfg.remat else Block
        self._block = block_cls(self.cfg)

    @property
    def layers_per_stage(self):
        return self.cfg.n_layers // self.n_stages

    def init(self, rng, sample_tokens):
        """Init params: {'embed', 'blocks' ([n_stages, layers/stage, ...]
        leaves), 'head'}."""
        k_e, k_h, *k_layers = jax.random.split(rng, 2 + self.cfg.n_layers)
        p_embed = self._embed.init(k_e, sample_tokens)["params"]
        x = self._embed.apply({"params": p_embed}, sample_tokens)
        per_layer = [self._block.init(k, x)["params"] for k in k_layers]
        lp = self.layers_per_stage
        stages = [stack_stage_params(per_layer[s * lp:(s + 1) * lp])
                  for s in range(self.n_stages)]
        p_head = self._head.init(k_h, x)["params"]
        return {"embed": p_embed, "blocks": stack_stage_params(stages),
                "head": p_head}

    def from_transformer(self, params):
        """Re-slice a sequential `Transformer` checkpoint into the
        pipelined layout (inverse of interleaving)."""
        per_layer = [params[f"layer_{i}"] for i in range(self.cfg.n_layers)]
        lp = self.layers_per_stage
        stages = [stack_stage_params(per_layer[s * lp:(s + 1) * lp])
                  for s in range(self.n_stages)]
        embed = {"token_embed": params["token_embed"]}
        if not self.cfg.rope:
            embed["pos_embed"] = params["pos_embed"]
        return {"embed": embed,
                "blocks": stack_stage_params(stages),
                "head": {"ln_f": params["ln_f"],
                         "lm_head": params["lm_head"]}}

    def apply(self, params, tokens, mesh, n_micro=None):
        """Forward pass: embed (dp), pipeline the block tower (pp), head
        (dp).  `n_micro` defaults to the pp degree (the minimum that keeps
        every stage busy once the pipeline fills)."""
        n_micro = n_micro or self.n_stages
        B, S = tokens.shape
        if mesh.shape.get("pp", 1) != self.n_stages:
            # an exact multiple would shard silently and DROP stages
            # (shard_map slices [n_stages] to [n_stages/pp] and the local
            # body uses slice [0]); anything else errors cryptically
            raise ValueError(
                f"mesh pp axis size {mesh.shape.get('pp', 1)} must equal "
                f"n_stages={self.n_stages}")
        if B % n_micro:
            raise ValueError(
                f"batch {B} must be divisible by n_micro={n_micro}")
        x = self._embed.apply({"params": params["embed"]}, tokens)
        D = x.shape[-1]
        x_micro = x.reshape(n_micro, B // n_micro, S, D)

        block = self._block

        def stage_fn(stage_p, xm):
            def body(x, layer_p):
                return block.apply({"params": layer_p}, x), None
            y, _ = lax.scan(body, xm, stage_p)
            return y

        y = pipeline_apply(stage_fn, params["blocks"], x_micro, mesh)
        y = y.reshape(B, S, D)
        return self._head.apply({"params": params["head"]}, y)
