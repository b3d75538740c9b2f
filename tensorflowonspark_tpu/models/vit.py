"""Vision Transformer — image classification on the shared encoder stack.

Net-new relative to the reference (whose vision models are MNIST CNN,
ResNet-CIFAR, and UNet — SURVEY.md §2.5): ViT rounds out the vision family
with the architecture TPUs are best at — one big patchify matmul followed by
the same `transformer.Block` stack the LM/BERT families use, so the
tensor-parallel sharding rules (parallel/sharding.DEFAULT_RULES) apply to
it unchanged.

TPU notes: patchify is a stride=patch conv (one MXU matmul over
[B*N, p*p*c] x [p*p*c, d]); bf16 activations with f32 layernorms; static
token count N = (image/patch)^2 so everything jit-compiles once.
"""
import dataclasses

import flax.linen as nn
import jax.numpy as jnp

from tensorflowonspark_tpu.models.transformer import (
    Block, TransformerConfig, remat_block)


@dataclasses.dataclass(frozen=True)
class ViTConfig:
    image_size: int = 224
    patch_size: int = 16
    channels: int = 3
    num_classes: int = 1000
    d_model: int = 768
    n_heads: int = 12
    n_layers: int = 12
    d_ff: int = 3072
    dtype: str = "bfloat16"
    pool: str = "cls"             # cls token | mean over patch tokens
    remat: bool = False
    attention_impl: str = "auto"

    def __post_init__(self):
        if self.image_size % self.patch_size:
            raise ValueError(
                f"image_size {self.image_size} not divisible by "
                f"patch_size {self.patch_size}")
        if self.pool not in ("cls", "mean"):
            raise ValueError(f"pool={self.pool!r} not in ('cls', 'mean')")

    @property
    def num_patches(self):
        return (self.image_size // self.patch_size) ** 2

    def block_config(self):
        """The shared transformer-block config: bidirectional attention
        over patch tokens (+1 cls token when pool='cls')."""
        return TransformerConfig(
            vocab_size=1, d_model=self.d_model, n_heads=self.n_heads,
            n_layers=self.n_layers, d_ff=self.d_ff,
            max_seq_len=self.num_patches + 1, causal=False,
            dtype=self.dtype, remat=self.remat,
            attention_impl=self.attention_impl)


class ViT(nn.Module):
    """images [B, H, W, C] (float, any scale) -> logits [B, num_classes]."""
    cfg: ViTConfig

    @nn.compact
    def __call__(self, images):
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        p = cfg.patch_size
        B = images.shape[0]
        x = nn.Conv(cfg.d_model, (p, p), strides=(p, p), padding="VALID",
                    dtype=dtype, name="patch_embed")(images.astype(dtype))
        x = x.reshape(B, -1, cfg.d_model)              # [B, N, d]
        n_tokens = x.shape[1]
        if cfg.pool == "cls":
            cls = self.param("cls_token", nn.initializers.zeros_init(),
                             (1, 1, cfg.d_model))
            x = jnp.concatenate(
                [jnp.broadcast_to(cls, (B, 1, cfg.d_model)).astype(dtype), x],
                axis=1)
            n_tokens += 1
        pos = self.param("pos_embed",
                         nn.initializers.normal(stddev=0.02),
                         (1, n_tokens, cfg.d_model))
        x = x + pos.astype(dtype)
        bcfg = self.cfg.block_config()
        block_cls = remat_block() if cfg.remat else Block
        for i in range(cfg.n_layers):
            x = block_cls(bcfg, name=f"layer_{i}")(x)
        x = nn.LayerNorm(name="ln_f", dtype=jnp.float32)(x)
        pooled = x[:, 0] if cfg.pool == "cls" else x.mean(axis=1)
        return nn.Dense(cfg.num_classes, dtype=jnp.float32,
                        name="head")(pooled.astype(jnp.float32))


def ViTTiny(num_classes=10, image_size=32, patch_size=4, **kw):
    """CIFAR-scale ViT for tests/examples."""
    return ViT(ViTConfig(image_size=image_size, patch_size=patch_size,
                         num_classes=num_classes, d_model=192, n_heads=3,
                         n_layers=4, d_ff=768, **kw))


def ViTBase(num_classes=1000, **kw):
    """ViT-B/16 (86M params)."""
    return ViT(ViTConfig(num_classes=num_classes, **kw))
