"""BERT encoder family — masked-LM pretraining on TPU.

Net-new relative to the reference (whose model zoo stops at MNIST CNN /
ResNet-CIFAR / UNet, SURVEY.md §2.5); BASELINE.json lists BERT-base
pretraining through the pipeline Estimator as a target config.  Built from
the same `transformer.Block` the causal LM uses (bidirectional: causal=False),
so the tensor-parallel sharding rules (parallel/sharding.DEFAULT_RULES)
apply unchanged — column-parallel qkv/wi, row-parallel out/wo.

TPU notes: bf16 activations with f32 norms; the MLM logits tie to the token
embedding via `nn.Embed.attend` (one [d_model, vocab] matmul on the MXU, no
separate lm_head weights); the MLM loss reuses the gather-free one-hot
einsum from `transformer.lm_loss` so a vocab-sharded embedding still works
under jit sharding propagation.
"""
import dataclasses

import flax.linen as nn
import jax.numpy as jnp

from tensorflowonspark_tpu.models.transformer import (
    Block, TransformerConfig, _activation, lm_loss, remat_block)


@dataclasses.dataclass(frozen=True)
class BertConfig:
    vocab_size: int = 30522
    d_model: int = 768
    n_heads: int = 12
    n_layers: int = 12
    d_ff: int = 3072
    max_seq_len: int = 512
    type_vocab_size: int = 2
    dtype: str = "bfloat16"
    remat: bool = False
    attention_impl: str = "auto"
    mask_token_id: int = 103  # [MASK] in the canonical BERT vocab
    # faithful original-BERT numerics (checkpoint-compatible with
    # convert.from_hf_bert): post-LN blocks, biased denses, erf GELU
    norm_style: str = "post"
    use_bias: bool = True
    activation: str = "gelu_exact"
    ln_eps: float = 1e-12

    def block_config(self):
        """The shared transformer-block config, bidirectional."""
        return TransformerConfig(
            vocab_size=self.vocab_size, d_model=self.d_model,
            n_heads=self.n_heads, n_layers=self.n_layers, d_ff=self.d_ff,
            max_seq_len=self.max_seq_len, causal=False, dtype=self.dtype,
            remat=self.remat, attention_impl=self.attention_impl,
            norm_style=self.norm_style, use_bias=self.use_bias,
            activation=self.activation, ln_eps=self.ln_eps)


class BertEncoder(nn.Module):
    """Embeddings (token + position + segment) -> post-embedding LN ->
    bidirectional transformer stack."""
    cfg: BertConfig

    @nn.compact
    def __call__(self, tokens, type_ids=None, attention_mask=None):
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        embed = nn.Embed(cfg.vocab_size, cfg.d_model, name="token_embed",
                         dtype=dtype)
        x = embed(tokens)
        pos = nn.Embed(cfg.max_seq_len, cfg.d_model, name="pos_embed",
                       dtype=dtype)(jnp.arange(tokens.shape[1])[None])
        x = x + pos
        if cfg.type_vocab_size:
            if type_ids is None:
                type_ids = jnp.zeros_like(tokens)
            x = x + nn.Embed(cfg.type_vocab_size, cfg.d_model,
                             name="type_embed", dtype=dtype)(type_ids)
        x = nn.LayerNorm(name="ln_embed", dtype=jnp.float32,
                         epsilon=cfg.ln_eps)(x).astype(dtype)
        bcfg = cfg.block_config()
        block_cls = remat_block() if cfg.remat else Block
        for i in range(cfg.n_layers):
            x = block_cls(bcfg, name=f"layer_{i}")(x, mask=attention_mask)
        if cfg.norm_style == "post":
            # post-LN blocks end normalized; a final LN is a pre-LN artifact
            return x, embed
        return nn.LayerNorm(name="ln_f", dtype=jnp.float32,
                            epsilon=cfg.ln_eps)(x), embed


class BertForPreTraining(nn.Module):
    """MLM head (embedding-tied decoder) + NSP head over the [CLS] pooler.

    Returns `(mlm_logits [B,S,V], nsp_logits [B,2])`.
    """
    cfg: BertConfig

    @nn.compact
    def __call__(self, tokens, type_ids=None, attention_mask=None):
        cfg = self.cfg
        h, embed = BertEncoder(cfg, name="encoder")(
            tokens, type_ids=type_ids, attention_mask=attention_mask)
        # MLM transform: dense + gelu + LN, then decode against the tied
        # embedding table (attend = h @ E^T) with a free bias
        t = nn.Dense(cfg.d_model, name="mlm_dense",
                     dtype=jnp.dtype(cfg.dtype))(h)
        t = _activation(t, cfg.activation)
        t = nn.LayerNorm(name="mlm_ln", dtype=jnp.float32,
                         epsilon=cfg.ln_eps)(t)
        mlm_logits = embed.attend(t.astype(embed.embedding.dtype))
        mlm_logits = mlm_logits + self.param(
            "mlm_bias", nn.initializers.zeros, (cfg.vocab_size,))
        # NSP: tanh pooler over position 0, binary classifier
        pooled = nn.tanh(nn.Dense(cfg.d_model, name="pooler",
                                  dtype=jnp.dtype(cfg.dtype))(h[:, 0]))
        nsp_logits = nn.Dense(2, name="nsp_head")(
            pooled.astype(jnp.float32))
        return mlm_logits, nsp_logits


def check_checkpoint_layout(cfg, params):
    """Raise a targeted error when a restored param tree's norm layout
    disagrees with `cfg.norm_style`.

    The pre-LN layout carries `encoder/ln_f`; the (default, HF-faithful)
    post-LN layout does not.  Checkpoints written before the post-LN
    default would otherwise fail deep inside `apply` with an opaque
    missing-param error — see MIGRATION.md "BERT checkpoint layout".
    """
    if isinstance(params, dict):
        params = params.get("params", params)   # flax variables wrapper
    enc = params.get("encoder", params) if isinstance(params, dict) else {}
    has_ln_f = isinstance(enc, dict) and "ln_f" in enc
    if cfg.norm_style == "post" and has_ln_f:
        raise ValueError(
            "checkpoint contains encoder/ln_f (pre-LN layout) but the "
            "config is norm_style='post' (the default since the HF-faithful "
            "change); load with BertConfig(norm_style='pre', use_bias=False, "
            "activation='gelu', ln_eps=1e-6) or re-save the checkpoint")
    if cfg.norm_style != "post" and isinstance(enc, dict) and enc \
            and not has_ln_f:
        raise ValueError(
            "checkpoint lacks encoder/ln_f but the config is pre-LN; this "
            "looks like a post-LN checkpoint — use the default BertConfig")


def build_bert(**kwargs):
    """Builder-spec target for export_saved_model ('module:callable' with
    JSON kwargs — BertConfig fields)."""
    return BertForPreTraining(BertConfig(**kwargs))


def mlm_loss(logits, targets):
    """Masked-LM cross entropy; `targets` = original token id at masked
    positions, -1 everywhere else (ignored).  Gather-free (vocab-shard
    safe) via transformer.lm_loss."""
    return lm_loss(logits, targets, ignore_id=-1)


def nsp_loss(logits, labels):
    """Next-sentence-prediction cross entropy over [B, 2] logits."""
    import optax

    return optax.softmax_cross_entropy_with_integer_labels(
        logits.astype(jnp.float32), labels).mean()


def apply_mlm_masking(rng, tokens, mask_token_id, vocab_size,
                      mask_prob=0.15):
    """The BERT 80/10/10 corruption: of the 15% selected positions, 80%
    become [MASK], 10% a random token, 10% stay unchanged.  Returns
    (corrupted_tokens, targets) with targets = -1 at unselected positions.

    Pure numpy — runs in the host-side feeder path, not under jit.
    """
    import numpy as np

    rng = np.random.default_rng(rng)
    tokens = np.asarray(tokens)
    select = rng.random(tokens.shape) < mask_prob
    targets = np.where(select, tokens, -1)
    action = rng.random(tokens.shape)
    corrupted = tokens.copy()
    corrupted[select & (action < 0.8)] = mask_token_id
    rand_tok = rng.integers(0, vocab_size, tokens.shape)
    corrupted[select & (action >= 0.8) & (action < 0.9)] = \
        rand_tok[select & (action >= 0.8) & (action < 0.9)]
    return corrupted, targets
