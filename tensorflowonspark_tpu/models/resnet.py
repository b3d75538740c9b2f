"""ResNet family — parity with the reference's ResNet/CIFAR example
(reference: examples/resnet/resnet_cifar_dist.py, which wraps the upstream
tf/models ResNet-56) plus the ResNet-50/ImageNet variant named by the
BASELINE north star (BASELINE.json: ResNet-50 >60% MFU on v4-32).

TPU-first choices:
- NHWC layout, 3x3/1x1 convs with static shapes — XLA tiles these onto the
  MXU directly; bfloat16 activations with float32 normalization.
- Default norm is GroupNorm: stateless (no batch_stats threading through
  the pjit train step) and it needs no cross-replica sync, where BatchNorm
  under SPMD data parallelism requires axis-grouped statistics.  Pass
  ``norm="batch"`` for classic BN (caller manages the ``batch_stats``
  collection via ``mutable=["batch_stats"]``).
"""
import functools
from typing import Any, Callable, Optional, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp

ModuleDef = Any


class ResNetBlock(nn.Module):
    """Basic 3x3+3x3 residual block (CIFAR/ResNet-18/34 style)."""
    filters: int
    conv: ModuleDef
    norm: ModuleDef
    act: Callable
    strides: int = 1

    @nn.compact
    def __call__(self, x):
        residual = x
        y = self.conv(self.filters, (3, 3), (self.strides, self.strides))(x)
        y = self.norm()(y)
        y = self.act(y)
        y = self.conv(self.filters, (3, 3))(y)
        y = self.norm(scale_init=nn.initializers.zeros_init())(y)
        if residual.shape != y.shape:
            residual = self.conv(self.filters, (1, 1),
                                 (self.strides, self.strides),
                                 name="conv_proj")(residual)
            residual = self.norm(name="norm_proj")(residual)
        return self.act(residual + y)


class BottleneckBlock(nn.Module):
    """1x1-3x3-1x1 bottleneck block (ResNet-50/101/152)."""
    filters: int
    conv: ModuleDef
    norm: ModuleDef
    act: Callable
    strides: int = 1

    @nn.compact
    def __call__(self, x):
        residual = x
        y = self.conv(self.filters, (1, 1))(x)
        y = self.norm()(y)
        y = self.act(y)
        y = self.conv(self.filters, (3, 3), (self.strides, self.strides))(y)
        y = self.norm()(y)
        y = self.act(y)
        y = self.conv(self.filters * 4, (1, 1))(y)
        y = self.norm(scale_init=nn.initializers.zeros_init())(y)
        if residual.shape != y.shape:
            residual = self.conv(self.filters * 4, (1, 1),
                                 (self.strides, self.strides),
                                 name="conv_proj")(residual)
            residual = self.norm(name="norm_proj")(residual)
        return self.act(residual + y)


class ResNet(nn.Module):
    """Configurable ResNet over NHWC images.

    ``stage_sizes`` counts blocks per stage; ``small_inputs`` keeps the
    CIFAR-style 3x3 stem (no max-pool) vs the 7x7/stride-2 ImageNet stem.

    ``norm``:
    - "group" (default): stateless GroupNorm — SPMD-friendly, but its
      statistics pass re-reads every conv output from HBM (the round-1
      profile's dominant cost at ImageNet shapes);
    - "batch": classic BN (caller threads ``batch_stats``);
    - "none": normalizer-free — weight-standardized convs (common.WSConv)
      + SkipInit residual scaling (common.IdentityNorm); no activation
      statistics at all, the HBM-optimal variant (NF-ResNet recipe).
    """
    stage_sizes: Sequence[int] = (3, 4, 6, 3)
    num_classes: int = 1000
    num_filters: int = 64
    bottleneck: bool = True
    small_inputs: bool = False
    norm: str = "group"
    dtype: str = "bfloat16"
    # "s2d": 2x2 space-to-depth stem — the 7x7/s2 conv over 3-channel
    # images runs the MXU at 3/128 input-lane efficiency; reshaping to
    # [H/2, W/2, 12] and using a 4x4/s1 conv (same output shape, ~8x8/s2
    # receptive field) is the standard TPU ResNet stem optimization
    # (MLPerf space-to-depth trick).
    stem: str = "conv"
    # None = classifier head over the classic stride-32 backbone.
    # 16 (or 8) trades the last one (two) stage strides for dilation —
    # the DeepLab-style dense-prediction backbone: same receptive field,
    # higher-resolution features, still static NHWC shapes for the MXU.
    output_stride: Optional[int] = None
    # True: return the final feature map instead of pooled class logits
    # (the feature-extractor seam models.deeplab consumes — one backbone,
    # so norm="none"/WSConv and the s2d stem reach every consumer).
    features_only: bool = False

    @nn.compact
    def __call__(self, x, train=False):
        dtype = jnp.dtype(self.dtype)
        conv = functools.partial(nn.Conv, use_bias=False, padding="SAME",
                                 dtype=dtype)
        if self.norm == "batch":
            norm = functools.partial(nn.BatchNorm, use_running_average=not train,
                                     momentum=0.9, epsilon=1e-5,
                                     dtype=jnp.float32)
        elif self.norm == "none":
            from .common import IdentityNorm, WSConv
            conv = functools.partial(WSConv, dtype=self.dtype)
            norm = IdentityNorm
        else:
            from .common import ChannelGroupNorm
            norm = ChannelGroupNorm
        act = nn.relu
        block_cls = BottleneckBlock if self.bottleneck else ResNetBlock

        x = x.astype(dtype)
        if self.small_inputs:
            x = conv(self.num_filters, (3, 3), name="conv_init")(x)
        elif self.stem == "s2d":
            n, h, w, c = x.shape
            x = x.reshape(n, h // 2, 2, w // 2, 2, c)
            x = x.transpose(0, 1, 3, 2, 4, 5).reshape(n, h // 2, w // 2,
                                                      4 * c)
            x = conv(self.num_filters, (4, 4), name="conv_init")(x)
        else:
            x = conv(self.num_filters, (7, 7), (2, 2), name="conv_init")(x)
        x = norm(name="norm_init")(x)
        with jax.named_scope("stem"):
            x = act(x)
            if not self.small_inputs:
                x = nn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")
        # with output_stride, the last N stages trade their stride-2 for
        # dilation: stride 32 -> 16 dilates the last stage, -> 8 the last
        # two (the striding stages are 1..len-1; the stem contributes /4)
        n_dilated = 0
        if self.output_stride is not None:
            if self.output_stride not in (8, 16):
                raise ValueError("output_stride must be 8, 16, or None")
            n_dilated = {16: 1, 8: 2}[self.output_stride]
        for i, block_count in enumerate(self.stage_sizes):
            dilated = i >= len(self.stage_sizes) - n_dilated
            stage_conv = (functools.partial(conv, kernel_dilation=(2, 2))
                          if dilated else conv)
            for j in range(block_count):
                strides = 2 if (i > 0 and j == 0 and not dilated) else 1
                x = block_cls(self.num_filters * 2 ** i, conv=stage_conv,
                              norm=norm, act=act, strides=strides,
                              name=f"stage{i}_block{j}")(x)
        if self.features_only:
            return x
        x = jnp.mean(x, axis=(1, 2)).astype(jnp.float32)
        return nn.Dense(self.num_classes, dtype=jnp.float32, name="head")(x)


def ResNet50(num_classes=1000, **kwargs):
    """ImageNet ResNet-50 — the BASELINE.json north-star workload."""
    return ResNet(stage_sizes=(3, 4, 6, 3), num_classes=num_classes,
                  bottleneck=True, **kwargs)


def ResNet56Cifar(num_classes=10, **kwargs):
    """CIFAR ResNet-56 — parity with the reference's resnet example
    (examples/resnet/resnet_cifar_dist.py trains resnet56 on CIFAR-10):
    3 stages x 9 basic blocks, 16 base filters, 3x3 stem."""
    return ResNet(stage_sizes=(9, 9, 9), num_classes=num_classes,
                  num_filters=16, bottleneck=False, small_inputs=True,
                  **kwargs)
