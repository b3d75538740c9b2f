"""The operation and byte counts the utilisation metrics rest on."""
import json
import math
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import toy  # noqa: E402
import harness  # noqa: E402
import traffic  # noqa: E402


def family(name):
    cfg = traffic.load("configs", name)
    return cfg, harness.load_module("families", cfg["family"])


def test_resnet50_count_is_the_published_one():
    cfg, fam = family("resnet50-gn")
    work = fam.step_work(cfg, 256)
    # He et al. give 3.8e9 multiply-adds for the v1 model; v1.5 (stride on
    # the 3x3) is the 4.09e9 everyone quotes.  Two operations a multiply-add,
    # backward twice the forward.
    assert work["forward_macs_per_image"] == pytest.approx(4.09e9, rel=0.02)
    assert work["flops"] / 256 == pytest.approx(3 * 2 * 4.09e9, rel=0.02)
    # the compiled B=256 step for the described v5e:2x2 counts 6.14e12
    # (ISSUE 25, sandbox compile, not a chip run): the compiler also counts
    # normalisation and the optimizer, a few percent
    assert work["flops"] == pytest.approx(6.14e12, rel=0.05)
    assert work["n_params"] == 25557032


def test_gpt2_large_count_leaves_out_gathers_and_recomputation():
    cfg, fam = family("gpt2-large")
    work = fam.step_work(cfg, 8)
    d, ff, v, s, n = 1280, 5120, 50257, 1024, 36
    assert work["n_params"] == 838359040
    # the matmul parameters hold neither wte nor wpe, nor biases and norms
    assert work["matmul_params"] == n * (4 * d * d + 2 * d * ff) + d * v
    assert work["matmul_params"] < work["n_params"] - v * d - s * d
    tokens = 8 * s
    attn = n * 8 * 6 * s * s * d
    # 6 N T and half of the attention square: with remat the chip executes
    # about a third more, which is not required work and is not counted
    assert work["flops"] == 6 * work["matmul_params"] * tokens + attn
    assert work["flash"]["flops"] == attn
    assert work["adamw"]["bytes"] == work["n_params"] * 24   # bf16 mu
    assert work["flops"] == pytest.approx(40.3e12, rel=0.01)


def test_work_scales_with_the_batch():
    for name in ("gpt2-large", "resnet50-gn"):
        cfg, fam = family(name)
        assert fam.step_work(cfg, 32)["flops"] == \
            4 * fam.step_work(cfg, 8)["flops"]


def test_reference_shapes_are_the_programs():
    """The paths and shapes the benchmark makes weights for are the ones the
    program's own init gives (toy size; names are what the adaptor needs)."""
    import jax
    import jax.numpy as jnp

    import weights
    from tensorflowonspark_tpu.models.resnet import ResNet
    from tensorflowonspark_tpu.models.transformer import (
        Transformer, TransformerConfig)

    cfg = toy.lm_config()
    fam = harness.load_module("families", "transformer_lm")
    model = Transformer(TransformerConfig(**cfg["program"]["model"]))
    theirs = jax.eval_shape(lambda k: model.init(
        k, jnp.zeros((1, 8), jnp.int32))["params"], jax.random.key(0))
    ours = fam.param_shapes(cfg)
    assert {k: v.shape for k, v in weights.flatten(theirs).items()} == \
        {k: s for k, (s, _) in ours.items()}

    cfg = toy.resnet_config()
    fam = harness.load_module("families", "resnet")
    m = dict(cfg["program"]["model"], stage_sizes=tuple(cfg["stage_sizes"]))
    theirs = jax.eval_shape(lambda k: ResNet(**m).init(
        k, jnp.zeros((1, 32, 32, 3), jnp.bfloat16))["params"],
        jax.random.key(0))
    ours = fam.param_shapes(cfg)
    assert {k: v.shape for k, v in weights.flatten(theirs).items()} == \
        {k: s for k, (s, _) in ours.items()}


def test_peaks_table_is_keyed_by_exact_kind():
    with open(os.path.join(toy.BENCH, "peaks.json")) as f:
        peaks = json.load(f)
    v5e = peaks["devices"]["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert math.isclose(v5e["ici_bytes_per_s"] * 8, 1600e9)
    assert "source" in peaks and "default" not in peaks["devices"]
