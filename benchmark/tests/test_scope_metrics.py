"""The metric files that read the step's `jax.named_scope`s (`route`,
`dispatch`, `experts`, `combine`, `cast` under the expert layer,
`unembed_xent`, `optimizer`): each file's `args` through the one reader,
`metrics/region_ms.py`, over a made-up split that holds the module keys a
program with the scopes gives; on a split without them (the parent's) every
one reads nothing.
"""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import toy  # noqa: E402,F401  (puts benchmark/ on the path)
import harness  # noqa: E402
import traffic  # noqa: E402

region_ms = harness.load_module("metrics", "region_ms")
SUFFIXES = ("moe", "lfm", "joy")


def row(s, **kernels):
    return {"s": s, "kernels": kernels}


# seconds of a made-up sparse step with a prediction module; the kernels'
# own seconds are part of their region's
SCOPED = {
    "forward/layer/moe/route": row(0.010),
    "recomputed/layer/moe/route": row(0.008),
    "backward/layer/moe/route": row(0.002),
    "forward/mtp_block/moe/route": row(0.003),
    "forward/layer/moe/dispatch": row(0.020),
    "backward/layer/moe/dispatch": row(0.030),
    "recomputed/layer/moe/dispatch": row(0.020),
    "forward/layer/moe/combine": row(0.025),
    "backward/mtp_block/moe/combine": row(0.035),
    "forward/layer/moe/experts": row(0.050, **{"moe_gmm (pallas)": 0.030}),
    "backward/layer/moe/experts": row(
        0.090, **{"moe_gmm (pallas)": 0.020, "moe_tgmm (pallas)": 0.025}),
    "recomputed/layer/moe/experts": row(
        0.030, **{"jvp_moe_gmm (pallas)": 0.015}),
    "backward/layer/moe/cast": row(0.006),
    "recomputed/layer/moe/cast": row(0.004),
    # what stays filed at the layer: the router, the scores' softmax, the
    # gradient sums
    "forward/layer/moe/router": row(0.002),
    "backward/layer/moe/router": row(0.003),
    "forward/layer/moe": row(0.004),
    "backward/layer/moe": row(0.011),
    "forward/layer/moe/shared": row(0.040),
    "forward/unembed_xent": row(0.012),
    "backward/unembed_xent": row(0.045),
    "forward/lm_head": row(0.007),
    "forward/-": row(0.009),
    "rest/optimizer": row(0.005),
    "rest/optimizer/adamw_fused": row(
        0.026, **{"adamw_fused (pallas)": 0.024}),
    "rest/-": row(0.015),
}
UNSCOPED = {
    "forward/layer/moe": row(0.2, **{"moe_gmm (pallas)": 0.05}),
    "backward/layer/moe/router": row(0.003),
    "forward/-": row(0.06),
    "rest/adamw_fused": row(0.026, **{"adamw_fused (pallas)": 0.024}),
    "rest/-": row(0.02),
}


def seconds(metric, regions=SCOPED):
    args = dict(traffic.load("metrics", metric)["args"])
    args.pop("share", None)
    return region_ms.compute(regions, **args)


@pytest.mark.parametrize("suffix", SUFFIXES)
def test_route_reads_the_route_scope_in_every_pass(suffix):
    assert seconds(f"moe_route_ms.{suffix}") == pytest.approx(
        0.010 + 0.008 + 0.002 + 0.003)


@pytest.mark.parametrize("suffix", SUFFIXES)
def test_dispatch_reads_dispatch_and_combine(suffix):
    assert seconds(f"moe_dispatch_ms.{suffix}") == pytest.approx(
        0.020 + 0.030 + 0.020 + 0.025 + 0.035)


@pytest.mark.parametrize("suffix", SUFFIXES)
def test_experts_outside_gmm_leaves_the_kernels_out(suffix):
    assert seconds(f"moe_experts_outside_gmm_ms.{suffix}") == pytest.approx(
        (0.050 - 0.030) + (0.090 - 0.045) + (0.030 - 0.015) + 0.006 + 0.004)


@pytest.mark.parametrize("suffix", SUFFIXES)
def test_phases_and_remainder_sum_to_outside_gmm(suffix):
    """The three phases and what is still filed at the layer (the router's
    product, the scores, the gradient sums) are `moe_outside_gmm_ms`'s
    reading of the same split, the shared expert in none of them."""
    remainder = 0.002 + 0.003 + 0.004 + 0.011
    assert (seconds(f"moe_route_ms.{suffix}")
            + seconds(f"moe_dispatch_ms.{suffix}")
            + seconds(f"moe_experts_outside_gmm_ms.{suffix}")
            + remainder) == pytest.approx(
                seconds(f"moe_outside_gmm_ms.{suffix}"))


@pytest.mark.parametrize("suffix", SUFFIXES)
def test_head_reads_the_fused_loss_and_is_blind_to_lm_head(suffix):
    assert seconds(f"head_ms.{suffix}") == pytest.approx(0.012 + 0.045)
    assert seconds("head_ms.lm") == pytest.approx(0.007)


def test_optimizer_outside_kernel_leaves_the_adamw_kernels_out():
    assert seconds("optimizer_outside_kernel_ms") == pytest.approx(
        0.005 + (0.026 - 0.024))
    # and the accepted share of unnamed time no longer holds either
    assert seconds("device_unnamed_pct") == pytest.approx(0.009 + 0.015)


NEW = ([f"{m}.{s}" for m in ("moe_route_ms", "moe_dispatch_ms",
                             "moe_experts_outside_gmm_ms", "head_ms")
        for s in SUFFIXES] + ["optimizer_outside_kernel_ms"])


@pytest.mark.parametrize("metric", NEW)
def test_reads_nothing_on_a_program_without_the_scopes(metric):
    assert seconds(metric, UNSCOPED) is None
    run = {"result": {"trace": {"regions": UNSCOPED}}, "spec": None}
    assert region_ms.read(run, **traffic.load("metrics",
                                              metric)["args"]) is None
