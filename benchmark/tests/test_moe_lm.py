"""The `moe_lm` family at toy size on the CPU: a whole run through
`cluster.run` is `correct`, each way of breaking the timed path is not
(half batch, state unchanged, one expert's output zeroed), the control and
the planted faults are not, and the counts of `step_work` are the ones a
count by hand gives."""
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import control  # noqa: E402
import toy  # noqa: E402
import toy_moe  # noqa: E402
import harness  # noqa: E402
import traffic  # noqa: E402
from test_rehearsal import cache_env  # noqa: E402,F401  (a fixture)


def drive(**kw):
    spec = toy_moe.spec(**kw)
    return spec, harness.drive(spec, start_method="spawn", timeout=280)


def test_rehearsal_is_correct_and_counts_its_routing(cache_env):  # noqa: F811
    from tensorflowonspark_tpu import trace

    spec, r = drive()
    assert r["correct"], r["numbers"]
    w, tr = r["window"], spec.traffic
    assert w["steps"] >= 2 and w["compiles_in_window"] == 0
    assert r["info"]["n_params"] == sum(
        int(__import__("math").prod(s)) for s, _ in
        harness.load_module("families", "moe_lm").param_shapes(
            spec.config).values())
    # the node's counters reach this (the driver's) process: every (token,
    # pick) pair of every layer of every step the compiled step object took,
    # once (remat runs the forward twice), on a held expert or an absent one
    node = [rep for rep in trace.collected()
            if str(rep.get("source", "")).startswith("node")][-1]
    c = node["counters"]
    steps = tr["check_steps"] + tr["warm_steps"] + w["steps"]
    pairs = tr["batch"] * tr["units_per_record"] * 2 * 4 * steps
    assert c["moe.pairs.local"] + c["moe.pairs.absent"] == pairs
    assert 0 < c["moe.pairs.local"] < pairs
    assert c["moe.load.max"] >= c["moe.load.mean"] > 0


@pytest.mark.parametrize("fault,zero_expert,caught_by", [
    ("state_unchanged", None, "update_norm_gap"),
    ("half_batch", None, "grad_norm_gap"),
    (None, 1, "grad_norm_gap"),
])
def test_broken_timed_path_is_not_correct(cache_env, fault, zero_expert,  # noqa: F811
                                          caught_by):
    _, r = drive(fault=fault, zero_expert=zero_expert)
    assert not r["correct"], r["numbers"]
    n = r["numbers"][caught_by]
    assert n["value"] > n["limit"], r["numbers"]


def test_control_and_half_batch_are_not_correct():
    spec = toy_moe.spec()
    got = control.readings(spec, seed=7, which=("control", "half_batch"))
    for name, (correct, numbers, _) in got.items():
        assert not correct, (name, numbers)


def test_step_work_is_the_count_by_hand():
    cfg = traffic.load("configs", "mellum2-12b-a2.5b")
    fam = harness.load_module("families", cfg["family"])
    work = fam.step_work(cfg, 2)
    t, s, w = 16384, 8192, 1024
    assert work["n_params"] == 595153152
    attn_layer = 2 * 2304 * 4096 + 2 * 2304 * 512        # q, o; k, v
    assert attn_layer == 21233664
    # visible pairs a row: a window layer sees min(i + 1, 1024) keys
    window = sum(min(i + 1, w) for i in range(s))
    assert fam.visible_pairs(s, w) == window == 7864832
    assert fam.visible_pairs(s) == s * (s + 1) // 2
    pairs = 2 * (3 * window + s * (s + 1) // 2)
    assert work["visible_pairs"] == pairs
    # a token's 8 picks fall on the 16 held of 64 experts a quarter of the
    # time: 2 a token a layer
    assert work["local_pairs"] == 4 * t * 2
    macs_token = (4 * (attn_layer + 2304 * 64) + 2304 * 24576   # projections
                  + 4 * 2 * 3 * 2304 * 896)                     # held experts
    assert macs_token == pytest.approx(84.9e6 + 0.6e6 + 56.6e6 + 49.5e6,
                                       rel=0.002)
    assert work["flops"] == 6 * macs_token * t + 12 * pairs * 4096
    assert work["flops"] == pytest.approx(24.5e12, rel=0.01)
    assert work["flash"]["flops"] == 12 * pairs * 4096
    assert work["moe_gmm"]["flops"] == 4 * t * 2 * 3 * 6 * 2304 * 896
    assert work["adamw"]["bytes"] == work["n_params"] * 24      # bf16 mu
    assert fam.step_work(cfg, 4)["flops"] == 2 * work["flops"]


def test_reference_shapes_are_the_programs():
    import jax
    import jax.numpy as jnp

    import weights
    from tensorflowonspark_tpu.models.transformer import (
        Transformer, TransformerConfig)

    cfg = toy_moe.config()
    fam = harness.load_module("families", "moe_lm")
    model = Transformer(TransformerConfig(**cfg["program"]["model"]))
    theirs = jax.eval_shape(lambda k: model.init(
        k, jnp.zeros((1, 8), jnp.int32))["params"], jax.random.key(0))
    ours = fam.param_shapes(cfg)
    assert {k: v.shape for k, v in weights.flatten(theirs).items()} == \
        {k: s for k, (s, _) in ours.items()}


def test_a_reduced_configuration_states_its_cut():
    """`test_contract.py` holds every configuration to `reduced == []`;
    for one that is cut: file and `BENCHMARK.json` agree, and each reduced
    key has its published value and the deployment beside it."""
    with open(os.path.join(toy.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cut = [c for c in bench["configs"] if c["reduced"]]
    assert [c["name"] for c in cut] == ["mellum2-12b-a2.5b"]
    for c in cut:
        cfg = json.load(open(os.path.join(toy.ROOT, c["file"])))
        assert cfg["reduced"] == c["reduced"] and len(c["reduced"]) <= 16
        assert c["source"] in cfg["source"]
        for key in c["reduced"]:
            assert cfg["published"][key] > cfg[key] > 0
            assert str(cfg["published"][key]) in cfg["deployment"][key]
            assert not key.endswith(("_dim", "_rank", "_size")) or \
                key == "vocab_size"
        assert cfg["assumed"] and cfg["departures"]
        dep = cfg["deployment"]
        assert dep["chips_sharing_a_layer"] * cfg["num_experts"] == \
            cfg["published"]["num_experts"]
        assert dep["pipeline_stages"] * dep["layers_a_stage"] == \
            cfg["published"]["num_hidden_layers"]
        # the floors: a whole period, 8 routed experts, an eighth of the ids
        period = cfg["layer_types"][:cfg["num_hidden_layers"]]
        assert set(period) == set(cfg["layer_types"])
        assert cfg["num_experts"] >= 8
        assert cfg["vocab_size"] * 8 >= cfg["published"]["vocab_size"]
        # what the program is built with is the file's own numbers
        m = cfg["program"]["model"]
        assert (m["d_model"], m["n_heads"], m["n_kv_heads"], m["head_dim"],
                m["moe_d_ff"], m["moe_top_k"], m["num_experts"],
                m["moe_experts_held"], m["n_layers"], m["vocab_size"],
                m["sliding_window"]) == (
            cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["moe_intermediate_size"], cfg["num_experts_per_tok"],
            cfg["published"]["num_experts"], cfg["num_experts"],
            cfg["num_hidden_layers"], cfg["vocab_size"],
            cfg["sliding_window"])
        assert m["layer_types"] == period


def test_exposed_collective_reader_and_the_new_metric_files():
    """`allreduce_exposed_ms.lm` a step from `tracered.reduce`'s seconds
    over the traced steps; nothing without a trace.  Every metric this
    family's cell lists has its file and its reader."""
    import argparse

    reader = harness.load_module("metrics", "exposed_collective_ms")
    spec = argparse.Namespace(traffic={"trace_steps": 4})
    run = {"spec": spec, "result": {"trace": {"exposed_collective_s": 0.02}}}
    assert reader.read(run) == pytest.approx(5.0)
    assert reader.read({"spec": spec, "result": {}}) is None
    cell = harness.load_spec("mellum2-12b-a2.5b.fed_s8k_b2", 1, 1, 1)
    names = {m["name"] for m in cell.per_layer}
    assert {"step_mfu.moe", "flash_roofline.moe", "flash_kernel_ms.moe",
            "moe_gmm_roofline.moe", "moe_gmm_kernel_ms.moe",
            "adamw_kernel_ms.moe", "moe_local_pairs_pct.moe",
            "device_idle_pct.moe", "feed_wait_pct.moe"} <= names
    for name in names:
        desc = traffic.load("metrics", name)
        assert harness.load_module("metrics", desc["reader"]).read
    # the kernels' names as the device trace spells them
    import re
    gmm = traffic.load("metrics", "moe_gmm_kernel_ms.moe")["args"]["pattern"]
    call = ' = bf16[8,8]{1,0} custom-call(), custom_call_target="tpu_custom_call"'
    assert re.search(gmm, "%transpose_jvp_moe_tgmm__.3" + call)
    assert re.search(gmm, "%moe_gmm.38" + call)
    assert not re.search(gmm, "%flash_fwd.11" + call)
    flash = traffic.load("metrics", "flash_kernel_ms.moe")["args"]["pattern"]
    assert re.search(flash, "%flash_dkv.4" + call)
    assert not re.search(flash, "%adamw_fused.85" + call)
