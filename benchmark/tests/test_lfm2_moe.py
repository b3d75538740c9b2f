"""The `lfm2_moe` family at toy size on the CPU: a whole run through
`cluster.run` is `correct` and counts its routing and its mixers, each way
of breaking it is not (the timed path: half batch, state unchanged; the
mathematics: the selection bias left out of the choice or left in the
weights, a tap of the convolution zeroed, the query/key norm left out, an
expert's output zeroed, planted in the reference), the fp8 control is not,
and the counts of `step_work` are the ones a count by hand gives."""
import json
import math
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import faults  # noqa: E402
import toy  # noqa: E402
import toy_lfm2  # noqa: E402
import harness  # noqa: E402
import traffic  # noqa: E402
from test_rehearsal import cache_env  # noqa: E402,F401  (a fixture)

FAMILY = harness.load_module("families", "lfm2_moe")
CELL = "lfm2-8b-a1b.fed_s8k_b2"


def drive(**kw):
    spec = toy_lfm2.spec(**kw)
    return spec, harness.drive(spec, start_method="spawn", timeout=280)


def test_rehearsal_is_correct_and_counts_routing_and_mixers(cache_env):  # noqa: F811
    from tensorflowonspark_tpu import trace

    spec, r = drive()
    assert r["correct"], r["numbers"]
    w, tr = r["window"], spec.traffic
    assert w["steps"] >= 2 and w["compiles_in_window"] == 0
    assert r["info"]["n_params"] == sum(
        math.prod(s) for s, _ in FAMILY.param_shapes(spec.config).values())
    node = [rep for rep in trace.collected()
            if str(rep.get("source", "")).startswith("node")][-1]
    c = node["counters"]
    steps = tr["check_steps"] + tr["warm_steps"] + w["steps"]
    pairs = tr["batch"] * tr["units_per_record"] * 2 * 4 * steps
    assert c["moe.pairs.local"] + c["moe.pairs.absent"] == pairs
    assert c["moe.picks.moved"] + c["moe.picks.kept"] == pairs
    assert 0 < c["moe.picks.moved"] < pairs / 2
    assert 0 < c["moe.pairs.local"] < pairs
    # what the step program was built of: four conv mixers to one attention
    assert c["mixer.calls.conv"] == 4 * c["mixer.calls.attention"] > 0
    # the readers of the cell's two routing metrics find their counters
    ratio = harness.load_module("metrics", "counter_ratio")
    loaded = {"node": [node]}
    for metric, counter in (("moe_biased_picks_pct.lfm", "moe.picks.moved"),
                            ("moe_local_pairs_pct.lfm", "moe.pairs.local")):
        args = traffic.load("metrics", metric)["args"]
        assert ratio.compute(loaded, **args) == pytest.approx(
            100.0 * c[counter] / pairs)
    # a program without the counters (the parent): nothing, and no error
    assert ratio.compute({"node": [{"counters": {"moe.pairs.local": 3}}]},
                         **traffic.load("metrics",
                                        "moe_biased_picks_pct.lfm")["args"]) \
        is None


@pytest.mark.parametrize("fault,reference_fault,caught_by", [
    ("state_unchanged", None, "update_norm_gap"),
    ("half_batch", None, "grad_norm_gap"),
    (None, "bias_out_of_choice", "grad_norm_gap"),
    (None, "bias_in_weights", "grad_norm_gap"),
    (None, "tap_zeroed", "grad_norm_gap"),
    (None, "no_qk_norm", "grad_norm_gap"),
    (None, "zero_expert", "grad_norm_gap"),
])
def test_a_broken_run_is_not_correct(cache_env, fault, reference_fault,  # noqa: F811
                                     caught_by):
    _, r = drive(fault=fault, reference_fault=reference_fault)
    assert not r["correct"], r["numbers"]
    n = r["numbers"][caught_by]
    assert n["value"] > n["limit"], r["numbers"]


def test_control_and_every_planted_fault_read_not_correct():
    spec = toy_lfm2.spec()
    got = faults.readings(spec, seed=7)
    assert set(got) == {"control", "half_batch"} | set(FAMILY.FAULTS)
    for name, (correct, numbers, _) in got.items():
        assert not correct, (name, numbers)


def test_step_work_is_the_count_by_hand():
    cfg = traffic.load("configs", "lfm2-8b-a1b")
    work = FAMILY.step_work(cfg, 2)
    t, s, d = 16384, 8192, 2048
    assert work["n_params"] == 507820288
    conv = d * 3 * d + d * d                     # in_proj, out_proj
    attn = 2 * d * d + 2 * d * 512               # q, o; k, v
    dense, head, router = 3 * d * 7168, d * 16384, d * 32
    assert (conv, attn, dense, head) == (16777216, 10485760, 44040192,
                                         33554432)
    pairs = 2 * s * (s + 1) // 2                 # one attention layer, 2 rows
    assert work["visible_pairs"] == pairs
    # a token's 4 picks fall on the 8 held of 32 experts a quarter of the
    # time: 1 a token a sparse layer
    assert work["local_pairs"] == 4 * t
    macs_token = 4 * conv + attn + dense + 4 * router + head
    gmm = 4 * t * 3 * 6 * d * 1792
    assert work["flops"] == 6 * macs_token * t + 12 * pairs * d + gmm
    assert work["flops"] == pytest.approx(21.3e12, rel=0.005)
    assert work["flash"]["flops"] == 12 * pairs * d == pytest.approx(
        1.65e12, rel=0.005)
    assert work["moe_gmm"]["flops"] == gmm == pytest.approx(4.33e12,
                                                            rel=0.005)
    assert work["flash"]["bytes"] == 6 * t * (2048 + 512) * 2
    assert work["moe_gmm"]["bytes"] == 4 * 9 * (
        8 * d * 1792 + t * (d + 1792)) * 2
    assert work["adamw"]["bytes"] == work["n_params"] * 24      # bf16 mu
    # the shares the cell's `why` states
    share = {"conv": 6 * 4 * conv * t, "experts": gmm,
             "dense": 6 * dense * t, "head": 6 * head * t,
             "attention": 6 * attn * t + 12 * pairs * d}
    assert {k: round(100 * v / work["flops"]) for k, v in share.items()} == {
        "conv": 31, "experts": 20, "dense": 20, "head": 16, "attention": 13}
    assert FAMILY.step_work(cfg, 4)["flops"] == 2 * work["flops"]
    # and at the toy's size
    toy_cfg = toy_lfm2.config()
    w = FAMILY.step_work(toy_cfg, 4)
    tt, dd = 4 * 48, 64
    macs = (4 * (dd * 3 * dd + dd * dd) + 2 * dd * dd + 2 * dd * 32
            + 3 * dd * 128 + 4 * dd * 8 + dd * 256)
    assert w["local_pairs"] == 4 * tt * 2 * 4 // 8
    assert w["flops"] == 6 * macs * tt + 12 * (4 * 48 * 49 // 2) * dd + \
        w["local_pairs"] * 18 * dd * 48


def test_reference_shapes_are_the_programs():
    import jax
    import jax.numpy as jnp

    import weights
    from tensorflowonspark_tpu.models.transformer import (
        Transformer, TransformerConfig)

    cfg = toy_lfm2.config()
    model = Transformer(TransformerConfig(**cfg["program"]["model"]))
    theirs = jax.eval_shape(lambda k: model.init(
        k, jnp.zeros((1, 8), jnp.int32))["params"], jax.random.key(0))
    ours = FAMILY.param_shapes(cfg)
    assert {k: v.shape for k, v in weights.flatten(theirs).items()} == \
        {k: s for k, (s, _) in ours.items()}


def test_the_configuration_states_its_cut_and_the_catalogs_numbers():
    """File and `BENCHMARK.json` agree; each reduced key has its published
    value and the deployment beside it; the floors hold; what the program
    is built with is the file's own numbers."""
    with open(os.path.join(toy.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == "lfm2-8b-a1b")
    cfg = json.load(open(os.path.join(toy.ROOT, entry["file"])))
    assert cfg["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "num_dense_layers", "num_experts", "vocab_size"]
    assert entry["source"] in cfg["source"]
    for key in entry["reduced"]:
        assert cfg["published"][key] > cfg[key] > 0
        assert str(cfg["published"][key]) in cfg["deployment"][key]
    assert cfg["assumed"] and cfg["departures"]
    # every width as published
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"], cfg["num_experts_per_tok"],
            cfg["conv_L_cache"], cfg["conv_bias"], cfg["norm_eps"],
            cfg["rope_theta"], cfg["routed_scaling_factor"],
            cfg["norm_topk_prob"], cfg["use_expert_bias"]) == (
        2048, 32, 8, 7168, 1792, 4, 3, False, 1e-5, 1000000, 1, True, True)
    assert len(cfg["layer_types"]) == cfg["published"]["num_hidden_layers"]
    dep = cfg["deployment"]
    assert dep["chips_sharing_a_layer"] * cfg["num_experts"] == \
        cfg["published"]["num_experts"]
    assert len(dep["layers_by_stage"]) == dep["pipeline_stages"]
    assert sum(dep["layers_by_stage"]) == cfg["published"]["num_hidden_layers"]
    # the floors: a whole period and four layers behind the dense ones, 8
    # routed experts, an eighth of the ids
    z = FAMILY._sizes(cfg)
    assert set(z["kinds"]) == set(cfg["layer_types"])
    assert z["n"] - z["dense"] >= 4 and z["kinds"][z["dense"]:] == [
        "full_attention", "conv", "conv", "conv"]
    assert cfg["num_experts"] >= 8
    assert cfg["vocab_size"] * 8 >= cfg["published"]["vocab_size"]
    m = cfg["program"]["model"]
    assert (m["d_model"], m["n_heads"], m["n_kv_heads"], m["d_ff"],
            m["moe_d_ff"], m["moe_top_k"], m["num_experts"],
            m["moe_experts_held"], m["moe_expert_offset"], m["n_layers"],
            m["moe_dense_layers"], m["vocab_size"], m["conv_kernel"],
            m["ln_eps"], m["rope_theta"]) == (
        cfg["hidden_size"], cfg["num_attention_heads"],
        cfg["num_key_value_heads"], cfg["intermediate_size"],
        cfg["moe_intermediate_size"], cfg["num_experts_per_tok"],
        cfg["published"]["num_experts"], cfg["num_experts"],
        dep["this_chip"]["expert_offset"], cfg["num_hidden_layers"],
        cfg["num_dense_layers"], cfg["vocab_size"], cfg["conv_L_cache"],
        cfg["norm_eps"], cfg["rope_theta"])
    assert cfg["routed_scaling_factor"] == 1     # the program has no field
    assert m["layer_types"] == z["kinds"]
    assert m["qk_norm"] and m["tie_embeddings"] and m["moe_expert_bias"]
    assert m["moe_scoring"] == "sigmoid" and not m["use_bias"]


def test_the_cells_metric_files_name_readers_that_exist():
    cell = harness.load_spec(CELL, 1, 1, 1)
    names = {m["name"] for m in cell.per_layer}
    assert {"step_mfu.lfm", "flash_roofline.lfm", "flash_kernel_ms.lfm",
            "moe_gmm_roofline.lfm", "moe_gmm_kernel_ms.lfm",
            "adamw_kernel_ms.lfm", "moe_local_pairs_pct.lfm",
            "moe_biased_picks_pct.lfm", "device_idle_pct.lfm",
            "feed_wait_pct.lfm", "flash_packed_calls_pct", "launch_s",
            "compile_s", "node_rendezvous_s"} <= names
    assert {m["name"] for m in cell.end_to_end} == {"tokens_per_s",
                                                    "setup_s"}
    for name in names:
        desc = traffic.load("metrics", name)
        assert harness.load_module("metrics", desc["reader"]).read
    # the `.lfm` files read what the `.moe` ones read
    for base in ("flash_roofline", "flash_kernel_ms", "moe_gmm_roofline",
                 "moe_gmm_kernel_ms", "adamw_kernel_ms",
                 "moe_local_pairs_pct"):
        assert traffic.load("metrics", base + ".lfm") == \
            traffic.load("metrics", base + ".moe")
