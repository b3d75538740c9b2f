"""The `mla_moe` family at toy size on the CPU: a whole run through
`cluster.run` is `correct` and counts its routing, its latent mixers, its
shared experts and the two terms of its loss; each way of breaking it is not
(the timed path: half batch, state unchanged; the mathematics, planted in
the reference: a rotary key a head, rotary on every lane, the softmax scale
taken from the value's width, the scaling factor or the shared expert or the
second loss left out, the second loss on the next token, the bias in the
weights, an expert's output zeroed), the fp8 control is not, and the counts
of `step_work` are the ones a count by hand gives."""
import json
import math
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import faults  # noqa: E402
import toy  # noqa: E402
import toy_mla  # noqa: E402
import harness  # noqa: E402
import traffic  # noqa: E402
from test_rehearsal import cache_env  # noqa: E402,F401  (a fixture)

FAMILY = harness.load_module("families", "mla_moe")
CELL = "joyai-llm-flash.fed_s8k_b2"


def drive(**kw):
    spec = toy_mla.spec(**kw)
    return spec, harness.drive(spec, start_method="spawn", timeout=280)


def test_rehearsal_is_correct_and_counts_what_the_step_was_built_of(cache_env):  # noqa: F811
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
    # rows x tokens x picks x sparse layers (two and the module's)
    pairs = tr["batch"] * tr["units_per_record"] * 4 * 3 * steps
    assert c["moe.pairs.local"] + c["moe.pairs.absent"] == pairs
    assert c["moe.picks.moved"] + c["moe.picks.kept"] == pairs
    assert 0 < c["moe.picks.moved"] < pairs / 2
    assert 0 < c["moe.pairs.local"] < pairs
    # what the step program was built of: four latent mixers (three layers
    # and the module's), three shared experts, no other attention, and the
    # latent kernels for all of them (forward, dq, dk/dv: traced once)
    assert c["mixer.calls.latent"] % 4 == 0 < c["mixer.calls.latent"]
    assert c["moe.shared.calls"] * 4 == c["mixer.calls.latent"] * 3
    assert "mixer.calls.attention" not in c
    assert c["flash.calls.latent"] >= 3
    assert not c.get("flash.calls.packed") and not c.get(
        "flash.calls.transposed")
    # the two terms of the loss as they were summed, over every step
    assert c["loss.terms.next1"] > 0 and c["loss.terms.next2"] > 0
    share = c["loss.terms.next2"] / (c["loss.terms.next1"]
                                     + c["loss.terms.next2"])
    assert 0.05 < share < 0.15        # 0.1 x a loss near the first's
    # the readers of the cell's counter metrics find their counters
    ratio = harness.load_module("metrics", "counter_ratio")
    loaded = {"node": [node]}
    for metric, want in (
            ("moe_biased_picks_pct.joy", 100.0 * c["moe.picks.moved"] / pairs),
            ("moe_local_pairs_pct.joy", 100.0 * c["moe.pairs.local"] / pairs),
            ("flash_latent_calls_pct.joy", 100.0),
            ("mtp_loss_pct.joy", 100.0 * share),
            ("flash_packed_calls_pct", 0.0)):
        args = traffic.load("metrics", metric)["args"]
        assert ratio.compute(loaded, **args) == pytest.approx(want), metric
    # a program without the counters (the parent): nothing, and no error
    for metric in ("flash_latent_calls_pct.joy", "mtp_loss_pct.joy"):
        assert ratio.compute(
            {"node": [{"counters": {"moe.pairs.local": 3}}]},
            **traffic.load("metrics", metric)["args"]) is None


@pytest.mark.parametrize("fault,reference_fault,caught_by", [
    ("state_unchanged", None, "update_norm_gap"),
    ("half_batch", None, "grad_norm_gap"),
] + [(None, name, "grad_norm_gap") for name in FAMILY.FAULTS])
def test_a_broken_run_is_not_correct(cache_env, fault, reference_fault,  # noqa: F811
                                     caught_by):
    _, r = drive(fault=fault, reference_fault=reference_fault)
    assert not r["correct"], r["numbers"]
    n = r["numbers"][caught_by]
    assert n["value"] > n["limit"], r["numbers"]


def test_control_and_every_planted_fault_read_not_correct():
    spec = toy_mla.spec()
    got = faults.readings(spec, seed=7)
    assert set(got) == {"control", "half_batch"} | set(FAMILY.FAULTS)
    for name, (correct, numbers, _) in got.items():
        assert not correct, (name, numbers)


def test_step_work_is_the_count_by_hand():
    cfg = traffic.load("configs", "joyai-llm-flash")
    work = FAMILY.step_work(cfg, 2)
    t, s, d = 16384, 8192, 2048
    assert work["n_params"] == 680441088
    mla = (d * 1536 + 1536 * 32 * 192 + d * 576 + 512 * 32 * 256
           + 32 * 128 * d)                       # q_a, q_b, kv_a, kv_b, out
    dense, shared, router = 3 * d * 7168, 3 * d * 768, d * 256
    head, wm = d * 16160, 2 * d * d
    assert (mla, dense, shared, head) == (26345472, 44040192, 4718592,
                                          33095680)
    pairs = 6 * 2 * s * (s + 1) // 2        # six latent mixers, two rows
    assert work["visible_pairs"] == pairs
    # a token's 8 picks fall on the 16 held of 256 experts a sixteenth of
    # the time: half a pick a token a sparse layer, five sparse layers
    assert work["local_pairs"] == 5 * t // 2
    attn = pairs * 32 * (192 + 128) * 2 * 3
    gmm = 5 * (t // 2) * 3 * 6 * d * 768
    macs_token = (6 * mla + dense + 5 * (router + shared) + 2 * head + wm)
    assert work["flops"] == 6 * macs_token * t + attn + gmm
    assert work["flops"] == pytest.approx(55.7e12, rel=0.005)
    assert work["flash"]["flops"] == attn == pytest.approx(24.7e12,
                                                           rel=0.005)
    assert work["moe_gmm"]["flops"] == gmm == pytest.approx(1.16e12,
                                                            rel=0.005)
    # the rotary key counted once a token: 3 x 64, not 3 x 32 x 64
    assert work["flash"]["bytes"] == 6 * t * (
        3 * 32 * 192 + 9 * 32 * 128 + 3 * 64) * 2
    assert work["moe_gmm"]["bytes"] == 5 * 9 * (
        16 * d * 768 + (t // 2) * (d + 768)) * 2
    assert work["adamw"]["bytes"] == work["n_params"] * 24      # bf16 mu
    # the shares the issue and the cell's `why` state
    module = 6 * (mla + router + shared + head + wm) * t + attn // 6 + \
        gmm // 5
    share = {"latent attention": attn,
             "projections, dense, shared, routers": 6 * t * (
                 6 * mla + dense + 5 * (router + shared)),
             "heads and Wm": 6 * t * (2 * head + wm), "experts": gmm,
             "module": module}
    assert {k: round(100 * v / work["flops"]) for k, v in share.items()} == {
        "latent attention": 44, "projections, dense, shared, routers": 40,
        "heads and Wm": 13, "experts": 2, "module": 21}
    assert FAMILY.step_work(cfg, 4)["flops"] == 2 * work["flops"]
    # FLOPs bind the latent kernels' roofline, not bytes
    assert work["flash"]["flops"] / 197e12 > 5 * work["flash"]["bytes"] / 819e9


def test_reference_shapes_are_the_programs():
    import jax
    import jax.numpy as jnp

    import weights
    from tensorflowonspark_tpu.models.transformer import (
        Transformer, TransformerConfig)

    cfg = toy_mla.config()
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
    entry = next(c for c in bench["configs"] if c["name"] == "joyai-llm-flash")
    cfg = json.load(open(os.path.join(toy.ROOT, entry["file"])))
    assert cfg["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert entry["source"] in cfg["source"]
    for key in entry["reduced"]:
        assert cfg["published"][key] > cfg[key] > 0
        assert str(cfg["published"][key]) in cfg["deployment"][key]
    assert cfg["assumed"] and cfg["departures"]
    # every other key as published (the catalog's row)
    published = {
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
        "head_dim": 64, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 7168, "kv_lora_rank": 512,
        "max_position_embeddings": 131072, "model_type": "joyai_llm_flash",
        "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
        "n_shared_experts": 1, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 8,
        "num_key_value_heads": 32, "num_nextn_predict_layers": 1,
        "q_lora_rank": 1536, "qk_head_dim": 192, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
        "rope_interleave": True, "rope_scaling": None,
        "rope_theta": 32000000, "routed_scaling_factor": 2.5,
        "scoring_func": "sigmoid", "tie_word_embeddings": False,
        "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128}
    assert {k: cfg[k] for k in published} == published
    dep = cfg["deployment"]
    assert dep["chips_sharing_a_layer"] * cfg["n_routed_experts"] == \
        cfg["published"]["n_routed_experts"]
    assert dep["vocab_slices"] * cfg["vocab_size"] == \
        cfg["published"]["vocab_size"]
    # the floors: four sparse layers behind the dense one, 8 routed
    # experts, an eighth of the ids
    z = FAMILY._sizes(cfg)
    assert z["n"] - z["dense"] >= 4 and cfg["n_routed_experts"] >= 8
    assert cfg["vocab_size"] * 8 >= cfg["published"]["vocab_size"]
    m = cfg["program"]["model"]
    assert (m["d_model"], m["n_heads"], m["d_ff"], m["moe_d_ff"],
            m["moe_top_k"], m["num_experts"], m["moe_experts_held"],
            m["moe_expert_offset"], m["n_layers"], m["moe_dense_layers"],
            m["vocab_size"], m["ln_eps"], m["rope_theta"], m["q_lora_rank"],
            m["kv_lora_rank"], m["qk_nope_head_dim"], m["qk_rope_head_dim"],
            m["v_head_dim"], m["rope_interleave"], m["moe_shared_experts"],
            m["moe_routed_scale"], m["mtp_modules"],
            m["mtp_loss_weight"]) == (
        cfg["hidden_size"], cfg["num_attention_heads"],
        cfg["intermediate_size"], cfg["moe_intermediate_size"],
        cfg["num_experts_per_tok"], cfg["published"]["n_routed_experts"],
        cfg["n_routed_experts"], dep["this_chip"]["expert_offset"],
        cfg["num_hidden_layers"], cfg["first_k_dense_replace"],
        cfg["vocab_size"], cfg["rms_norm_eps"], cfg["rope_theta"],
        cfg["q_lora_rank"], cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
        cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["rope_interleave"],
        cfg["n_shared_experts"], cfg["routed_scaling_factor"],
        cfg["num_nextn_predict_layers"], cfg["mtp_loss_weight"])
    assert m["moe_scoring"] == "sigmoid" and m["moe_expert_bias"]
    assert not m["use_bias"] and "tie_embeddings" not in m


def test_the_cells_metric_files_name_readers_that_exist():
    cell = harness.load_spec(CELL, 1, 1, 1)
    names = {m["name"] for m in cell.per_layer}
    assert {"step_mfu.joy", "flash_roofline.joy", "flash_kernel_ms.joy",
            "moe_gmm_roofline.joy", "moe_gmm_kernel_ms.joy",
            "adamw_kernel_ms.joy", "moe_local_pairs_pct.joy",
            "moe_biased_picks_pct.joy", "device_idle_pct.joy",
            "feed_wait_pct.joy", "flash_latent_calls_pct.joy",
            "mtp_loss_pct.joy", "flash_packed_calls_pct", "adamw_direct_pct",
            "launch_s", "compile_s", "node_rendezvous_s"} <= names
    assert {m["name"] for m in cell.end_to_end} == {"tokens_per_s",
                                                    "setup_s"}
    for name in names:
        desc = traffic.load("metrics", name)
        assert harness.load_module("metrics", desc["reader"]).read
    # the `.joy` files that read what the `.lfm` ones read
    for base in ("moe_gmm_roofline", "moe_gmm_kernel_ms", "adamw_kernel_ms",
                 "moe_local_pairs_pct", "moe_biased_picks_pct",
                 "device_idle_pct", "feed_wait_pct", "step_mfu"):
        assert traffic.load("metrics", base + ".joy") == \
            traffic.load("metrics", base + ".lfm")
    # the latent kernels' names, and none of the other cells' kernels
    import re
    pattern = traffic.load("metrics", "flash_kernel_ms.joy")["args"]["pattern"]
    call = ' = bf16[64,8192,128] custom-call(...), ' \
           'custom_call_target="tpu_custom_call"'
    for name in ("%mla_fwd.3", "%transpose_jvp_mla_dq__.7", "%mla_dkv"):
        assert re.search(pattern, name + call)
    for name in ("%flash_fwd.3", "%moe_gmm.1", "%adamw_fused.9"):
        assert not re.search(pattern, name + call)
    other = traffic.load("metrics", "flash_kernel_ms.lfm")["args"]["pattern"]
    assert not re.search(other, "%mla_fwd.3" + call)
