"""The `nemotron_h` family at toy size on the CPU: a whole run through
`cluster.run` is `correct` and counts its routing, its mixers and its SSD
calls, each way of breaking it is not (the timed path: half batch, state
unchanged; the mathematics, planted in the reference: the decay left out,
the state not carried across chunks, the skip term, the gate, the norm over
all lanes, the head-to-group map, the step without its softplus, relu for
relu squared, the shared expert, the bias in the weights), the fp8 control
is not, and the counts of `step_work` are the ones a count by hand gives."""
import inspect
import json
import math
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import faults  # noqa: E402
import toy  # noqa: E402
import toy_nemotron  # noqa: E402
import harness  # noqa: E402
import traffic  # noqa: E402
from test_rehearsal import cache_env  # noqa: E402,F401  (a fixture)

FAMILY = harness.load_module("families", "nemotron_h")
CELL = "nemotron-labs-twotower-30b-a3b.fed_s8k_b2"
CONFIG = "nemotron-labs-twotower-30b-a3b"


def drive(**kw):
    spec = toy_nemotron.spec(**kw)
    return spec, harness.drive(spec, start_method="spawn", timeout=280)


def test_rehearsal_is_correct_and_counts_routing_mixers_and_scans(cache_env):  # noqa: F811
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
    # what the step program was built of: four Mamba mixers to one
    # attention layer, every scan on the kernels
    assert c["mixer.calls.mamba"] == 4 * c["mixer.calls.attention"] > 0
    assert c["ssd.calls.kernel"] == c["mixer.calls.mamba"]
    assert "ssd.calls.reference" not in c
    ratio = harness.load_module("metrics", "counter_ratio")
    loaded = {"node": [node]}
    for metric, want in (
            ("ssd_kernel_calls_pct.nem", 100.0),
            ("moe_biased_picks_pct.nem", 100.0 * c["moe.picks.moved"] / pairs),
            ("moe_local_pairs_pct.nem", 100.0 * c["moe.pairs.local"] / pairs)):
        args = traffic.load("metrics", metric)["args"]
        assert ratio.compute(loaded, **args) == pytest.approx(want)
    # a program without the counters (the parent): nothing, and no error
    args = traffic.load("metrics", "ssd_kernel_calls_pct.nem")["args"]
    assert ratio.compute({"node": [{"counters": {"moe.pairs.local": 3}}]},
                         **args) is None


@pytest.mark.parametrize("fault,reference_fault,caught_by", [
    ("state_unchanged", None, "update_norm_gap"),
    ("half_batch", None, "grad_norm_gap"),
    (None, "no_decay", "grad_norm_gap"),
    (None, "state_per_chunk", "update_norm_gap"),
    (None, "no_d_skip", "grad_norm_gap"),
    (None, "no_gate", "grad_norm_gap"),
    (None, "norm_all_lanes", "grad_norm_gap"),
    (None, "group_map", "loss_gap"),
    (None, "dt_no_softplus", "grad_norm_gap"),
    (None, "relu_not_relu2", "grad_norm_gap"),
    (None, "no_shared_expert", "grad_norm_gap"),
    (None, "bias_in_weights", "grad_norm_gap"),
])
def test_a_broken_run_is_not_correct(cache_env, fault, reference_fault,  # noqa: F811
                                     caught_by):
    _, r = drive(fault=fault, reference_fault=reference_fault)
    assert not r["correct"], r["numbers"]
    n = r["numbers"][caught_by]
    assert not n["value"] <= n["limit"], r["numbers"]


def test_control_and_every_planted_fault_read_not_correct():
    spec = toy_nemotron.spec()
    got = faults.readings(spec, seed=7)
    assert set(got) == {"control", "half_batch"} | set(FAMILY.FAULTS)
    for name, (correct, numbers, _) in got.items():
        assert not correct, (name, numbers)


def test_step_work_is_the_count_by_hand():
    cfg = traffic.load("configs", CONFIG)
    work = FAMILY.step_work(cfg, 2)
    t, s, d = 16384, 8192, 2688
    assert work["n_params"] == 666963456
    mamba = d * 10304 + 4096 * d                 # in_proj, out_proj
    attn = 2 * d * 4096 + 2 * d * 256            # q, o; k, v
    sparse, head = d * 128 + 2 * d * 3712, d * 16384   # router, shared
    assert (mamba, attn, sparse, head) == (38707200, 23396352, 20299776,
                                           44040192)
    pairs = 2 * s * (s + 1) // 2                 # one attention layer, 2 rows
    assert work["visible_pairs"] == pairs
    # a token's 6 picks fall on the 8 held of 128 experts a sixteenth of
    # the time: 768 tokens an expert a sparse part
    assert work["local_pairs"] == 4 * t * 6 // 16 == 4 * 8 * 768
    gmm = 4 * t * 6 // 16 * 2 * 6 * d * 1856
    # the SSD kernels: 128 chunks of 128 a layer; C B^T once a group of 8
    # heads, a head's products of the chunk and of the state, forward and
    # backward
    L, H, G, P, N = 128, 64, 8, 64, 128
    fwd = G * 2 * L * L * N + H * (2 * L * L * P + 4 * L * N * P)
    bwd = G * 4 * L * L * N + H * (6 * L * L * P + 4 * L * L * N
                                   + 10 * L * N * P)
    scan = 4 * 128 * (fwd + bwd)
    assert work["ssd"]["flops"] == scan
    assert fwd / L == pytest.approx(3.4e6, rel=0.01)    # a token, forward
    macs_token = 4 * mamba + attn + 4 * sparse + head
    assert work["flops"] == 6 * macs_token * t + 12 * pairs * 4096 + gmm \
        + scan
    assert work["flops"] == pytest.approx(35.68e12, rel=0.001)
    assert work["flash"]["flops"] == 12 * pairs * 4096
    assert work["moe_gmm"]["flops"] == gmm
    assert work["flash"]["bytes"] == 6 * t * (4096 + 256) * 2
    assert work["moe_gmm"]["bytes"] == 4 * 6 * (
        8 * d * 1856 + 4 * 8 * 768 // 4 * (d + 1856)) * 2
    # bytes: x dt, the running sums, B and C (transposed too in the
    # backward), y, the chunk states in float32, the gradients
    heads, groups, sums = t * H * P * 2, t * G * N * 2, t * H * 4
    states = 2 * 64 * H * N * P * 4
    assert work["ssd"]["bytes"] == 4 * (
        (2 * heads + sums + 2 * groups + states)
        + (3 * heads + 2 * sums + 6 * groups + states))
    assert work["adamw"]["bytes"] == work["n_params"] * 24      # bf16 mu
    # the shares the cell's `why` states
    share = {"mamba": 6 * 4 * mamba * t + scan, "experts":
             gmm + 6 * 4 * sparse * t, "attention": 6 * attn * t
             + 12 * pairs * 4096, "head": 6 * head * t}
    assert {k: round(100 * v / work["flops"]) for k, v in share.items()} == {
        "mamba": 46, "experts": 26, "attention": 16, "head": 12}
    assert FAMILY.step_work(cfg, 4)["flops"] == 2 * work["flops"]


def test_reference_shapes_are_the_programs():
    import jax
    import jax.numpy as jnp

    import weights
    from tensorflowonspark_tpu.models.transformer import (
        Transformer, TransformerConfig)

    cfg = toy_nemotron.config()
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
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    cfg = json.load(open(os.path.join(toy.ROOT, entry["file"])))
    assert cfg["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert entry["source"] in cfg["source"]
    for key in entry["reduced"]:
        assert cfg["published"][key] > cfg[key] > 0
        assert str(cfg["published"][key]) in cfg["deployment"][key]
    assert cfg["assumed"] and any("denoiser" in d for d in cfg["departures"])
    for key in ("rope_theta", "partial_rotary_factor"):
        assert key in cfg and key in cfg["assumed"]["positions"]
    # every width as published
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["mamba_num_heads"], cfg["mamba_head_dim"], cfg["n_groups"],
            cfg["ssm_state_size"], cfg["conv_kernel"], cfg["chunk_size"],
            cfg["moe_intermediate_size"],
            cfg["moe_shared_expert_intermediate_size"],
            cfg["num_experts_per_tok"], cfg["routed_scaling_factor"],
            cfg["norm_eps"], cfg["mlp_hidden_act"], cfg["use_conv_bias"],
            cfg["tie_word_embeddings"]) == (
        2688, 32, 2, 128, 64, 64, 8, 128, 4, 128, 1856, 3712, 6, 2.5, 1e-5,
        "relu2", True, False)
    pattern = cfg["hybrid_override_pattern"]
    assert len(pattern) == cfg["published"]["num_hidden_layers"]
    assert [pattern.count(c) for c in "ME*"] == [23, 23, 6]
    dep = cfg["deployment"]
    assert dep["chips_sharing_a_layer"] * cfg["n_routed_experts"] == \
        cfg["published"]["n_routed_experts"]
    assert dep["vocab_slices"] * cfg["vocab_size"] == \
        cfg["published"]["vocab_size"]
    # the floors: a whole period and four layers, 8 routed experts, an
    # eighth of the ids
    z = FAMILY._sizes(cfg)
    assert z["parts"] == "MEMEM*EME" and set(z["parts"]) == set(pattern)
    assert cfg["n_routed_experts"] >= 8
    assert cfg["vocab_size"] * 8 >= cfg["published"]["vocab_size"]
    m = cfg["program"]["model"]
    from tensorflowonspark_tpu.ops import ssd     # the scan's own chunk
    ssd_chunk = inspect.signature(ssd.ssd).parameters["chunk"].default
    assert (m["d_model"], m["n_heads"], m["n_kv_heads"], m["head_dim"],
            m["ssm_heads"], m["ssm_head_dim"], m["ssm_groups"],
            m["ssm_state"], ssd_chunk, m["conv_kernel"], m["moe_d_ff"],
            m["moe_shared_experts"] * m["moe_d_ff"], m["moe_top_k"],
            m["num_experts"],
            m["moe_experts_held"], m["moe_expert_offset"], m["n_layers"],
            m["vocab_size"], m["ln_eps"], m["moe_routed_scale"],
            m["layer_pattern"]) == (
        cfg["hidden_size"], cfg["num_attention_heads"],
        cfg["num_key_value_heads"], cfg["head_dim"], cfg["mamba_num_heads"],
        cfg["mamba_head_dim"], cfg["n_groups"], cfg["ssm_state_size"],
        cfg["chunk_size"], cfg["conv_kernel"], cfg["moe_intermediate_size"],
        cfg["moe_shared_expert_intermediate_size"],
        cfg["num_experts_per_tok"], cfg["published"]["n_routed_experts"],
        cfg["n_routed_experts"], dep["this_chip"]["expert_offset"],
        cfg["num_hidden_layers"], cfg["vocab_size"], cfg["norm_eps"],
        cfg["routed_scaling_factor"], z["parts"])
    assert m["activation"] == "relu2" and m["mlp_style"] == "plain"
    assert not m["rope"] and not m["learned_positions"]
    assert m["moe_scoring"] == "sigmoid" and m["moe_expert_bias"]
    # one published shared expert of 3712 is the program's one shared MLP of
    # moe_shared_experts x moe_d_ff lanes
    assert cfg["n_shared_experts"] == 1
    assert not m["use_bias"] and m["remat"]


def test_the_cells_metric_files_name_readers_that_exist():
    cell = harness.load_spec(CELL, 1, 1, 1)
    names = {m["name"] for m in cell.per_layer}
    new = {"ssd_kernel_ms.nem", "ssd_roofline.nem",
           "mamba_outside_ssd_ms.nem", "ssd_kernel_calls_pct.nem"}
    assert new | {"step_mfu.nem", "flash_roofline.nem", "moe_gmm_roofline.nem",
                  "recompute_ms.nem", "flash_packed_calls_pct", "launch_s",
                  "compile_s", "node_rendezvous_s",
                  "remat_saved_attention_pct", "device_unnamed_pct",
                  "optimizer_outside_kernel_ms", "adamw_direct_pct"} <= names
    assert {m["name"] for m in cell.end_to_end} == {"tokens_per_s",
                                                    "setup_s"}
    for name in names:
        desc = traffic.load("metrics", name)
        assert harness.load_module("metrics", desc["reader"]).read
    # the `.nem` files of the sparse cells' set read what the `.lfm` ones read
    for name in names - new:
        if name.endswith(".nem"):
            assert traffic.load("metrics", name) == traffic.load(
                "metrics", name[:-4] + ".lfm"), name
    # on a trace with no SSD kernel and no Mamba module (the parent's):
    # nothing, and no error
    run = {"result": {"trace": {"ops": {"%fusion.1 = f32[8] fusion(x)": 1.0},
                                "regions": {"forward/layer/moe": {
                                    "s": 1.0, "kernels": {}}}}},
           "spec": cell, "peak": {"bf16_flops_per_s": 1.97e14,
                                  "hbm_bytes_per_s": 8.19e11},
           "work": FAMILY.step_work(cell.config, 2)}
    for name in ("ssd_kernel_ms.nem", "ssd_roofline.nem",
                 "mamba_outside_ssd_ms.nem"):
        desc = traffic.load("metrics", name)
        reader = harness.load_module("metrics", desc["reader"])
        assert reader.read(run, **desc["args"]) is None, name
