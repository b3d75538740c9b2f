"""The `kimi_linear` family at toy size on the CPU: a whole run through
`cluster.run` is `correct` and counts its routing, its mixers and its KDA
calls, each way of breaking it is not (the timed path: half batch, state
unchanged; the mathematics, planted in the reference: the decay a head and
not a channel, the delta rule without its correction, q and k without
their L2 norm, a SiLU output gate, the MLA key rotated, the shared expert
left out), the fp8 control is not, and the counts of `step_work` are the
ones a count by hand gives."""
import inspect
import json
import math
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import faults  # noqa: E402
import toy  # noqa: E402
import toy_kimi  # noqa: E402
import harness  # noqa: E402
import traffic  # noqa: E402
from test_rehearsal import cache_env  # noqa: E402,F401  (a fixture)

FAMILY = harness.load_module("families", "kimi_linear")
CELL = "kimi-linear-48b-a3b.fed_s8k_b2"
CONFIG = "kimi-linear-48b-a3b"
# the cell's own per-layer metrics, named here and not copied from another
# suffix's list: the KDA kernels' and mixer's under `.kim`; what reads the
# latent attention, the expert layer, the optimizer, the head and the device
# letter for letter as JoyAI's cell does, through the `.joy` metrics
KIM = {"kda_kernel_ms.kim", "kda_roofline.kim", "kda_kernel_calls_pct.kim",
       "kda_outside_scan_ms.kim"}
JOY = {"step_mfu.joy", "flash_kernel_ms.joy", "flash_roofline.joy",
       "flash_latent_calls_pct.joy", "moe_gmm_kernel_ms.joy",
       "moe_gmm_roofline.joy", "moe_outside_gmm_ms.joy",
       "moe_local_pairs_pct.joy", "moe_biased_picks_pct.joy",
       "adamw_kernel_ms.joy", "head_ms.joy", "recompute_ms.joy",
       "device_idle_pct.joy", "feed_wait_pct.joy", "step_ms_max.joy"}


def drive(**kw):
    spec = toy_kimi.spec(**kw)
    return spec, harness.drive(spec, start_method="spawn", timeout=280)


def test_rehearsal_is_correct_and_counts_routing_mixers_and_kernels(cache_env):  # noqa: F811
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
    assert 0 < c["moe.picks.moved"] < pairs / 2
    # what the step program was built of: three KDA mixers to one latent
    # attention layer among the sparse layers and one before them, every
    # recurrence on the kernels
    assert c["mixer.calls.kda"] == 4 * c["mixer.calls.latent"] > 0
    assert c["kda.calls.kernel"] == c["mixer.calls.kda"]
    assert "kda.calls.reference" not in c
    ratio = harness.load_module("metrics", "counter_ratio")
    loaded = {"node": [node]}
    for metric, want in (
            ("kda_kernel_calls_pct.kim", 100.0),
            ("flash_latent_calls_pct.joy", 100.0),
            ("moe_local_pairs_pct.joy", 100.0 * c["moe.pairs.local"] / pairs)):
        args = traffic.load("metrics", metric)["args"]
        assert ratio.compute(loaded, **args) == pytest.approx(want)
    # a program without the counters (the parent): nothing, and no error
    args = traffic.load("metrics", "kda_kernel_calls_pct.kim")["args"]
    assert ratio.compute({"node": [{"counters": {"moe.pairs.local": 3}}]},
                         **args) is None


@pytest.mark.parametrize("fault,caught_by", [
    ("state_unchanged", "update_norm_gap"),
    ("half_batch", "grad_norm_gap"),
])
def test_a_broken_timed_path_is_not_correct(cache_env, fault, caught_by):  # noqa: F811
    _, r = drive(fault=fault)
    assert not r["correct"], r["numbers"]
    n = r["numbers"][caught_by]
    assert not n["value"] <= n["limit"], r["numbers"]


def test_control_and_every_planted_fault_read_not_correct():
    """The program reads correct and every other reading does not, in one
    process (the harness's own `compare`): the fp8 control, half the batch
    and each of the family's planted faults."""
    spec = toy_kimi.spec()
    got = faults.readings(spec, seed=7, which=("program", "control",
                                               "half_batch") + FAMILY.FAULTS)
    assert set(got) == {"program", "control", "half_batch"} | set(
        FAMILY.FAULTS)
    for name, (correct, numbers, _) in got.items():
        assert correct == (name == "program"), (name, numbers)


def test_step_work_is_the_count_by_hand():
    cfg = traffic.load("configs", CONFIG)
    work = FAMILY.step_work(cfg, 2)
    t, s, d = 16384, 8192, 2304
    assert work["n_params"] == 602450816
    kda = 3 * d * 4096 + d * 32 + 2 * (d * 128 + 128 * 4096) + 4096 * d
    mla = d * 32 * 192 + d * 576 + 512 * 32 * 256 + 32 * 128 * d
    dense, sparse = 3 * d * 9216, d * 256 + 3 * d * 1024
    head = d * 20480
    assert (kda, mla, dense, sparse, head) == (
        39460864, 29114368, 63700992, 7667712, 47185920)
    pairs = 2 * s * (s + 1) // 2                 # one MLA layer, 2 rows
    assert work["visible_pairs"] == pairs
    # a token's 8 picks fall on the 8 held of 256 experts a 32nd of the
    # time: 512 tokens an expert a sparse layer
    assert work["local_pairs"] == 4 * t * 8 // 32 == 4 * 8 * 512
    gmm = 4 * t * 8 // 32 * 3 * 6 * d * 1024
    # the KDA kernels: 64 chunks of 128 a row and head; forward three
    # C x D x D products and five triangles of D, backward twice that
    C, D = 128, 128
    chunks = 2 * 64 * 32
    fwd = 3 * C * D * D + 5 * (C * C // 2) * D
    rec = 4 * chunks * 2 * 3 * fwd
    assert work["kda"]["flops"] == rec
    seqs = t * 32 * D
    states = chunks * D * D * 4
    assert work["kda"]["bytes"] == 4 * (
        (5 * seqs * 2 + seqs * 4 + states)
        + (9 * seqs * 2 + 2 * seqs * 4 + states))
    macs_token = 4 * kda + mla + dense + 4 * sparse + head
    attn = 6 * pairs * 32 * (192 + 128)
    assert work["flops"] == 6 * macs_token * t + attn + gmm + rec
    assert work["flops"] == pytest.approx(3.825e13, rel=0.001)
    assert work["flash"]["flops"] == attn
    assert work["moe_gmm"]["flops"] == gmm
    assert work["flash"]["bytes"] == t * (3 * 32 * 192 + 9 * 32 * 128
                                          + 3 * 64) * 2
    assert work["moe_gmm"]["bytes"] == 4 * 9 * (
        8 * d * 1024 + 4 * 8 * 512 // 4 * (d + 1024)) * 2
    assert work["adamw"]["bytes"] == work["n_params"] * 24      # bf16 mu
    # the shares the cell's `why` states: the KDA mixers the largest
    share = {"kda": 6 * 4 * kda * t + rec,
             "experts": gmm + 6 * 4 * sparse * t,
             "mla": 6 * mla * t + attn, "dense": 6 * dense * t,
             "head": 6 * head * t}
    assert {k: round(100 * v / work["flops"]) for k, v in share.items()} == {
        "kda": 44, "experts": 10, "mla": 18, "dense": 16, "head": 12}
    assert FAMILY.step_work(cfg, 4)["flops"] == 2 * work["flops"]


def test_reference_shapes_are_the_programs():
    import jax
    import jax.numpy as jnp

    import weights
    from tensorflowonspark_tpu.models.transformer import (
        Transformer, TransformerConfig)

    for cfg in (toy_kimi.config(), traffic.load("configs", CONFIG)):
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
        "num_hidden_layers", "num_experts", "vocab_size"]
    assert entry["source"] in cfg["source"]
    for key in entry["reduced"]:
        assert cfg["published"][key] > cfg[key] > 0
        assert str(cfg["published"][key]) in cfg["deployment"][key]
    assert (cfg["published"]["num_hidden_layers"],
            cfg["published"]["num_experts"],
            cfg["published"]["vocab_size"]) == (27, 256, 163840)
    # every width as published
    lin = cfg["linear_attn_config"]
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["kv_lora_rank"],
            cfg["q_lora_rank"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["head_dim"],
            cfg["intermediate_size"], cfg["moe_intermediate_size"],
            cfg["num_experts_per_token"], cfg["num_shared_experts"],
            cfg["routed_scaling_factor"], cfg["rms_norm_eps"],
            cfg["first_k_dense_replace"], cfg["mla_use_nope"],
            cfg["moe_router_activation_func"], cfg["moe_renormalize"],
            cfg["num_expert_group"], cfg["topk_group"],
            cfg["num_nextn_predict_layers"], cfg["tie_word_embeddings"],
            lin["num_heads"], lin["head_dim"],
            lin["short_conv_kernel_size"]) == (
        2304, 32, 32, 512, None, 128, 64, 128, 72, 9216, 1024, 8, 1, 2.446,
        1e-5, 1, True, "sigmoid", True, 1, 1, 0, False, 32, 128, 4)
    assert sorted(lin["kda_layers"] + lin["full_attn_layers"]) == list(
        range(1, 28))
    assert len(lin["full_attn_layers"]) == 7
    dep = cfg["deployment"]
    assert dep["chips_sharing_a_layer"] * cfg["num_experts"] == \
        cfg["published"]["num_experts"]
    assert dep["vocab_slices"] * cfg["vocab_size"] == \
        cfg["published"]["vocab_size"]
    # the floors: the leading dense layer, a whole period and four layers
    # after it, 8 routed experts, an eighth of the ids
    z = FAMILY._sizes(cfg)
    assert z["kinds"] == ["kda", "kda", "kda", "mla", "kda"]
    assert z["dense"] == 1 and z["n"] - z["dense"] >= 4
    assert cfg["num_experts"] >= 8
    assert cfg["vocab_size"] * 8 >= cfg["published"]["vocab_size"]
    for key in ("e_score_correction_bias", "A_log", "dt_bias", "g_b",
                "1e-6", "lr 3e-6"):
        assert key in json.dumps(cfg["assumed"]), key
    m = cfg["program"]["model"]
    from tensorflowonspark_tpu.ops import kda     # the kernels' own chunk
    chunk = inspect.signature(kda.kda).parameters["chunk"].default
    assert chunk == FAMILY.KDA_CHUNK
    assert (m["d_model"], m["n_heads"], m["kda_heads"], m["kda_head_dim"],
            m["conv_kernel"], m["kv_lora_rank"], m["qk_nope_head_dim"],
            m["qk_rope_head_dim"], m["v_head_dim"], m["d_ff"], m["moe_d_ff"],
            m["moe_shared_experts"], m["moe_top_k"], m["num_experts"],
            m["moe_experts_held"], m["moe_expert_offset"], m["n_layers"],
            m["vocab_size"], m["ln_eps"], m["moe_routed_scale"],
            m["moe_dense_layers"]) == (
        cfg["hidden_size"], cfg["num_attention_heads"], lin["num_heads"],
        lin["head_dim"], lin["short_conv_kernel_size"], cfg["kv_lora_rank"],
        cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"],
        cfg["intermediate_size"], cfg["moe_intermediate_size"],
        cfg["num_shared_experts"], cfg["num_experts_per_token"],
        cfg["published"]["num_experts"], cfg["num_experts"],
        dep["this_chip"]["expert_offset"], cfg["num_hidden_layers"],
        cfg["vocab_size"], cfg["rms_norm_eps"],
        cfg["routed_scaling_factor"], cfg["first_k_dense_replace"])
    assert m["layer_types"] == ["kda" if k == "kda" else "full_attention"
                                for k in z["kinds"]]
    assert "q_lora_rank" not in m and not m["rope"]
    assert not m["learned_positions"]
    assert m["activation"] == "silu" and m["mlp_style"] == "gated"
    assert m["moe_scoring"] == "sigmoid" and m["moe_expert_bias"]
    assert not m["use_bias"] and m["remat"]


def test_the_cells_metric_files_name_readers_that_exist():
    cell = harness.load_spec(CELL, 1, 1, 1)
    names = {m["name"] for m in cell.per_layer}
    assert {n for n in names if n.endswith(".kim")} == KIM
    assert {n for n in names if n.endswith(".joy")} == JOY
    assert {"flash_packed_calls_pct", "launch_s", "compile_s",
            "node_rendezvous_s", "remat_saved_attention_pct",
            "device_unnamed_pct", "optimizer_outside_kernel_ms",
            "adamw_direct_pct"} <= names
    assert {m["name"] for m in cell.end_to_end} == {"tokens_per_s",
                                                    "setup_s"}
    for name in names:
        desc = traffic.load("metrics", name)
        assert harness.load_module("metrics", desc["reader"]).read
    # on a trace with no KDA kernel and no KDA module (the parent's):
    # nothing, and no error
    run = {"result": {"trace": {"ops": {"%fusion.1 = f32[8] fusion(x)": 1.0},
                                "regions": {"forward/layer/moe": {
                                    "s": 1.0, "kernels": {}}}}},
           "spec": cell, "peak": {"bf16_flops_per_s": 1.97e14,
                                  "hbm_bytes_per_s": 8.19e11},
           "work": FAMILY.step_work(cell.config, 2)}
    for name in ("kda_kernel_ms.kim", "kda_roofline.kim",
                 "kda_outside_scan_ms.kim"):
        desc = traffic.load("metrics", name)
        reader = harness.load_module("metrics", desc["reader"])
        assert reader.read(run, **desc["args"]) is None, name
