"""Toy-size specs of the `moe_lm` family for the CPU rehearsals: the
configuration file cut to a width a CPU steps through in seconds, every
mechanism kept (window under the sequence length, GQA, a head size that is
not `d_model // n_heads`, YaRN on the full layer, top-2 of 8 experts of
which 4 are held, a sliced vocabulary)."""
import copy

import toy  # noqa: F401  (puts benchmark/ and the checkout on sys.path)
import harness
import traffic

# from toy readings on the CPU, as toy.LIMITS: the bf16 program reads loss
# 2.8e-5, gradient 0.0084, update 0.0027 at the most; the fp8 control
# 1.3e-4 and 0.026 at the least, half batch 0.18 in the update
LIMITS = {"feed_rows_wrong": 0, "loss_gap": 8e-5, "grad_norm_gap": 0.018,
          "update_norm_gap": 0.1}


def config(held=4, offset=2, dtype="bfloat16"):
    cfg = copy.deepcopy(traffic.load("configs", "mellum2-12b-a2.5b"))
    seq, experts = 48, 8
    cfg.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
               head_dim=32, moe_intermediate_size=48, num_hidden_layers=4,
               num_experts=held, num_experts_per_tok=2, vocab_size=256,
               sliding_window=16)
    cfg["published"]["num_experts"] = experts
    cfg["deployment"]["this_chip"]["expert_offset"] = offset
    cfg["rope_parameters"]["full_attention"][
        "original_max_position_embeddings"] = 32
    cfg["program"]["model"].update(
        vocab_size=256, d_model=64, n_heads=4, n_kv_heads=2, head_dim=32,
        d_ff=128, max_seq_len=seq, sliding_window=16,
        rope_yarn_original_max=32, num_experts=experts, moe_top_k=2,
        moe_d_ff=48, moe_experts_held=held, moe_expert_offset=offset,
        attention_impl="flash", dtype=dtype)
    cfg["program"].update(seq_len=seq, xent_chunk=32)
    return cfg


def spec(seed=3, seconds=1.0, trace=0, fault=None, limits=None,
         zero_expert=None):
    cfg = config()
    if zero_expert is not None:
        cfg["program"]["zero_expert"] = zero_expert
    seq = cfg["program"]["seq_len"]
    tr = copy.deepcopy(traffic.load("traffic", "fed_s8k_b2"))
    tr["record"][0]["shape"] = [seq + 1]
    # a partition outlasts the window: one offered as the node closes its
    # feed makes its feeder send STOP, the reservation server goes, and the
    # node's report (the routing counters) finds nobody to take it
    tr.update(units_per_record=seq, pool=64, batch=4,
              records_per_partition=512, feed_records_per_s=400,
              warm_steps=1, trace_steps=2, reference_row_block=2)
    cell = {"config": cfg["name"], "chips": 1, "mesh": None,
            "rate_metric": "tokens_per_s", "limits": dict(limits or LIMITS)}
    return harness.make_spec(
        "toy-moe", seed, seconds, trace, cell=cell, config=cfg, traffic=tr,
        chips=1, peaks={}, platform="cpu", fault=fault,
        end_to_end=[{"name": "setup_s", "unit": "s"},
                    {"name": "tokens_per_s", "unit": "x/s"}])
