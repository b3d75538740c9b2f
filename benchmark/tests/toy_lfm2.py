"""Toy-size specs of the `lfm2_moe` family for the CPU rehearsals: the
configuration file cut to a width a CPU steps through in seconds, every
mechanism kept (a dense conv layer, an attention layer with query/key norms
under GQA, sparse conv layers, sigmoid top-2 of 8 experts with a selection
bias of which 4 are held at an offset, a tied and sliced table)."""
import copy

import toy  # noqa: F401  (puts benchmark/ and the checkout on sys.path)
import harness
import traffic

# from toy readings on the CPU (seeds 3, 7, 11), as toy_moe.LIMITS: the bf16
# program reads loss 1.15e-4, gradient 0.0087, update 0.0043 at the most;
# the fp8 control 1.5e-3 in the loss at the least (its gradient, 0.011, is
# not this size's to catch), the planted faults 0.07 (the bias left out of
# the choice) to 1.0 in the gradient, half batch 0.20 in the update
LIMITS = {"feed_rows_wrong": 0, "loss_gap": 4e-4, "grad_norm_gap": 0.03,
          "update_norm_gap": 0.05}


def config(dtype="bfloat16"):
    cfg = copy.deepcopy(traffic.load("configs", "lfm2-8b-a1b"))
    seq, experts, held, offset = 48, 8, 4, 2
    cfg.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
               intermediate_size=128, moe_intermediate_size=48,
               num_experts=held, num_experts_per_tok=2, vocab_size=256)
    cfg["published"]["num_experts"] = experts
    cfg["deployment"]["this_chip"]["expert_offset"] = offset
    # scores of logits of N(0, 0.02 * 8) lie closer together than the
    # cell's: a smaller bias moves the same tenth of the picks
    cfg["init"]["expert_bias_std"] = 0.01
    cfg["program"]["model"].update(
        vocab_size=256, d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
        max_seq_len=seq, num_experts=experts, moe_top_k=2, moe_d_ff=48,
        moe_experts_held=held, moe_expert_offset=offset,
        attention_impl="flash", dtype=dtype)
    cfg["program"].update(seq_len=seq, xent_chunk=32)
    return cfg


def spec(seed=3, seconds=1.0, trace=0, fault=None, limits=None,
         reference_fault=None):
    cfg = config()
    if reference_fault is not None:
        cfg["program"]["fault"] = reference_fault
    seq = cfg["program"]["seq_len"]
    tr = copy.deepcopy(traffic.load("traffic", "fed_s8k_b2"))
    tr["record"][0]["shape"] = [seq + 1]
    # a partition outlasts the window (see toy_moe.spec)
    tr.update(units_per_record=seq, pool=64, batch=4,
              records_per_partition=512, feed_records_per_s=400,
              warm_steps=1, trace_steps=2, reference_row_block=2)
    cell = {"config": cfg["name"], "chips": 1, "mesh": None,
            "rate_metric": "tokens_per_s", "limits": dict(limits or LIMITS)}
    return harness.make_spec(
        "toy-lfm2", seed, seconds, trace, cell=cell, config=cfg, traffic=tr,
        chips=1, peaks={}, platform="cpu", fault=fault,
        end_to_end=[{"name": "setup_s", "unit": "s"},
                    {"name": "tokens_per_s", "unit": "x/s"}])
