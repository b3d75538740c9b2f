"""Toy-size specs of the `mla_moe` family for the CPU rehearsals: the
configuration file cut to a width a CPU steps through in seconds, every
mechanism and every ratio kept (latent attention with queries of 16 + 8
rotated lanes over values of 16 and two latent ranks, a dense layer and two
sparse ones, a shared expert beside sigmoid top-4 of 16 experts with a
selection bias of which 4 are held at an offset, the routed sum scaled by
2.5, the prediction module, an untied sliced table and head)."""
import copy

import toy  # noqa: F401  (puts benchmark/ and the checkout on sys.path)
import harness
import traffic

# from toy readings on the CPU (`faults.readings`, seeds 3 and 7, the ones
# the tests drive): the bf16 program reads loss 7.0e-5, gradient 0.0104,
# update 0.0060 at the most; the fp8 control 1.3e-3 in the loss (its
# gradient, 0.022 at seed 7, is not this size's to catch), the planted
# faults 0.045 (rotary on every lane; 0.048 a rotary key a head) to 1.0 in
# the gradient, half batch 0.19 in the update.  A toy's limits are not a
# cell's: of 192 tokens a few near-ties at the fourth pick flip under
# bfloat16, and at seeds 11 and 19 the program reads 1.05e-4 in the loss
# and, at 11, 0.050 on one router's kernel, where 16,384 tokens average
# the flips out
LIMITS = {"feed_rows_wrong": 0, "loss_gap": 4e-4, "grad_norm_gap": 0.025,
          "update_norm_gap": 0.05}


def config(dtype="bfloat16"):
    cfg = copy.deepcopy(traffic.load("configs", "joyai-llm-flash"))
    seq, experts, held, offset = 48, 16, 4, 4
    cfg.update(hidden_size=64, num_attention_heads=4, q_lora_rank=24,
               kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
               qk_head_dim=24, v_head_dim=16, intermediate_size=128,
               moe_intermediate_size=24, num_hidden_layers=3,
               n_routed_experts=held, num_experts_per_tok=4, vocab_size=256)
    cfg["published"]["n_routed_experts"] = experts
    cfg["deployment"]["this_chip"]["expert_offset"] = offset
    # scores of logits of N(0, 0.02 * 8) lie closer together than the
    # cell's: a smaller bias moves the same share of the picks
    cfg["init"]["expert_bias_std"] = 0.003
    # kernels at the cell's N(0, 0.02) give queries and keys of 64 inputs a
    # softmax too flat for the rotary faults to show: N(0, 0.1) here, near
    # 1 / sqrt(64), gives the scores the cell's spread
    cfg["init"]["kernel_std"] = 0.1
    cfg["program"]["model"].update(
        vocab_size=256, d_model=64, n_heads=4, n_layers=3, d_ff=128,
        max_seq_len=seq, q_lora_rank=24, kv_lora_rank=16,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        num_experts=experts, moe_top_k=4, moe_d_ff=24,
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
        "toy-mla", seed, seconds, trace, cell=cell, config=cfg, traffic=tr,
        chips=1, peaks={}, platform="cpu", fault=fault,
        end_to_end=[{"name": "setup_s", "unit": "s"},
                    {"name": "tokens_per_s", "unit": "x/s"}])
