"""Toy-size specs of the `kimi_linear` family for the CPU rehearsals: the
configuration file cut to a width a CPU steps through in seconds, every
mechanism kept (the cell's five layers, KDA with the dense SwiGLU, then
KDA, KDA, MLA, KDA each with experts; KDA mixers of 4 heads of 16 lanes
over rows of 72, which the program's kernels take in two chunks of 64, the
second cut short; MLA of 4 heads of [16 | 8] over 16 with no positions and
no query low-rank; sigmoid top-2 of 8 SwiGLU experts with a selection bias
of which 4 are held at an offset, a shared expert; an untied sliced
head)."""
import copy

import toy  # noqa: F401  (puts benchmark/ and the checkout on sys.path)
import harness
import traffic

# from toy readings on the CPU (seeds 3, 7, 11), as toy_nemotron.LIMITS:
# the bf16 program reads loss 1.3e-3, gradient 0.031, update 0.0158 at the
# most; the fp8 control gradient 0.060 and update 0.024 at the least (its
# loss 9.7e-4-2.1e-3 is the program's own); of the planted faults the
# rotated MLA key is the nearest, caught by the gradient on seed 3 (0.064)
# and by the update on seeds 7 and 11 (0.0255, 0.0193); the others read a
# gradient of 0.18 or more
LIMITS = {"feed_rows_wrong": 0, "loss_gap": 2e-3, "grad_norm_gap": 0.045,
          "update_norm_gap": 0.017}


def config(dtype="bfloat16"):
    cfg = copy.deepcopy(traffic.load("configs", "kimi-linear-48b-a3b"))
    seq, experts, held, offset = 72, 8, 4, 2
    cfg.update(hidden_size=64, num_attention_heads=4, kv_lora_rank=16,
               qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
               intermediate_size=64, moe_intermediate_size=32,
               num_experts=held, num_experts_per_token=2, vocab_size=256)
    cfg["linear_attn_config"].update(num_heads=4, head_dim=16)
    cfg["published"]["num_experts"] = experts
    cfg["deployment"]["this_chip"]["expert_offset"] = offset
    # at a width of 64 kernels of N(0, 0.02) leave the mixers' outputs
    # too small beside the residual for their faults to show; scores of
    # logits of N(0, 0.1 * 8) lie as the cell's do
    cfg["init"].update(kernel_std=0.1, expert_bias_std=0.03)
    # no positions here either; the planted rotation of the MLA key turns
    # each of the toy's four pairs of 8 lanes a radian a position (at the
    # published 10000 three of them barely turn over 72 positions)
    cfg["rope_theta"] = 1.0
    cfg["program"]["optimizer"]["learning_rate"] = 3e-5
    cfg["program"]["model"].update(
        vocab_size=256, d_model=64, n_heads=4, d_ff=64, max_seq_len=seq,
        kda_heads=4, kda_head_dim=16, kv_lora_rank=16, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, num_experts=experts, moe_top_k=2,
        moe_d_ff=32, moe_experts_held=held, moe_expert_offset=offset,
        attention_impl="flash", dtype=dtype)
    cfg["program"].update(seq_len=seq, xent_chunk=36)
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
        "toy-kimi", seed, seconds, trace, cell=cell, config=cfg,
        traffic=tr, chips=1, peaks={}, platform="cpu", fault=fault,
        end_to_end=[{"name": "setup_s", "unit": "s"},
                    {"name": "tokens_per_s", "unit": "x/s"}])
