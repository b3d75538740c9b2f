"""Toy-size specs for the CPU rehearsals: every path of a run but the chip."""
import copy
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (BENCH, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

import harness  # noqa: E402
import traffic  # noqa: E402

# set as the cells' are, from toy readings on the CPU: the bf16 program
# reads loss 1.2e-5 / 2.2e-4, the fp8 control 1.3e-4 / 3e-3 at the least
LIMITS = {"lm": {"feed_rows_wrong": 0, "loss_gap": 5e-5,
                 "grad_norm_gap": 0.1, "update_norm_gap": 0.1},
          "resnet": {"feed_rows_wrong": 0, "loss_gap": 1e-3,
                     "grad_norm_gap": 0.3, "update_norm_gap": 0.3}}


def lm_config(d=64, ff=128, seq=32, vocab=256):
    cfg = copy.deepcopy(traffic.load("configs", "gpt2-large"))
    cfg.update(n_embd=d, n_layer=2, n_head=4, n_inner=ff, n_positions=seq,
               vocab_size=vocab)
    cfg["program"]["model"].update(
        vocab_size=vocab, d_model=d, n_heads=4, n_layers=2, d_ff=ff,
        max_seq_len=seq)
    return cfg


def resnet_config():
    cfg = copy.deepcopy(traffic.load("configs", "resnet50-gn"))
    cfg.update(stage_sizes=[1, 1], num_filters=16, image_size=32,
               num_classes=10)
    cfg["program"]["model"].update(stage_sizes=[1, 1], num_filters=16,
                                   num_classes=10)
    return cfg


def spec(kind, chips=1, seed=3, seconds=1.0, trace=0, fault=None,
         platform="cpu", limits=None):
    if kind in ("lm", "lm_small"):
        # lm_small: head 64 and S=256, the least the flash kernel tiles;
        # it is what the recorded trace under tests/data was taken at
        cfg = lm_config() if kind == "lm" else lm_config(256, 1024, 256, 1024)
        seq = cfg["n_positions"]
        tr = copy.deepcopy(traffic.load("traffic", "fed_b8"))
        tr["record"][0]["shape"] = [seq + 1]
        tr.update(units_per_record=seq, pool=64, batch=8 * chips,
                  records_per_partition=64, feed_records_per_s=400,
                  warm_steps=1, trace_steps=2)
        rate = "tokens_per_s"
    else:
        cfg = resnet_config()
        tr = copy.deepcopy(traffic.load("traffic", "fed_u8_b256"))
        tr["record"][0]["shape"] = [32, 32, 3]
        tr.update(pool=64, batch=8, records_per_partition=64,
                  feed_records_per_s=400, warm_steps=1, trace_steps=2,
                  reference_row_block=4)
        rate = "images_per_s"
    cell = {"config": cfg["name"], "chips": chips,
            "mesh": {"dp": chips} if chips > 1 else None,
            "rate_metric": rate,
            "limits": dict(limits or LIMITS[kind.split("_")[0]])}
    return harness.make_spec(
        f"toy-{kind}", seed, seconds, trace, cell=cell, config=cfg,
        traffic=tr, chips=chips, peaks={}, platform=platform, fault=fault,
        end_to_end=[{"name": "setup_s", "unit": "s"},
                    {"name": rate, "unit": "x/s"}])
