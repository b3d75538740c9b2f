"""A kernel's share of its roofline over the traced steps: the least time
the chip could take for the work the algorithm needs (the larger of
operations over peak FLOP/s and bytes over peak HBM bytes/s, from the
family's count) over the summed device time of the kernel's events, found by
`pattern` (and not `exclude`) among the trace's operation names, which are
whole HLO instructions.  No such event: nothing."""
import tracered


def read(run, work, pattern, exclude=None):
    tr = run["result"].get("trace")
    if not tr or not run["peak"]:
        return None
    spent = tracered.seconds_in(tr["ops"], pattern, exclude)
    if spent <= 0:
        return None
    need = run["work"][work]
    least = max(need.get("flops", 0) / run["peak"]["bf16_flops_per_s"],
                need.get("bytes", 0) / run["peak"]["hbm_bytes_per_s"])
    steps = run["spec"].traffic["trace_steps"]
    return 100.0 * least * steps / spent
