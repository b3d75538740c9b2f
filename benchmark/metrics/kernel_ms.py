"""Device milliseconds a step spends in one kernel: the summed device time
of the kernel's events over the traced steps, found by `pattern` among the
trace's operation names (whole HLO instructions).  No such event: nothing."""
import tracered


def read(run, pattern):
    tr = run["result"].get("trace")
    spent = tr and tracered.seconds_in(tr["ops"], pattern)
    if not spent:
        return None
    return 1e3 * spent / run["spec"].traffic["trace_steps"]
