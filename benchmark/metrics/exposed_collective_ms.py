"""Device milliseconds a step during which a collective operation ran and
nothing else did on that device (`tracered.reduce`'s
`exposed_collective_s`), averaged over the devices of the traced steps:
the part of the gradient exchange the step does not hide under compute.
No trace: nothing."""


def read(run):
    tr = run["result"].get("trace")
    if not tr or "exposed_collective_s" not in tr:
        return None
    return 1e3 * tr["exposed_collective_s"] / run["spec"].traffic["trace_steps"]
