"""Seconds the node spent making the weights (their program compiled or
loaded from the cache) and in `.lower().compile()` of the step."""


def read(run):
    info = run["result"]["info"]
    return info["init_s"] + info["step_compile_s"]
