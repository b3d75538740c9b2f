"""The whole step's share of the chips' peak: operations the forward and
backward passes REQUIRE (the family's count from shapes: no gathers, no
recomputation) times the window's steps, over its seconds, the chips and the
peak bf16 rate of `peaks.json`."""


def read(run):
    if not run["peak"]:
        return None
    w = run["result"]["window"]
    least = run["work"]["flops"] * w["steps"] / (
        run["spec"].chips * run["peak"]["bf16_flops_per_s"])
    return 100.0 * least / w["seconds"]
