"""`cluster.run` called to the node's function entered, by the benchmark's
own clock (a timestamp the node function takes first of all)."""


def read(run):
    return run["result"].get("launch_s")
