"""Share of the measured window the consuming loop was blocked in `next()`
of the prefetch iterator, waiting for the next device batch."""


def read(run):
    w = run["result"]["window"]
    return 100.0 * w["wait_s"] / w["seconds"]
