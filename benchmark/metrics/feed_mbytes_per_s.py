"""Payload bytes of the records the window's steps consumed over its
seconds: the feed's work done, as a count over the host's clock."""


def read(run):
    w = run["result"]["window"]
    return w["records"] * run["record_bytes"] / w["seconds"] / 1e6
