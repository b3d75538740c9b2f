"""One less the union of the device's operation intervals over the traced
window, on the device that was busy longest."""


def read(run):
    tr = run["result"].get("trace")
    if not tr:
        return None
    f = tr["fullest"]
    return 100.0 * (1.0 - f["busy_s"] / f["window_s"])
