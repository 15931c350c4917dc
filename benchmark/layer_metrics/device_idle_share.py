"""Device: 1 - (union of the device's operation intervals over the traced
window of whole steps), averaged over the chips."""


def read(cell, ev):
    trace = ev.get("trace")
    if not trace or not trace["window_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
