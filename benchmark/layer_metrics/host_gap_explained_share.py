"""Device: of the time the device sat idle inside the traced window, the
share that lies under a named span of the loop's thread (the step
annotation does not count). The join is on one clock: the spans are
events of the host plane of the same file (`reduce/host_spans.py`).
Under 95 % a span is missing where the loop waits."""

from benchmark.reduce import host_spans


def read(cell, ev):
    got = host_spans.of(cell, ev)
    if not got:
        return None
    idle = sum(got["idle"].values())
    if not idle:
        return None
    return 100.0 * (idle - got["idle"].get(host_spans.NO_SPAN, 0.0)) / idle
