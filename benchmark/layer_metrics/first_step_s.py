"""Entry points: the resumed generation's own `first-step wall` (trace +
compile or cache load + run of the first step)."""


def read(cell, ev):
    return ev.get("resume", {}).get("first_step", {}).get("first_step_s")
