"""Loader: the share of a step's tokens that the noise masked, as the
trainer counts it on its step lines (`masked=`): the median over the
window's step lines, in per cent. A control, not a quantity to move:
one level t a block, uniform on (0.001, 1], masks 50 % in expectation,
and a reading far from 50 says the loader's noise is not the
objective's (the schema wants a direction; `higher` is given and means
nothing)."""

from statistics import median


def read(cell, ev):
    shares = [line["masked"] for line in ev.get("step_counters", [])
              if "masked" in line]
    return 100.0 * median(shares) if shares else None
