"""Step: the fullest expert's tokens over the mean expert's, in the
worst layer, as the trainer counts it on its step lines (`moe_max_load=`;
1 is perfect balance, E/k one expert chosen by every token): the median
over the window's step lines. It bounds how uneven the grouped matmuls'
groups are; nothing is dropped at any value."""

from statistics import median


def read(cell, ev):
    loads = [line["moe_max_load"] for line in ev.get("step_counters", [])
             if "moe_max_load" in line]
    return median(loads) if loads else None
