"""Step: the share of the tokens' expert assignments that fall on the
experts this chip holds, as the trainer counts it on its step lines
(`moe_held=`; held / all experts at perfect balance): the median over
the window's step lines, in per cent. It is the rows the grouped matmuls
work on over the rows of their buffer."""

from statistics import median


def read(cell, ev):
    held = [line["moe_held"] for line in ev.get("step_counters", [])
            if "moe_held" in line]
    return 100.0 * median(held) if held else None
