"""Loss: the prediction module's cross-entropy less the main model's,
in nats, as the trainer writes both on its step lines: `mtp_loss=` and
`loss=` (the sum `loss = main + weight x mtp_loss`, the weight the
configuration's `mtp_loss_weight`), the median over the window's step
lines. A control, not a quantity to move: on fresh weights both heads
read near ln(vocabulary) and the gap is near 0; a gap that is far from
0 there says the module is fed or scored at the wrong place (the schema
wants a direction; `lower` is given and means nothing)."""

from statistics import median


def read(cell, ev):
    weight = cell.config.get("mtp_loss_weight")
    gaps = [line["mtp_loss"] - (line["loss"] - weight * line["mtp_loss"])
            for line in ev.get("step_counters", [])
            if weight is not None and "mtp_loss" in line and "loss" in line]
    return median(gaps) if gaps else None
