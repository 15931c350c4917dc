"""Step: device time under the scope `rope` inside the blocks' `attn`
module (the rotary positions on q and k: forward, replayed and
backward, the tables they are turned by, and what XLA fuses into the
pass that feeds them) over busy time. With `attn_time_share` it says
how much of attention's time around the flash kernels the positions
are. Nothing for a model with learned or no positions."""

from benchmark.reduce import moe_scopes


def read(cell, ev):
    return moe_scopes.share(ev, "rope")
