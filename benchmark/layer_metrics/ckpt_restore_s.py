"""Loop + checkpoints: `restore_s` on the resumed generation's
`first-step-complete` line (disk -> host -> device of the whole state)."""


def read(cell, ev):
    return ev["resume"]["resumed"]["restore_s"] if "resume" in ev else None
