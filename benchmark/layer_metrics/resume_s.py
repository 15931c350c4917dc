"""Elastic control: SIGKILL of the trainer -> the respawned generation's
`first-step-complete` line (which follows a `block_until_ready`), on the
benchmark's clock. What a user of preemptible capacity loses at every
kill, beside the steps since the last seal. It is part of the cell's
set-up, so `setup_s` holds its median; it has no bound of its own
because it spread by up to 8.6 % between runs (PERF.md §2)."""


def read(cell, ev):
    return ev.get("resume", {}).get("resume_s")
