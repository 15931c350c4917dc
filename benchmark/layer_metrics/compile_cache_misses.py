"""Entry points: programs the newest generation compiled instead of
loading from the persistent cache, by the trainer's own count on its
`first-step wall` line. After a cell's first run in a checkout every
miss is set-up time that the cache should have saved."""


def read(cell, ev):
    return ev.get("newest_generation", {}).get("misses")
