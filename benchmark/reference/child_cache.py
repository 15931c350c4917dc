"""A reference child keeps what it compiles, and says what it compiled.

The checkers under `benchmark/reference/` compile minutes of programs
(the initialiser, the plain forward and gradient in float32, the
program's forward, the trainer's step once more under a key of its
own), and a run of a cell is a new process: with no cache a child
compiled them all again in every run, 240 s of the 346 s a warm run of
JoyAI's cell took (PERF.md section 6, PR 55). The harness gives each
child a directory of its own (`harness/cell.REF_CACHE` in the checkout,
never the trainer's: a child's entries pushed the trainer's step out of
a capped directory once), and `keep_programs` makes JAX write every
program there, the quick ones too: a second run in that checkout reads
them all.

What has to hold for a second run to hit: the keys are the lowered
programs, so nothing of a run may be a constant of one. `--seed` makes
the shards, which enter as arguments; `trainer_seed` and the sizes are
the configuration's; a Pallas kernel's debug locations hold the call
stack, which is the same files and lines in every run of one checkout.
A fault that a controls tool patches in changes a program and so its
key; one that only rounds the reference's matrices changes arguments,
which were never in a key.
"""

from __future__ import annotations

_EVENTS = {"/jax/compilation_cache/compile_requests_use_cache": "asked",
           "/jax/compilation_cache/cache_hits": "read"}


def keep_programs():
    """Every program this process compiles from here on is written to
    the directory JAX_COMPILATION_CACHE_DIR names (where it names none,
    nothing is kept and nothing is counted). Returns a function that
    gives {"compiled": n, "read": m} of this process so far: the
    requests JAX made of its cache, less and with those it answered."""
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # A cap that the machine sets for the trainer's directory
    # (JAX_COMPILATION_CACHE_MAX_SIZE: 200 MB on the chip's machines) is
    # not this directory's: five cells' children keep 0.5 GB between
    # them, and under the cap each cell's child pushed the last one's
    # programs out, so that a run after another cell's compiled again.
    jax.config.update("jax_compilation_cache_max_size", -1)
    seen = {"asked": 0, "read": 0}

    def count(event: str, **_) -> None:
        if event in _EVENTS:
            seen[_EVENTS[event]] += 1
    jax.monitoring.register_event_listener(count)

    def programs() -> dict:
        return {"compiled": seen["asked"] - seen["read"],
                "read": seen["read"]}
    return programs


def phase_log():
    """`phase(what)`: a line on standard error with the seconds since
    this call, as the checkers that time their phases print them."""
    import sys
    import time
    t0 = time.monotonic()

    def phase(what: str) -> None:
        print(f"[check +{time.monotonic() - t0:6.1f}s] {what}",
              file=sys.stderr, flush=True)
    return phase


def sentence(programs: dict) -> str:
    """What a checker ends its last phase line with; the driver's `say`
    repeats the line (`harness/cell.reference_child`)."""
    import jax
    return (f"compiled {programs['compiled']} programs and read "
            f"{programs['read']} from "
            f"{jax.config.jax_compilation_cache_dir or 'no cache'}")
