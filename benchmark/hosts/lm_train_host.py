"""Hosts `edl_tpu.examples.lm_train.main` unchanged, and reports the
chip's peak memory, which the trainer does not log.

Only the process that holds the chip can read `memory_stats()`. A thread
waits until the trainer itself has initialised JAX's backend (it never
does so first), then samples `memory_stats()` twice a second and keeps
in `$EDL_BENCH_MEM_DIR/mem.<pid>` the most the fullest local device has
held: its `peak_bytes_in_use`, or `bytes_in_use + bytes_reserved` of a
sample where that is more. On this runtime a running program's
temporaries are reserved, not "in use" (a 2.1 GB temporary showed as
`bytes_reserved`, my chip run, PR 22), so the first counter alone misses
them. The value only rises, so what a SIGKILL leaves behind is the peak
up to half a second before it. PERF.md lists this file for removal once
the trainer logs the peak itself.
"""

from __future__ import annotations

import os
import sys
import threading
import time


def _report_peak(path: str, every: float = 0.5) -> None:
    from jax._src import xla_bridge
    while not xla_bridge.backends_are_initialized():
        time.sleep(0.2)
    import jax
    peak = 0
    while True:
        try:
            stats = [d.memory_stats() or {} for d in jax.local_devices()]
        except Exception:  # noqa: BLE001 - a backend without the counter
            return
        peak = max([peak] + [max(
            s.get("peak_bytes_in_use", 0),
            s.get("bytes_in_use", 0) + s.get("bytes_reserved", 0))
            for s in stats])
        with open(path + ".tmp", "w") as f:
            f.write(str(int(peak)))
        os.replace(path + ".tmp", path)
        time.sleep(every)


def main() -> int:
    out = os.environ.get("EDL_BENCH_MEM_DIR")
    if out:
        threading.Thread(target=_report_peak, daemon=True, args=(
            os.path.join(out, f"mem.{os.getpid()}"),)).start()
    from edl_tpu.examples import lm_train
    return lm_train.main(sys.argv[1:])


if __name__ == "__main__":
    sys.exit(main())
