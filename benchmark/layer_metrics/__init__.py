"""One reader per per-layer metric, found by the metric's name.

    read(cell, evidence) -> number, or None where there is nothing to read

`evidence` is what the driver gathered (the stamped step lines of the
window, its log windows that the profiler did not touch, what the kill
and the resume took) plus the device, its peaks and, in a traced run,
`trace`: the reduction of the profiler's file by
`benchmark.reduce.xplane`.
"""
