"""From the profiler's `.xplane.pb` to what the per-layer readers need.

Read with `jax.profiler.ProfileData` alone (no converter). A TPU's plane
is named `/device:TPU:<n>`; its line `XLA Modules` has one event per
executed program (the jitted step is the one that takes most time), and
its line `XLA Ops` one event per HLO operation, one at a time, with the
operation's name. That is all the program gives today: it writes no
`TraceAnnotation`, no `named_scope` and no kernel `name=`, so kernels
are told apart by what the compiler called them (PERF.md §7).

Everything is kept in nanoseconds as the trace has it. The steady window
of a device is whole periods of the step: from the start of its first
step event to the start of its last. A trace with fewer than two step
events falls back to first operation start .. last operation end.
"""

from __future__ import annotations

import collections
import glob
import os
import re
import statistics

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
# an event of the ops line is named by its whole HLO instruction:
#   %attn.24 = (bf16[96,2048,128]{...}, f32[...]) custom-call(...), custom_call_target="tpu_custom_call", ...
INSTRUCTION = re.compile(r"^(%[^\s=]+) = (.*?[\]\}\)]) ([a-z][\w\-]*)\(")
TARGET = re.compile(r'custom_call_target="([^"]+)"')
LAYOUT = re.compile(r"\{[^{}]*\}")
NUMBER = re.compile(r"\.\d+(?= )")
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast")
CONTAINERS = ("while", "conditional", "call")  # their bodies' ops are events too
MOSAIC_TARGET = "tpu_custom_call"


def newest_trace(trace_dir: str) -> str | None:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    return found[-1] if found else None


def load(path: str):
    os.environ.setdefault("JAX_PLATFORMS", "cpu")  # never reach for a chip
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def union(intervals) -> list[tuple[float, float]]:
    """Sorted, disjoint cover of (start, end) intervals."""
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        elif b > a:
            out.append([a, b])
    return [(a, b) for a, b in out]


def length(intervals) -> float:
    return sum(b - a for a, b in intervals)


def clip(intervals, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def subtract(cover, holes):
    """Parts of the disjoint sorted ``cover`` outside disjoint sorted
    ``holes``."""
    out = []
    for a, b in cover:
        at = a
        for c, d in holes:
            if d <= at or c >= b:
                continue
            if c > at:
                out.append((at, c))
            at = max(at, d)
        if at < b:
            out.append((at, b))
    return out


def parse_op(text: str) -> tuple[str, str, str]:
    """(label, opcode, kind) of an ops-line event. The label is the
    instruction's name, its opcode (a custom call's target) and its
    result's shape without layouts; kind is collective | pallas |
    container | other."""
    m = INSTRUCTION.match(text)
    if m is None:
        return text[:80], "?", "other"
    name, shape, opcode = m.groups()
    kind = "other"
    if opcode.removesuffix("-start").removesuffix("-done") in COLLECTIVES:
        kind = "collective"
    elif opcode in CONTAINERS:
        kind = "container"
    elif opcode == "custom-call":
        target = TARGET.search(text)
        opcode = target.group(1) if target else opcode
        if opcode == MOSAIC_TARGET:
            kind = "pallas"
    shape = LAYOUT.sub("", shape)
    if len(shape) > 48:
        shape = shape[:45] + "..."
    return f"{name} {opcode} {shape}", opcode, kind


def reduce_plane(plane) -> dict | None:
    """One device: (start ns, end ns, label, kind) of every operation
    inside the window of whole steps, the steps, and the busy cover."""
    modules, ops, parsed = [], [], {}
    for line in plane.lines:
        if line.name == MODULES_LINE:
            modules = [(e.name, e.start_ns, e.duration_ns)
                       for e in line.events]
        elif line.name == OPS_LINE:
            for e in line.events:
                if e.name not in parsed:
                    parsed[e.name] = parse_op(e.name)
                label, _, kind = parsed[e.name]
                ops.append((e.start_ns, e.start_ns + e.duration_ns, label,
                            kind))
    if not ops:
        return None
    by_module = collections.Counter()
    for name, _, dur in modules:
        by_module[name] += dur
    step_name = by_module.most_common(1)[0][0] if by_module else None
    steps = sorted((s, s + d) for name, s, d in modules if name == step_name)
    if len(steps) >= 2:
        window = (steps[0][0], steps[-1][0])
        periods = [b[0] - a[0] for a, b in zip(steps, steps[1:])]
    else:
        window = (min(o[0] for o in ops), max(o[1] for o in ops))
        periods = []
    lo, hi = window
    inside = [o for o in ops if o[1] > lo and o[0] < hi]
    busy = clip(union((o[0], o[1]) for o in inside), lo, hi)
    return {"step_name": step_name, "steps": steps, "periods_ns": periods,
            "whole_steps": max(len(steps) - 1, 0),
            "window_ns": window, "ops": inside, "busy": busy}


def reduce_file(path: str) -> dict:
    devices = {}
    for plane in load(path).planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            got = reduce_plane(plane)
            if got is not None:
                devices[int(m.group(1))] = got
    if not devices:
        raise ValueError(f"no operation ran on a device in {path}")
    n = len(devices)
    return {
        "path": path, "devices": devices,
        # averaged over the chips used, as the contract asks
        "busy_s": sum(length(d["busy"]) for d in devices.values()) / n / 1e9,
        "window_s": sum(d["window_ns"][1] - d["window_ns"][0]
                        for d in devices.values()) / n / 1e9,
    }


def reduce_dir(trace_dir: str, n_devices: int) -> dict:
    path = newest_trace(trace_dir)
    if path is None:
        raise ValueError(f"no .xplane.pb under {trace_dir}")
    out = reduce_file(path)
    if len(out["devices"]) != n_devices:
        raise ValueError(f"the trace holds {len(out['devices'])} device "
                         f"planes, the run used {n_devices} chips")
    return out


# -- what the readers share ---------------------------------------------------

def seconds_by(trace: dict, key) -> dict:
    """Device seconds by ``key(op)``, averaged over the chips."""
    total = collections.Counter()
    for d in trace["devices"].values():
        lo, hi = d["window_ns"]
        for op in d["ops"]:
            if op[3] != "container":  # its body's operations are counted
                total[key(op)] += (min(op[1], hi) - max(op[0], lo)) / 1e9
    n = len(trace["devices"])
    return {k: v / n for k, v in total.items()}


def step_ms(trace: dict) -> float | None:
    periods = [p for d in trace["devices"].values()
               for p in d["periods_ns"]]
    return statistics.median(periods) / 1e6 if periods else None


def exposed_collective_s(trace: dict) -> float:
    """Seconds, averaged over the chips, in which a collective operation
    ran on a chip and no other operation did."""
    total = 0.0
    for d in trace["devices"].values():
        comm = union((o[0], o[1]) for o in d["ops"] if o[3] == "collective")
        work = union((o[0], o[1]) for o in d["ops"]
                     if o[3] not in ("collective", "container"))
        lo, hi = d["window_ns"]
        total += length(clip(subtract(comm, work), lo, hi))
    return total / len(trace["devices"]) / 1e9


def idle_gaps(trace: dict, top: int = 10) -> list[tuple[float, float, int]]:
    """(start ns, end ns, device) of the longest gaps between operations
    inside the window."""
    gaps = []
    for dev, d in trace["devices"].items():
        lo, hi = d["window_ns"]
        for a, b in subtract([(lo, hi)], d["busy"]):
            gaps.append((a, b, dev))
    return sorted(gaps, key=lambda g: g[0] - g[1])[:top]


def breakdown(trace: dict) -> dict:
    """The contract's `breakdown`: the ten kinds of device operation that
    took most time (seconds inside the traced window, averaged over the
    chips), under the names the trace gives them, and the idle time by
    where the gaps fall. Until the program writes `TraceAnnotation`s a gap can only
    be placed by where it falls in the step (after which operation)."""
    # one row per kind of operation: the same instruction in every layer
    # differs only in its number (%fusion.378, %fusion.370, ...)
    by_name = seconds_by(trace, lambda op: NUMBER.sub("", op[2], count=1))
    device_ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:10]
    gaps = collections.Counter()
    for a, b, dev in idle_gaps(trace, top=200):
        d = trace["devices"][dev]
        before = max((o for o in d["ops"]
                      if o[1] <= a and o[3] != "container"),
                     key=lambda o: o[1], default=None)
        in_step = any(s <= a and b <= e for s, e in d["steps"])
        where = ("inside the step, after "
                 + NUMBER.sub("", before[2], count=1) if in_step and before
                 else "between steps (host: loop, loader, log, save)")
        gaps[where] += (b - a) / 1e9
    n = len(trace["devices"])
    return {"device_ops": [[k, v] for k, v in device_ops],
            "idle_gaps": [[k, v / n] for k, v in gaps.most_common(10)]}
