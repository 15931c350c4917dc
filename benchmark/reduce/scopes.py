"""Device seconds by the program's own scopes.

The program names its device work (PR 24): `jax.named_scope` around the
streamed CE (`xent`), the optimizer update (`opt_update`), and inside a
block `attn`, `mlp`, `ln`; `embed` and `lm_head`; `name=` on every
Mosaic call. In the profiler's file a scope does not ride on the events
of `XLA Ops` but on their metadata: every instruction of the step has an
`XEventMetadata` whose stat `tf_op` is the JAX name stack of the
operation a fusion is rooted in,

    jit(train_step)/transpose(jvp(Transformer))/block3/mlp/mlp_in/dot_general:

and the bodies of `while` loops carry it too (my chip runs, PR 24).
`jax.profiler.ProfileData` does not hand out metadata stats, so the few
fields needed are decoded from the protobuf wire format here (XSpace ->
planes -> event_metadata and stat_metadata; the lines with their events
are skipped, not parsed).

A fusion across two scopes counts under the scope of its root, and an
operation the compiler added on its own (a copy, a gradient's
all-reduce) has no `tf_op`: it counts as `unscoped`.
"""

from __future__ import annotations

import collections
import re

from benchmark.harness.procs import say
from benchmark.reduce import xplane

# in the order they are looked for along the name stack
SCOPES = ("xent", "opt_update", "attn", "mlp", "ln", "embed", "lm_head")
UNSCOPED = "unscoped"
_WORD = re.compile(r"[A-Za-z_][\w.\-]*")
_NUMBER = re.compile(r"\d+$")
_SUFFIX = re.compile(r"\.\d+$")
_INSTRUCTION = re.compile(r"^(%[^\s=]+)")


def _varint(buf, at: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[at]
        at += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return value, at


def _fields(buf):
    """(field number, wire type, value) of one protobuf message; a
    length-delimited value is a view, not a copy."""
    at, end = 0, len(buf)
    while at < end:
        key, at = _varint(buf, at)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, at = _varint(buf, at)
        elif wire == 2:
            size, at = _varint(buf, at)
            value, at = buf[at:at + size], at + size
        elif wire == 1:
            value, at = buf[at:at + 8], at + 8
        elif wire == 5:
            value, at = buf[at:at + 4], at + 4
        else:
            raise ValueError(f"wire type {wire}")
        yield number, wire, value


def _map_values(entry):
    """The value message of one map<int64, message> entry."""
    for number, _, value in _fields(entry):
        if number == 2:
            return value
    return b""


def tf_ops(path: str) -> dict[int, dict[str, str]]:
    """device number -> {instruction name ('%fusion.12'): its `tf_op`}."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for number, _, plane in _fields(space):
        if number != 1:                       # XSpace.planes
            continue
        name, events, stats = "", [], {}
        for field, _, value in _fields(plane):
            if field == 2:                    # XPlane.name
                name = bytes(value).decode()
            elif field == 4:                  # XPlane.event_metadata
                events.append(_map_values(value))
            elif field == 5:                  # XPlane.stat_metadata
                meta = {f: v for f, _, v in _fields(_map_values(value))}
                stats[meta.get(1, 0)] = bytes(meta.get(2, b"")).decode()
        m = xplane.DEVICE_PLANE.match(name)
        if not m:
            continue
        wanted = {k for k, v in stats.items() if v == "tf_op"}
        by_name = {}
        for event in events:
            text, op = "", None
            for field, _, value in _fields(event):
                if field == 2:                # XEventMetadata.name
                    text = bytes(value).decode(errors="replace")
                elif field == 5:              # XEventMetadata.stats
                    stat = {f: v for f, _, v in _fields(value)}
                    if stat.get(1) in wanted and 5 in stat:
                        op = bytes(stat[5]).decode(errors="replace")
            got = _INSTRUCTION.match(text)
            if got and op:
                by_name[got.group(1)] = op
        out[int(m.group(1))] = by_name
    return out


def scope_of(tf_op: str | None) -> str:
    """The first of SCOPES along the name stack; else the outermost
    module of the model without its number (`block`); else `unscoped`."""
    if not tf_op:
        return UNSCOPED
    parts = [_WORD.findall(p)[-1:] for p in tf_op.rstrip(":").split("/")]
    names = [p[0] for p in parts if p]
    for scope in SCOPES:
        if scope in names[1:-1]:
            return scope
    # jit(train_step) / jvp(Model) / <module> / ... / <primitive>
    return _NUMBER.sub("", names[2]) if len(names) > 3 else UNSCOPED


def kernel_of(op) -> str | None:
    """A Mosaic call's `name=`, which is its instruction's name."""
    if op[3] != "pallas":
        return None
    return _SUFFIX.sub("", op[2].split(" ", 1)[0].lstrip("%"))


def reduce(trace: dict) -> dict:
    """{"by_scope": device seconds by scope, "by_kernel": by Mosaic
    call name, "busy_s"}, averaged over the chips, inside the window of
    whole steps; printed once as the table the readers share."""
    names = tf_ops(trace["path"])
    n = len(trace["devices"])
    by_scope: collections.Counter = collections.Counter()
    for dev, plane in trace["devices"].items():
        ops = names.get(dev, {})
        for key, seconds in xplane.seconds_by(
                {"devices": {dev: plane}}, lambda op: scope_of(
                    ops.get(op[2].split(" ", 1)[0]))).items():
            by_scope[key] += seconds / n
    by_kernel = {k: v for k, v in xplane.seconds_by(trace, kernel_of).items()
                 if k is not None}
    busy = trace["busy_s"]
    rows = sorted(by_scope.items(), key=lambda kv: -kv[1])
    say("device seconds by scope (share of busy time): " + ", ".join(
        f"{k} {v:.4f} ({100 * v / busy:.1f} %)" for k, v in rows)
        + "; by Mosaic call: " + ", ".join(
            f"{k} {v:.4f} ({100 * v / busy:.1f} %)" for k, v in
            sorted(by_kernel.items(), key=lambda kv: -kv[1])))
    return {"by_scope": dict(by_scope), "by_kernel": by_kernel,
            "busy_s": busy}


def of(ev: dict) -> dict | None:
    """The reduction of this run's trace, made once."""
    trace = ev.get("trace")
    if not trace or not trace["busy_s"]:
        return None
    if "scopes" not in ev:
        ev["scopes"] = reduce(trace)
    return ev["scopes"]


def share(ev: dict, table: str, *keys: str) -> float | None:
    """Per cent of busy device time under ``keys`` of ``table``; nothing
    where the program wrote none of them (a program older than its
    names)."""
    got = of(ev)
    if got is None:
        return None
    seconds = sum(got[table].get(k, 0.0) for k in keys)
    return 100.0 * seconds / got["busy_s"] if seconds else None
