"""The program's own names in a traced run: its stages on the device and
its spans on the host.

The program names the stages of an ADMM iteration with
`jax.named_scope("coke.<stage>")` (`core/step.py::run_step`: exchange,
primal, comm_decide, dual, record; the megakernel wrapper's layout copies,
`coke.layout`; the per-iteration history, `coke.history`), and spans
`fit()`'s host work with `jax.profiler.TraceAnnotation`s (`repro.fit`,
`repro.fit.prepare`, `repro.fit.chunk`). `load` reads the cell's
`.xplane.pb` once more and names each device op by the stage its name
stack puts it in, the innermost `coke.*` component ("unscoped" where there
is none), and by its op: "<stage> <op>". The record has the shape
`trace.load` gives, so `trace.py`'s reductions apply to it unchanged
(`tests/test_chipbench_stages.py`).
"""
from __future__ import annotations

import dataclasses
import functools
import os
import sys

from chipbench import harness, trace

SCOPE_PREFIX = "coke."
UNSCOPED = "unscoped"
# the stat of a TPU op's event metadata that holds its JAX name stack (the
# HLO `op_name`, as "<stack>:"). `jax.profiler.ProfileData` shows no
# metadata stats, so the device planes are decoded from the file here.
NAME_STACK_STAT = "tf_op"
FIT_SPAN = "repro.fit"
CONSENSUS = ("coke.exchange", "coke.comm_decide", "coke.dual",
             "coke.record")


def stage_of(name_stack: str) -> str:
    """The innermost `coke.*` component of a name stack ("a/b/op:", the
    op's type after the colon), else "unscoped"."""
    for part in reversed(name_stack.rsplit(":", 1)[0].split("/")):
        if part.startswith(SCOPE_PREFIX):
            return part
    return UNSCOPED


def stage_name(op: str) -> str:
    """The stage of a record's device op, named "<stage> <op>"."""
    return op.split(" ", 1)[0]


# ---------------------------------------------------------------------------
# the xplane file (tsl/profiler/protobuf/xplane.proto), as far as read here:
# XSpace planes=1; XPlane name=2, lines=3, event_metadata=4, stat_metadata=5
# (maps: key=1, value=2); XLine name=2, timestamp_ns=3, events=4; XEvent
# metadata_id=1, offset_ps=2, duration_ps=3; XEventMetadata name=2,
# stats=5; XStat metadata_id=1, str_value=5, ref_value=7; XStatMetadata
# name=2
# ---------------------------------------------------------------------------

def _varint(buf, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: an int for a varint,
    a memoryview for a length-delimited field; fixed-width ones skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError(f"protobuf wire type {wire} in an xplane file")
        yield key >> 3, value


def _text(value) -> str:
    return bytes(value).decode()


def _device_ops(plane) -> list:
    """[["<stage> <op>", start_ns, dur_ns], ...] of a device plane's `XLA
    Ops` line, control flow left out as `trace.load` leaves it out."""
    lines, event_meta, stat_names = [], {}, {}
    for field, value in _fields(plane):
        if field == 3:
            lines.append(value)
        elif field in (4, 5):
            entry = dict(_fields(value))
            if field == 4:
                event_meta[entry.get(1, 0)] = entry.get(2, b"")
            else:
                stat_names[entry.get(1, 0)] = _text(
                    dict(_fields(entry.get(2, b""))).get(2, b""))
    named = {}
    for key, meta in event_meta.items():
        name, stack = "", ""
        for field, value in _fields(meta):
            if field == 2:
                name = _text(value)
            elif field == 5:
                stat = dict(_fields(value))
                if stat_names.get(stat.get(1)) == NAME_STACK_STAT:
                    stack = (_text(stat[5]) if 5 in stat
                             else stat_names.get(stat.get(7), ""))
        if not name.startswith(trace.CONTROL_FLOW):
            # "%name = shape op(operands)": keep the name, as trace.load
            named[key] = f"{stage_of(stack)} {name.split(' = ')[0]}"
    ops = []
    for line in lines:
        head = {k: v for k, v in _fields(line) if k in (2, 3)}
        if _text(head.get(2, b"")) != trace.OPS_LINE:
            continue
        t0 = float(head.get(3, 0))
        for field, value in _fields(line):
            if field == 4:
                e = dict(_fields(value))
                if e.get(1) in named:  # whole ns, as ProfileData gives
                    ops.append([named[e[1]], t0 + e.get(2, 0) // 1000,
                                float(e.get(3, 0) // 1000)])
    return ops


def load(path: str) -> dict:
    """Read an xplane file into {"devices": {name: [["<stage> <op>",
    start, dur], ...]}, "host": [[span, start, dur], ...], "window":
    [start, end]} (ns), once per file."""
    return _load(path, os.stat(path).st_mtime_ns)


@functools.lru_cache(maxsize=2)
def _load(path: str, _mtime_ns: int) -> dict:
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        space = memoryview(f.read())
    devices = {}
    for field, plane in _fields(space):
        if field != 1:
            continue
        name = _text(dict(_fields(plane)).get(2, b""))
        if name.startswith("/device:TPU:") and "Core" not in \
                name.split(":")[-1]:
            ops = _device_ops(plane)
            if ops:
                devices[name.removeprefix("/device:")] = sorted(
                    ops, key=lambda e: e[1])
    host = [[e.name, float(e.start_ns), float(e.duration_ns)]
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:CPU")
            for line in plane.lines for e in line.events
            if e.name.startswith(trace.SPAN_PREFIX)]
    windows = [h for h in host if h[0] == trace.WINDOW_SPAN]
    if not windows:
        raise ValueError(f"{path} has no {trace.WINDOW_SPAN} span")
    w = max(windows, key=lambda h: h[2])
    return {"devices": devices, "host": sorted(host, key=lambda h: h[1]),
            "window": [w[1], w[1] + w[2]]}


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Split:
    """A traced window, per device (the mean over the devices): ns of
    device time by stage, busy ns, and idle ns by the innermost host span
    open in each gap; with the devices, the iterations and the `fit()`
    calls it held."""
    devices: int
    iterations: int
    fit_calls: int
    stage_ns: dict
    busy_ns: float
    idle_ns: dict

    @property
    def scoped(self) -> bool:
        """Does the program name its stages at all?"""
        return any(s != UNSCOPED for s in self.stage_ns)

    def stage_ms(self, *stages: str) -> float:
        """Device ms per iteration in `stages`."""
        return sum(self.stage_ns.get(s, 0.0) for s in stages) \
            / self.iterations / 1e6

    def fit_idle_ms(self) -> float:
        """Device idle ms per fit call under a `repro.fit*` span."""
        return sum(ns for span, ns in self.idle_ns.items()
                   if span.startswith(FIT_SPAN)) / self.fit_calls / 1e6


def split(record: dict, iterations: int) -> Split:
    """Reduce a `load` record with `trace.py`'s interval functions."""
    n_dev = len(record["devices"]) or 1
    stages = {stage_name(op) for ops in record["devices"].values()
              for op, _, _ in ops}
    stage_ns = {s: sum(trace.op_ns(
        record, lambda op, s=s: stage_name(op) == s).values()) / n_dev
        for s in stages}
    spans = {h[0] for h in record["host"]}
    idle_ns = {span: s * 1e9 / n_dev
               for span, s in trace.idle_gaps(record, k=len(spans) + 1)}
    lo, hi = record["window"]
    calls = sum(1 for name, s, _ in record["host"]
                if name == FIT_SPAN and lo <= s < hi)
    return Split(devices=len(record["devices"]), iterations=iterations,
                 fit_calls=calls, stage_ns=stage_ns,
                 busy_ns=sum(trace.busy_ns(record).values()) / n_dev,
                 idle_ns=idle_ns)


def record(run) -> dict | None:
    """The `load` record of a traced run; None where the run was not
    traced or left no trace file."""
    if run.trace is None:
        return None
    try:
        path = trace.find_xplane(os.path.join(harness.OUT, "trace",
                                              run.cell.name))
    except FileNotFoundError:
        return None
    return load(path)


def read(run) -> Split | None:
    """The split of a traced fit run, logged to standard error per
    iteration; None where the run was not traced."""
    if run.fit is None or not run.fit["iterations"]:
        return None
    rec = record(run)
    if rec is None:
        return None
    sp = split(rec, run.fit["iterations"])
    per_iter = lambda ns: ns / sp.iterations / 1e6  # noqa: E731
    parts = ", ".join(f"{s} {per_iter(ns)!r}" for s, ns in
                      sorted(sp.stage_ns.items(), key=lambda kv: -kv[1]))
    idle = ", ".join(f"{s} {per_iter(ns)!r}" for s, ns in
                     sorted(sp.idle_ns.items(), key=lambda kv: -kv[1]))
    print(f"[{run.cell.name}] per iteration (ms), {sp.iterations} "
          f"iterations in {sp.fit_calls} fit calls: device busy "
          f"{per_iter(sp.busy_ns)!r} = {parts}; sum of stages "
          f"{per_iter(sum(sp.stage_ns.values()))!r}; idle by host span: "
          f"{idle or 'none'} on {run.on}", file=sys.stderr)
    return sp
