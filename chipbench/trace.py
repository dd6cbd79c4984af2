"""From a profiler trace to per-layer numbers.

`load` reads the `.xplane.pb` that `jax.profiler` writes into a plain
record: per device, its operations as (name, start_ns, dur_ns); on the
host, the benchmark's own spans (`chipbench.*` TraceAnnotations) and the
program's (`repro.*`); and the window, the span of `chipbench.window`.
Everything else here reduces that record, so the reduction can be checked
on hand-made records with known answers (`tests/test_chipbench_trace.py`).
"""
from __future__ import annotations

import bisect
import glob
import json
import os

SPAN_PREFIX = ("chipbench.", "repro.")   # the benchmark's, the program's
WINDOW_SPAN = "chipbench.window"
# the line of a TPU plane that holds operations (XLA Ops), not their
# enclosing modules or steps; on it, control flow (a scan's `while`)
# spans every operation of its body and is left out
OPS_LINE = "XLA Ops"
CONTROL_FLOW = ("%while", "%conditional", "%call")


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str) -> dict:
    """Read an xplane file into {"devices": {name: [[op, start, dur],..]},
    "host": [[span, start, dur], ...], "window": [start, end]} (ns)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:") and "Core" not in \
                plane.name.split(":")[-1]:
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                # "%name = shape op(operands)": keep the name
                ops.extend([e.name.split(" = ")[0], float(e.start_ns),
                            float(e.duration_ns)]
                           for e in line.events
                           if not e.name.startswith(CONTROL_FLOW))
            if ops:
                devices[plane.name.removeprefix("/device:")] = sorted(
                    ops, key=lambda e: e[1])
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                host.extend([e.name, float(e.start_ns), float(e.duration_ns)]
                            for e in line.events
                            if e.name.startswith(SPAN_PREFIX))
    windows = [h for h in host if h[0] == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"{path} has no {WINDOW_SPAN} span")
    w = max(windows, key=lambda h: h[2])
    return {"devices": devices, "host": sorted(host, key=lambda h: h[1]),
            "window": [w[1], w[1] + w[2]]}


def save(record: dict, path: str) -> None:
    with open(path, "w") as f:
        json.dump(record, f)


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------

def clip(intervals, lo: float, hi: float) -> list:
    out = []
    for s, e in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out.append((s, e))
    return out


def union(intervals) -> list:
    """Merge possibly overlapping (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a, b) -> list:
    """Parts of the merged intervals `a` that the merged `b` does not
    cover."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def _spans(ops, pred=None):
    return [(s, s + d) for name, s, d in ops if pred is None or pred(name)]


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------

def window_ns(record: dict) -> float:
    lo, hi = record["window"]
    return hi - lo


def busy_ns(record: dict) -> dict:
    """Per device: ns of the window in which some operation ran."""
    lo, hi = record["window"]
    return {dev: total(union(clip(_spans(ops), lo, hi)))
            for dev, ops in record["devices"].items()}


def idle_share(record: dict) -> dict:
    """Per device: 1 - busy / window."""
    w = window_ns(record)
    return {dev: 1.0 - b / w for dev, b in busy_ns(record).items()}


def idle_pct(record: dict):
    """-> (mean over the devices, {device: share}) of the idle share in
    percent; (None, {}) where the trace holds no device."""
    shares = {dev: 100.0 * s for dev, s in idle_share(record).items()}
    if not shares:
        return None, {}
    return sum(shares.values()) / len(shares), shares


def op_ns(record: dict, pred) -> dict:
    """Per device: summed duration of the window's operations whose name
    satisfies `pred` (clipped to the window)."""
    lo, hi = record["window"]
    return {dev: total(clip(_spans(ops, pred), lo, hi))
            for dev, ops in record["devices"].items()}


def op_count(record: dict, pred) -> dict:
    lo, hi = record["window"]
    return {dev: sum(1 for name, s, d in ops
                     if pred(name) and s >= lo and s + d <= hi)
            for dev, ops in record["devices"].items()}


def top_ops(record: dict, k: int = 10) -> list:
    """[[op name, seconds summed over the window and the devices]], most
    time first."""
    lo, hi = record["window"]
    acc: dict = {}
    for ops in record["devices"].values():
        for name, s, d in ops:
            t = total(clip([(s, s + d)], lo, hi))
            if t > 0:
                acc[name] = acc.get(name, 0.0) + t
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
    return [[name, t / 1e9] for name, t in ranked]


def _open_span(host, starts, longest: float, t0: float, t1: float) -> str:
    """The host span that covers the most of [t0, t1], the shorter
    one on a tie; "outside spans" where none does. `host` is sorted by
    start, `starts` its starts, `longest` its longest duration."""
    best, best_cover, best_len = "outside spans", 0.0, float("inf")
    for i in range(bisect.bisect_left(starts, t0 - longest),
                   bisect.bisect_left(starts, t1)):
        name, s, d = host[i]
        cover = min(t1, s + d) - max(t0, s)
        if cover > best_cover or (cover == best_cover and 0 < cover
                                  and d < best_len):
            best, best_cover, best_len = name, cover, d
    return best


def idle_gaps(record: dict, k: int = 10) -> list:
    """[[host span, seconds]]: the window's device idle time (summed over
    devices) grouped by the host span that was open during each gap,
    most idle first."""
    lo, hi = record["window"]
    host = sorted((h for h in record["host"] if h[0] != WINDOW_SPAN
                   and h[1] < hi and h[1] + h[2] > lo),
                  key=lambda h: h[1])
    starts = [h[1] for h in host]
    longest = max((h[2] for h in host), default=0.0)
    acc: dict = {}
    for ops in record["devices"].values():
        busy = union(clip(_spans(ops), lo, hi))
        for s, e in subtract([(lo, hi)], busy):
            name = _open_span(host, starts, longest, s, e)
            acc[name] = acc.get(name, 0.0) + (e - s)
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
    return [[name, t / 1e9] for name, t in ranked]


def describe(path: str, k: int = 25) -> str:
    """A by-hand look at an xplane file: its planes and lines, and per
    line the most frequent event names with a sample of their stats."""
    from jax.profiler import ProfileData

    lines = []
    for plane in ProfileData.from_file(path).planes:
        lines.append(f"PLANE {plane.name}")
        for line in plane.lines:
            events = list(line.events)
            lines.append(f"  LINE {line.name!r}: {len(events)} events")
            names: dict = {}
            for e in events:
                n, t, st = names.get(e.name, (0, 0.0, None))
                names[e.name] = (n + 1, t + e.duration_ns,
                                 st if st is not None else list(e.stats))
            for name, (n, t, st) in sorted(names.items(),
                                           key=lambda kv: -kv[1][1])[:k]:
                lines.append(f"    {n:6d} x {t / 1e6:10.3f} ms  {name[:100]}"
                             f"  {str(st)[:300]}")
    return "\n".join(lines)
