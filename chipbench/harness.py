"""One benchmark run: resolve the cell, refuse anything but compiled
kernels on enough TPU chips, set up, measure for `--seconds`, check the
answers against the plain reference, print one JSON line.

    python3 chipbench/run.py --workload d16k.fit --seed 7 --seconds 10 \
        --trace 0

The last line of standard output is the result; the numbers compared for
`correct` are also the last lines of standard error. Every other line
names the device it was measured on.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import math
import os
import shutil
import sys
import time

from chipbench import spec, traffic

OUT = os.path.join(spec.HERE, "out")
CACHE_DIR = os.path.join(spec.ROOT, ".jax_cache")


class Refused(Exception):
    """The run cannot measure what the cell asks for."""


@dataclasses.dataclass
class Run:
    """What a cell's run measured; the metric readers read this."""
    cell: object
    seed: int
    seconds: float
    on: str                       # "<device kind> x<chips>"
    peaks: object
    setup_s: float = 0.0
    window_s: float = 0.0
    attempted: int = 0
    failed: int = 0
    mem_peak: int = 0             # bytes, the fullest chip, after the window
    checks: dict = dataclasses.field(default_factory=dict)  # name: (v, lim)
    fit: dict | None = None       # fit cells: iterations, work
    trace: dict | None = None     # trace.load() record of the window

    @property
    def correct(self) -> bool:
        return self.failed == 0 and all(
            math.isfinite(v) and v <= lim for v, lim in self.checks.values())


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def ready(out):
    """block_until_ready that also reaches into result dataclasses
    (`FitResult`), which are not pytrees."""
    import jax
    if dataclasses.is_dataclass(out) and not isinstance(out, type):
        for field in dataclasses.fields(out):
            ready(getattr(out, field.name))
    else:
        jax.block_until_ready(out)
    return out


def span(name: str, on: bool):
    """A host span in the profiler's trace when tracing, else nothing."""
    if not on:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(f"chipbench.{name}")


class CompileCounter:
    """Counts jaxpr traces and backend compilations inside its `with`."""

    EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "traced",
              "/jax/core/compile/backend_compile_duration": "compiled"}

    def __init__(self):
        self.counts = {"traced": 0, "compiled": 0}

    def _on(self, event, duration, **kw):
        if event in self.EVENTS:
            self.counts[self.EVENTS[event]] += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)


def device_check(chips: int):
    """-> the devices the cell runs on. Raises Refused without a TPU,
    with interpreted Pallas kernels, or with fewer chips than asked."""
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise Refused(f"JAX finds no accelerator: {e}") from None
    if devices[0].platform != "tpu":
        raise Refused(f"no TPU: JAX's devices are {devices}")
    if len(devices) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX finds "
                      f"{len(devices)}")
    from repro.kernels.runtime import resolve_interpret
    if resolve_interpret(None):
        raise Refused("Pallas kernels would run in interpret mode "
                      "(REPRO_PALLAS_INTERPRET="
                      f"{os.environ.get('REPRO_PALLAS_INTERPRET')!r})")
    return devices[:chips]


def enable_cache() -> str:
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


class Tracer:
    """The profiler around the window of a `--trace 1` run."""

    def __init__(self, workload: str, on: bool):
        self.on = on
        self.dir = os.path.join(OUT, "trace", workload)

    def start(self):
        if not self.on:
            return
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self):
        if not self.on:
            return None
        import jax
        from chipbench import trace
        jax.profiler.stop_trace()
        record = trace.load(trace.find_xplane(self.dir))
        trace.save(record, os.path.join(self.dir, "record.json"))
        return record


def _metric_line(run: Run, metrics) -> dict:
    out = {}
    for m in metrics:
        value = m.read(run)
        if value is None:
            log(f"[{run.cell.name}] {m.name}: nothing to read on {run.on}")
            continue
        out[m.name] = {"value": float(value), "unit": m.unit}
        log(f"[{run.cell.name}] {m.name} = {float(value)!r} {m.unit} "
            f"on {run.on}")
    return out


def result_line(run: Run, devices, traced: bool) -> dict:
    from chipbench import stages, trace
    cell = run.cell
    metrics = _metric_line(run, cell.per_layer if traced
                           else cell.end_to_end)
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": run.mem_peak}
    line = {"correct": run.correct, "attempted": run.attempted,
            "failed": run.failed, "metrics": metrics, "device": device}
    if traced:
        busy = trace.busy_ns(run.trace)
        device["busy_s"] = (sum(busy.values()) / len(busy) / 1e9
                            if busy else 0.0)
        device["window_s"] = trace.window_ns(run.trace) / 1e9
        # device ops as "<stage> <op>", idle gaps by the innermost
        # benchmark or program span open in each
        line["breakdown"] = {
            "device_ops": trace.top_ops(stages.record(run)),
            "idle_gaps": trace.idle_gaps(run.trace)}
    line["checks"] = {k: {"value": v, "limit": lim}
                      for k, (v, lim) in run.checks.items()}
    return line


def parse(argv=None):
    p = argparse.ArgumentParser(description=__doc__,
                                formatter_class=argparse.RawTextHelpFormatter)
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def runner(cell):
    """The runner of the cell's traffic kind ("fit"), once it has checked
    the cell's configuration: raises Refused before any set-up."""
    from chipbench import fit_cell
    fit_cell.check(cell)
    return fit_cell


def drive(cell, args, t0: float, devices, *, on: str, peaks) -> Run:
    """Run the cell with the runner of its traffic kind."""
    run = Run(cell=cell, seed=args.seed, seconds=args.seconds, on=on,
              peaks=peaks)
    runner(cell).run(run, devices, t0=t0,
                     tracer=Tracer(cell.name, bool(args.trace)))
    return run


def main(argv=None, t0: float | None = None) -> int:
    t0 = time.monotonic() if t0 is None else t0
    args = parse(argv)
    sys.path.insert(0, os.path.join(spec.ROOT, "src"))
    try:
        cell = spec.resolve(args.workload)
        traffic.check(cell.traffic)
        runner(cell)
        devices = device_check(cell.chips)
    except (Refused, KeyError, ValueError, FileNotFoundError,
            ImportError) as e:
        log(f"chipbench: refused: {e}")
        return 2
    from chipbench.peaks import peaks_for
    dev = devices[0]
    peaks = peaks_for(dev.device_kind)
    on = f"{dev.device_kind} x{len(devices)}"
    cache = enable_cache()
    log(f"[{cell.name}] seed {args.seed}, {args.seconds} s, trace "
        f"{args.trace}, on {on}; compile cache {cache}")
    run = drive(cell, args, t0, devices, on=on, peaks=peaks)
    line = result_line(run, devices, bool(args.trace))
    for name, c in line["checks"].items():
        log(f"check {name} = {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(line), flush=True)
    return 0
