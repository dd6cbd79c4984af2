"""Benchmark entry point: one function per paper table/figure + kernels +
roofline. Prints ``name,us_per_call,derived`` CSV and, with ``--json``,
writes one consolidated machine-readable record per run.

Usage:
  PYTHONPATH=src python -m benchmarks.run [--only a,b] [--smoke] [--json [F]]

--only   comma-separated suite names (default: all).
--smoke  pass smoke=True to every suite whose main() accepts it — the
         CI-sized fast path; suites without a smoke knob run as usual.
--json   write all emitted rows to F (default ``BENCH_all.json`` at the
         repo root, or ``BENCH_<suite>.json`` when --only names exactly
         one suite) — the artifact CI uploads per run.

A suite that raises is recorded as a ``<suite>/ERROR`` row, the other
suites still run, and the run then exits 1.
"""
from __future__ import annotations

import argparse
import inspect
import json
import os
import platform
import subprocess
import time
from datetime import datetime, timezone

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _apply_xla_flags() -> None:
    """The olmax runner recipe (SNIPPETS.md): quiet the TF logging spew
    and pin the host-platform device count before jax initializes its
    backend. Flags the caller already set in $XLA_FLAGS win. The recipe's
    `--xla_step_marker_location` flag is left out: the installed XLA
    refuses it at start-up on the TPU as well as on the CPU.

    Called from the __main__ entry only: in-process callers of `main()`
    (tests, notebooks) keep their environment untouched — mutating
    $XLA_FLAGS mid-process would leak into any subprocess they spawn."""
    os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "4")
    flags = ["--xla_force_host_platform_device_count=1"]
    existing = os.environ.get("XLA_FLAGS", "")
    extra = " ".join(f for f in flags if f.split("=")[0] not in existing)
    if extra:
        os.environ["XLA_FLAGS"] = f"{existing} {extra}".strip()


def _git_sha() -> str | None:
    """HEAD sha for provenance-stamping BENCH_*.json (None outside git)."""
    try:
        return subprocess.run(
            ["git", "rev-parse", "HEAD"], cwd=_ROOT, capture_output=True,
            text=True, timeout=10, check=True).stdout.strip()
    except Exception:
        return None


def main(argv: list[str] | None = None) -> int:
    """-> exit code: 1 when any suite raised, else 0."""
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("only", nargs="?", default=None,
                        help="legacy positional form of --only")
    parser.add_argument("--only", dest="only_flag", default=None,
                        help="comma-separated suite names to run")
    parser.add_argument("--smoke", action="store_true",
                        help="CI-sized runs for suites that support it")
    parser.add_argument("--json", nargs="?", const="", default=None,
                        metavar="PATH",
                        help="write consolidated results as JSON")
    args = parser.parse_args(argv)

    rows: list[dict] = []
    failed: list[str] = []

    def _emit(name: str, us: float, derived: str = "") -> None:
        print(f"{name},{us:.1f},{derived}", flush=True)
        rows.append({"name": name, "us_per_call": us, "derived": derived})

    t0 = time.time()
    from benchmarks import (big_d_bench, fused_bench, gossip_bench,
                            kernel_bench, many_model_bench,
                            paper_comm_cost, paper_convergence,
                            paper_generalization, paper_online,
                            personalize_bench, roofline,
                            serve_kernel_bench)

    suites = [
        ("paper_convergence", paper_convergence.main),   # Figs 1-2, Tab 1/2/4/5
        ("paper_comm_cost", paper_comm_cost.main),       # Fig 3, Tab 3/6
        ("paper_generalization", paper_generalization.main),  # Thm 3
        ("paper_online", paper_online.main),             # streaming regret/bits
        ("kernels", kernel_bench.main),
        ("fused", fused_bench.main),                     # megakernel vs unfused
        ("serve_kernel", serve_kernel_bench.main),       # deployment surface
        ("many_model", many_model_bench.main),           # multi-tenant store
        ("big_d", big_d_bench.main),                     # matrix-free CG sweep
        ("gossip", gossip_bench.main),                   # async agent-axis
        ("personalize", personalize_bench.main),         # learned-graph vs consensus
        ("roofline", roofline.main),                     # from dry-run cache
    ]
    known = {name for name, _ in suites}
    selected = args.only_flag if args.only_flag is not None else args.only
    only = None
    if selected is not None:
        only = {s.strip() for s in selected.split(",") if s.strip()}
        if not only:
            # an empty/whitespace --only must not degrade into "run all":
            # CI invocations build the suite list programmatically, and a
            # silently-universal run burns the full benchmark budget
            parser.error("--only selected no suites; "
                         f"choose from {sorted(known)}")
        unknown = only - known
        if unknown:
            parser.error(f"unknown suite(s) {sorted(unknown)}; "
                         f"choose from {sorted(known)}")

    for name, fn in suites:
        if only is not None and name not in only:
            continue
        kwargs = {}
        if args.smoke and "smoke" in inspect.signature(fn).parameters:
            kwargs["smoke"] = True
        try:
            fn(_emit, **kwargs)
        except Exception as e:  # run the other suites, then fail
            _emit(f"{name}/ERROR", 0.0, f"{type(e).__name__}: {e}")
            failed.append(name)
    _emit("total_wall_s", (time.time() - t0) * 1e6, "")

    if args.json is not None:
        path = args.json
        if not path:
            stem = f"BENCH_{next(iter(only))}" \
                if only and len(only) == 1 else "BENCH_all"
            path = os.path.join(_ROOT, f"{stem}.json")
        record = {
            "suites": sorted(only) if only else sorted(known),
            "smoke": args.smoke,
            "platform": platform.platform(),
            "python": platform.python_version(),
            "git_sha": _git_sha(),
            "timestamp": datetime.now(timezone.utc).isoformat(
                timespec="seconds"),
            "wall_s": time.time() - t0,
            "results": rows,
        }
        with open(path, "w") as f:
            json.dump(record, f, indent=2)
            f.write("\n")
        print(f"wrote {path}", flush=True)
    if failed:
        print(f"suite(s) failed: {', '.join(failed)}", flush=True)
        return 1
    return 0


if __name__ == "__main__":
    _apply_xla_flags()   # process entry: before jax initializes
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    raise SystemExit(main())
