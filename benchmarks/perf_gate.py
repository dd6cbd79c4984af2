"""The perf-regression gate: fail CI when a fresh BENCH json regresses.

Compares per-row `us_per_call` of a fresh `benchmarks.run --json` record
against a committed baseline, matched BY ROW NAME. A row fails when

    fresh_us > PERF_GATE_FACTOR * baseline_us        (default factor 1.5)

Rows named `total_wall_s` and rows present on only one side are reported
but never gated (suite composition may drift between the baseline and a
smoke run). A fresh `*/ERROR` row — a suite that raised — fails the gate
outright. The baseline's git sha + timestamp
stamps (benchmarks/run.py) are echoed so a gate failure names the exact
commit it regressed against.

Committed baselines at the repo root: `BENCH_gossip.json` (agent-axis
scaling), `BENCH_many_model.json` (multi-tenant serving), and
`BENCH_personalize.json` (personalized vs consensus on clustered
non-IID data) — CI runs the matching suite with --smoke and gates each
fresh record against its baseline.

    python -m benchmarks.perf_gate BENCH_fresh.json BENCH_gossip.json
    PERF_GATE_FACTOR=2.0 python -m benchmarks.perf_gate fresh.json base.json
"""
from __future__ import annotations

import json
import os
import sys


def _rows(record: dict) -> tuple[dict[str, float], dict[str, str]]:
    """-> (gateable rows, malformed rows as name -> reason).

    A row missing `us_per_call` or with a non-positive value cannot
    anchor a ratio (a <= 0 baseline would make every fresh value an
    "infinite regression"); such rows are reported as malformed / not
    gated instead of raising or spuriously failing."""
    out: dict[str, float] = {}
    bad: dict[str, str] = {}
    for row in record.get("results", []):
        name = row.get("name", "")
        if name == "total_wall_s" or name.endswith("/ERROR"):
            continue
        if "us_per_call" not in row:
            bad[name] = "missing us_per_call"
            continue
        try:
            us = float(row["us_per_call"])
        except (TypeError, ValueError):
            bad[name] = f"non-numeric us_per_call {row['us_per_call']!r}"
            continue
        if not us > 0:
            bad[name] = f"non-positive us_per_call {us!r}"
            continue
        out[name] = us
    return out, bad


def gate(fresh: dict, baseline: dict, factor: float) -> list[str]:
    """-> list of human-readable failures (empty = gate green)."""
    f_rows, f_bad = _rows(fresh)
    b_rows, b_bad = _rows(baseline)
    failures = [f"{row['name']}: suite raised {row.get('derived', '')}"
                for row in fresh.get("results", [])
                if row.get("name", "").endswith("/ERROR")]
    for name in sorted(f_rows.keys() & b_rows.keys()):
        new, old = f_rows[name], b_rows[name]
        ratio = new / old
        status = "FAIL" if ratio > factor else "ok"
        print(f"{status:>4}  {name:<40} {old:>12.1f} -> {new:>12.1f} us  "
              f"({ratio:.2f}x, limit {factor:.2f}x)")
        if status == "FAIL":
            failures.append(f"{name}: {old:.1f} -> {new:.1f} us "
                            f"({ratio:.2f}x > {factor:.2f}x)")
    for name in sorted(f_rows.keys() - b_rows.keys()):
        print(f"  new  {name} (no baseline row — not gated)")
    for name in sorted(b_rows.keys() - f_rows.keys()):
        print(f"  gone {name} (baseline-only row — not gated)")
    for name, reason in sorted(f_bad.items()):
        print(f"  WARN fresh row {name} malformed ({reason}) — not gated")
    for name, reason in sorted(b_bad.items()):
        print(f"  WARN baseline row {name} malformed ({reason}) "
              "— not gated")
    if not (f_rows.keys() & b_rows.keys()):
        failures.append("no rows in common between fresh and baseline — "
                        "the gate compared nothing")
    return failures


def summary_table(fresh: dict, baseline: dict, factor: float,
                  baseline_name: str) -> str:
    """The gate comparison as a GitHub-flavored markdown table — what CI
    appends to $GITHUB_STEP_SUMMARY so a reviewer reads the latency deltas
    on the run page instead of scrolling raw logs."""
    f_rows, f_bad = _rows(fresh)
    b_rows, b_bad = _rows(baseline)
    lines = [
        f"### perf gate: `{baseline_name}` "
        f"(sha `{baseline.get('git_sha')}`, limit {factor:.2f}x)",
        "",
        "| row | baseline (us) | fresh (us) | ratio | status |",
        "|---|---:|---:|---:|---|",
    ]
    for name in sorted(f_rows.keys() & b_rows.keys()):
        new, old = f_rows[name], b_rows[name]
        ratio = new / old
        status = "❌ FAIL" if ratio > factor else "✅ ok"
        lines.append(f"| `{name}` | {old:.1f} | {new:.1f} "
                     f"| {ratio:.2f}x | {status} |")
    for name in sorted(f_rows.keys() - b_rows.keys()):
        lines.append(f"| `{name}` | — | {f_rows[name]:.1f} | — "
                     "| 🆕 not gated |")
    for name in sorted(b_rows.keys() - f_rows.keys()):
        lines.append(f"| `{name}` | {b_rows[name]:.1f} | — | — "
                     "| gone, not gated |")
    for name, reason in sorted({**b_bad, **f_bad}.items()):
        lines.append(f"| `{name}` | — | — | — "
                     f"| ⚠️ malformed ({reason}), not gated |")
    return "\n".join(lines) + "\n"


def _write_step_summary(fresh: dict, baseline: dict, factor: float,
                        baseline_path: str) -> None:
    """Append the markdown comparison to $GITHUB_STEP_SUMMARY when CI set
    it (each gated baseline appends its own section); no-op locally."""
    path = os.environ.get("GITHUB_STEP_SUMMARY")
    if not path:
        return
    with open(path, "a") as f:
        f.write(summary_table(fresh, baseline, factor,
                              os.path.basename(baseline_path)))
        f.write("\n")


def main(argv: list[str] | None = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 2:
        print(__doc__)
        return 2
    fresh_path, baseline_path = argv
    factor = float(os.environ.get("PERF_GATE_FACTOR", "1.5"))
    with open(fresh_path) as f:
        fresh = json.load(f)
    with open(baseline_path) as f:
        baseline = json.load(f)
    print(f"baseline: {baseline_path} "
          f"(sha={baseline.get('git_sha')}, "
          f"recorded={baseline.get('timestamp')})")
    failures = gate(fresh, baseline, factor)
    _write_step_summary(fresh, baseline, factor, baseline_path)
    if failures:
        print("\nperf gate FAILED:")
        for f_ in failures:
            print(f"  {f_}")
        return 1
    print("\nperf gate green")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
