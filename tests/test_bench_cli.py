"""The benchmark CLI surface: `--only` selection validation (an empty or
whitespace selection must NOT degrade into running every suite), the
runner's XLA-flags recipe, and the perf gate's $GITHUB_STEP_SUMMARY
markdown emission."""
import json
import os

import pytest

from benchmarks import perf_gate
from benchmarks import run as bench_run


# ---------------------------------------------------------------------------
# benchmarks.run --only validation
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("only", ["", " ", ",", " , ,"],
                         ids=["empty", "space", "comma", "soup"])
def test_only_empty_selection_rejected(only, capsys):
    """`--only ""` (or any all-whitespace/comma selection) exits with a
    usage error instead of silently running ALL suites — a programmatic
    CI invocation with an empty list must not burn the full budget."""
    with pytest.raises(SystemExit) as exc:
        bench_run.main(["--only", only])
    assert exc.value.code == 2
    assert "no suites" in capsys.readouterr().err


def test_legacy_positional_empty_rejected():
    """The legacy positional spelling gets the same guard."""
    with pytest.raises(SystemExit) as exc:
        bench_run.main([""])
    assert exc.value.code == 2


def test_unknown_suite_rejected(capsys):
    with pytest.raises(SystemExit) as exc:
        bench_run.main(["--only", "gossip,nope"])
    assert exc.value.code == 2
    err = capsys.readouterr().err
    assert "nope" in err and "gossip" in err  # names the valid choices


# ---------------------------------------------------------------------------
# benchmarks.run XLA-flags recipe
# ---------------------------------------------------------------------------

def test_xla_flags_recipe(monkeypatch):
    """Caller-set flags win (no duplicate device-count flag — XLA takes
    the LAST occurrence, which would silently override the caller), the
    step-marker flag is never added (XLA aborts at startup on it, on the
    TPU as on the CPU), and the TF log level quiets by default."""
    monkeypatch.setenv("XLA_FLAGS",
                       "--xla_force_host_platform_device_count=8")
    monkeypatch.delenv("TF_CPP_MIN_LOG_LEVEL", raising=False)
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    bench_run._apply_xla_flags()
    flags = os.environ["XLA_FLAGS"]
    assert flags.count("--xla_force_host_platform_device_count") == 1
    assert "device_count=8" in flags
    assert "--xla_step_marker_location" not in flags
    assert os.environ["TF_CPP_MIN_LOG_LEVEL"] == "4"


def test_main_does_not_mutate_process_env(monkeypatch, capsys):
    """In-process `main()` calls (this very test suite) must leave
    $XLA_FLAGS alone: the recipe applies at the __main__ entry only.
    A leaked device-count flag would poison subprocesses other tests
    spawn with their own forced device counts."""
    monkeypatch.delenv("XLA_FLAGS", raising=False)
    with pytest.raises(SystemExit):
        bench_run.main(["--only", "nope"])
    capsys.readouterr()
    assert "XLA_FLAGS" not in os.environ


@pytest.mark.parametrize("env_dir", [None, "/elsewhere/cache"],
                         ids=["unset", "set"])
def test_compile_cache_location(monkeypatch, env_dir):
    """The entry points' persistent compile cache: with
    $JAX_COMPILATION_CACHE_DIR set, JAX reads it and nothing is set in
    code; unset, the cache lives at the one fixed, git-ignored directory
    in the checkout."""
    import jax

    from repro.launch import compile_cache

    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    before = jax.config.jax_compilation_cache_dir
    try:
        used = compile_cache.enable_compile_cache()
        after = jax.config.jax_compilation_cache_dir
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    if env_dir is None:
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert used == after == os.path.join(root, ".jax_cache")
        with open(os.path.join(root, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()
    else:
        assert used == env_dir and after == before


def test_failed_suite_fails_the_run(monkeypatch, capsys):
    """A suite that raises becomes a `<suite>/ERROR` row and the run
    returns exit code 1 instead of passing."""
    from benchmarks import roofline

    def boom(emit, **kw):
        raise RuntimeError("suite crashed")

    monkeypatch.setattr(roofline, "main", boom)
    assert bench_run.main(["--only", "roofline"]) == 1
    out = capsys.readouterr().out
    assert "roofline/ERROR" in out and "suite crashed" in out


def test_gate_fails_on_fresh_error_row(capsys):
    """A fresh ERROR row fails the gate even when every timed row is
    within the limit."""
    fresh = {"results": [{"name": "a", "us_per_call": 10.0},
                         {"name": "fused/ERROR", "us_per_call": 0.0,
                          "derived": "ValueError: boom"}]}
    base = {"results": [{"name": "a", "us_per_call": 10.0}]}
    failures = perf_gate.gate(fresh, base, 1.5)
    assert failures == ["fused/ERROR: suite raised ValueError: boom"]
    capsys.readouterr()


# ---------------------------------------------------------------------------
# benchmarks.perf_gate --> $GITHUB_STEP_SUMMARY
# ---------------------------------------------------------------------------

FRESH = {"results": [{"name": "a", "us_per_call": 30.0},
                     {"name": "b", "us_per_call": 10.0},
                     {"name": "c", "us_per_call": 1.0},
                     {"name": "total_wall_s", "us_per_call": 99.0}]}
BASE = {"git_sha": "cafe123", "results": [
    {"name": "a", "us_per_call": 10.0},
    {"name": "b", "us_per_call": 10.0},
    {"name": "d", "us_per_call": 5.0}]}


def test_summary_table_contents():
    md = perf_gate.summary_table(FRESH, BASE, 1.5, "BENCH_gossip.json")
    assert "### perf gate: `BENCH_gossip.json`" in md
    assert "`cafe123`" in md
    assert "| `a` | 10.0 | 30.0 | 3.00x | ❌ FAIL |" in md
    assert "| `b` | 10.0 | 10.0 | 1.00x | ✅ ok |" in md
    assert "🆕 not gated" in md          # fresh-only row c
    assert "gone, not gated" in md       # baseline-only row d
    assert "total_wall_s" not in md      # never gated, never tabled


MALFORMED = {"results": [
    {"name": "a", "us_per_call": 10.0},       # healthy, gated
    {"name": "zero", "us_per_call": 0.0},     # non-positive -> not gated
    {"name": "neg", "us_per_call": -3.0},     # non-positive -> not gated
    {"name": "nokey"},                        # missing -> not gated
]}
MALFORMED_FRESH = {"results": [
    {"name": "a", "us_per_call": 10.0},
    {"name": "zero", "us_per_call": 5.0},
    {"name": "neg", "us_per_call": 5.0},
    {"name": "nokey", "us_per_call": 5.0},
]}


def test_gate_malformed_baseline_rows_not_gated(capsys):
    """A baseline row with us_per_call <= 0 must NOT produce ratio=inf
    and a spurious FAIL, and a row missing us_per_call must not raise
    KeyError — both are warned as malformed / not gated."""
    failures = perf_gate.gate(MALFORMED_FRESH, MALFORMED, 1.5)
    assert failures == []                      # only `a` gated, 1.00x
    out = capsys.readouterr().out
    assert "ok" in out
    for name, reason in [("zero", "non-positive"), ("neg", "non-positive"),
                         ("nokey", "missing us_per_call")]:
        assert name in out and "not gated" in out
    assert reason  # last reason checked above
    assert "WARN" in out and "non-positive" in out


def test_gate_malformed_fresh_rows_not_gated(capsys):
    """Same guard on the fresh side: a crashed bench emitting 0 us must
    not silently pass as 0.00x NOR fail — it is simply not gated."""
    failures = perf_gate.gate(MALFORMED, MALFORMED_FRESH, 1.5)
    assert failures == []
    out = capsys.readouterr().out
    assert out.count("WARN") == 3


def test_summary_table_malformed_rows():
    md = perf_gate.summary_table(MALFORMED_FRESH, MALFORMED, 1.5,
                                 "BENCH_x.json")
    assert "| `a` | 10.0 | 10.0 | 1.00x | ✅ ok |" in md
    assert "malformed" in md and "not gated" in md
    assert "inf" not in md and "FAIL" not in md


def test_gate_writes_step_summary(tmp_path, monkeypatch, capsys):
    """main() appends one markdown section per invocation to the file
    named by $GITHUB_STEP_SUMMARY; unset, it writes nothing anywhere."""
    fresh_p = tmp_path / "fresh.json"
    base_p = tmp_path / "BENCH_gossip.json"
    fresh_p.write_text(json.dumps(FRESH))
    base_p.write_text(json.dumps(BASE))
    summary = tmp_path / "summary.md"
    monkeypatch.setenv("GITHUB_STEP_SUMMARY", str(summary))
    assert perf_gate.main([str(fresh_p), str(base_p)]) == 1  # a regressed
    assert perf_gate.main([str(fresh_p), str(base_p)]) == 1
    text = summary.read_text()
    assert text.count("### perf gate: `BENCH_gossip.json`") == 2  # appends
    assert "❌ FAIL" in text
    capsys.readouterr()

    monkeypatch.delenv("GITHUB_STEP_SUMMARY")
    summary.unlink()
    assert perf_gate.main([str(fresh_p), str(base_p)]) == 1
    assert not summary.exists()          # no-op without the env var
    capsys.readouterr()
