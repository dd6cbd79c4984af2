"""A run refuses to measure without compiled kernels on enough TPU chips,
and prints its result as one JSON line of the contract's keys."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from chipbench import harness, spec
from chipbench.tests.conftest import drive, tiny_cell

ARGS = ["--workload", "d16k.fit", "--seed", "3", "--seconds", "1",
        "--trace", "0"]


def _run(cmd, cwd, **env):
    e = dict(os.environ, JAX_PLATFORMS="cpu", **env)
    e.pop("PYTHONPATH", None)
    return subprocess.run(cmd, cwd=cwd, env=e, capture_output=True,
                          text=True, timeout=120)


@pytest.mark.parametrize("entry", [["chipbench/run.py"],
                                   ["-m", "chipbench"]])
def test_cpu_run_is_refused(entry):
    p = _run([sys.executable, *entry, *ARGS], spec.ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "refused" in p.stderr


def test_bare_checkout_is_refused(tmp_path):
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(spec.HERE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    p = _run([sys.executable, "chipbench/run.py", *ARGS], tmp_path)
    assert p.returncode != 0 and p.stdout.strip() == ""


class FakeTPU:
    platform = "tpu"
    device_kind = "TPU v5 lite"


def test_interpret_mode_is_refused(monkeypatch):
    import jax
    monkeypatch.setattr(jax, "devices", lambda *a: [FakeTPU()])
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    with pytest.raises(harness.Refused, match="interpret"):
        harness.device_check(1)
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    assert len(harness.device_check(1)) == 1


def test_too_few_chips_are_refused(monkeypatch):
    import jax
    monkeypatch.setattr(jax, "devices", lambda *a: [FakeTPU()])
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    with pytest.raises(harness.Refused, match="needs 4 chips"):
        harness.device_check(4)


@pytest.mark.parametrize("traced", [0, 1])
def test_last_line_keys(traced):
    run, line = drive(tiny_cell("d16k.fit"), trace=traced)
    keys = ["correct", "attempted", "failed", "metrics", "device"]
    if traced:
        keys.append("breakdown")
    # the numbers compared for `correct`, each beside its limit, under a
    # key of their own that comes last
    keys.append("checks")
    assert list(line) == keys
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    if traced:
        assert set(line["device"]) >= {"busy_s", "window_s"}
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
        assert "fit_step_mfu" in line["metrics"]
    else:
        assert set(line["metrics"]) == {"fit_iter_ms", "setup_s"}
    for c in line["checks"].values():
        assert set(c) == {"value", "limit"}
    json.dumps(line)
