"""`correct` on a run with the chip look skipped, at a tiny size on the
CPU: a sound run passes; the control (the plain reference computed in
bfloat16, one precision below the configuration's float32) fails the
cell's limits; and the run comes out not correct with the timed path
broken underneath, once for each fault the cell can have."""
import jax
import jax.numpy as jnp
import pytest

from chipbench import fit_cell, reference
from chipbench.tests.conftest import drive, tiny_cell

SEED = 2**31 + 99


@pytest.fixture(autouse=True)
def fresh_programs():
    """Planted faults live in traced Python: drop compiled programs so a
    fault is traced in, and traced out again afterwards."""
    jax.clear_caches()
    yield
    jax.clear_caches()


def _fails(checks: dict, limits: dict) -> list:
    return [k for k, v in checks.items() if not v <= limits[k]]


def test_fit_run_is_correct():
    run, line = drive(tiny_cell("d16k.fit"), seed=SEED)
    assert line["correct"], line["checks"]


def test_fit_control_fails():
    cell = tiny_cell("d16k.fit")
    config, iters = cell.config, cell.traffic["num_iters"]
    _, problem, (phi, labels), _ = fit_cell.build(config, SEED,
                                                  jax.devices()[:1])
    ref = fit_cell.reference_fit(config, phi, labels, iters,
                                 reference.REFERENCE)
    ctl = fit_cell.reference_fit(config, phi, labels, iters,
                                 reference.CONTROL)
    assert _fails(fit_cell.gaps(ctl, ref), config["limits"]["fit"])


def _megastep_fault(monkeypatch, fault):
    from repro.api import backends
    real = backends.coke_megastep

    def broken(theta, theta_hat, gamma, phi, y, **kw):
        if fault == "unchanged":
            return theta, jnp.zeros(theta.shape[:1], theta.dtype)
        if fault == "altered":            # one weight off where it is made
            new, xi_sq = real(theta, theta_hat, gamma, phi, y, **kw)
            return new.at[0, 0].add(1e-2), xi_sq
        half = phi.shape[1] // 2          # the mean over half the batch
        return real(theta, theta_hat, gamma, phi[:, :half], y[:, :half],
                    **kw)

    monkeypatch.setattr(backends, "coke_megastep", broken)


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "altered"])
def test_fit_fault_is_not_correct(monkeypatch, fault):
    _megastep_fault(monkeypatch, fault)
    run, line = drive(tiny_cell("d16k.fit"), seed=SEED)
    assert not line["correct"], (fault, line["checks"])
