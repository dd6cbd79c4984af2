"""The unified `repro.api` surface: registry round-trip, `fit()` parity
with the legacy drivers (bit-identical trajectories), backend parity
(simulator vs SPMD vs fused Pallas kernel), and the sweep-compilation
contract (traced censor thresholds -> one compiled loop)."""
import warnings

import jax.numpy as jnp
import numpy as np
import pytest
from conftest import assert_fit_parity

from repro.api import (FitConfig, KRRConfig, build_problem, fit, get_solver,
                       list_solvers)
from repro.api.fit import _simulator_chunk
from repro.api.registry import Solver
from repro.core import admm, cta
from repro.core.censor import CensorSchedule

KRR = KRRConfig(num_agents=6, samples_per_agent=50, num_features=16,
                lam=1e-2, rho=0.5, seed=0)
BASE = FitConfig(krr=KRR, algorithm="coke", censor_v=0.5, censor_mu=0.97,
                 num_iters=60)


@pytest.fixture(scope="module")
def built():
    return build_problem(BASE)


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

def test_registry_roundtrip():
    names = list_solvers()
    assert {"dkla", "coke", "cta", "online_dkla", "online_coke",
            "qc_odkla", "ridge_oracle"} <= set(names)
    for name in names:
        s = get_solver(name)
        assert isinstance(s, Solver)
        assert s.name == name
        assert set(s.backends) <= {"simulator", "spmd", "fused"}


def test_registry_unknown_name_lists_alternatives():
    with pytest.raises(KeyError, match="unknown solver.*coke"):
        get_solver("no_such_algorithm")


def test_unsupported_backend_rejected(built):
    with pytest.raises(ValueError, match="backends"):
        fit(BASE.replace(algorithm="online_coke", backend="spmd"),
            problem=built.problem)
    with pytest.raises(ValueError, match="unknown backend"):
        BASE.replace(backend="gpu_cluster")
    with pytest.raises(ValueError, match="chunk_size"):
        BASE.replace(chunk_size=0)


# ---------------------------------------------------------------------------
# fit() parity vs the legacy entry points
# ---------------------------------------------------------------------------

def _legacy_admm(problem, schedule, iters):
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        return admm.run(problem, schedule, iters)


def test_fit_dkla_bit_identical_to_legacy(built):
    legacy = _legacy_admm(built.problem, admm.dkla_schedule(), 60)
    new = fit(BASE.replace(algorithm="dkla"), problem=built.problem)
    np.testing.assert_array_equal(np.asarray(legacy.train_mse),
                                  np.asarray(new.train_mse))
    np.testing.assert_array_equal(np.asarray(legacy.comms),
                                  np.asarray(new.comms))
    np.testing.assert_array_equal(np.asarray(legacy.consensus_gap),
                                  np.asarray(new.consensus_gap))
    np.testing.assert_array_equal(np.asarray(legacy.state.theta),
                                  np.asarray(new.theta))


def test_fit_coke_bit_identical_to_legacy(built):
    legacy = _legacy_admm(built.problem, CensorSchedule(0.5, 0.97), 60)
    new = fit(BASE, problem=built.problem)
    np.testing.assert_array_equal(np.asarray(legacy.train_mse),
                                  np.asarray(new.train_mse))
    np.testing.assert_array_equal(np.asarray(legacy.comms),
                                  np.asarray(new.comms))
    assert int(new.comms[-1]) < 60 * KRR.num_agents  # censoring active


def test_fit_cta_matches_legacy(built):
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        legacy = cta.run(built.problem, built.graph, lr=0.9, num_iters=60)
    new = fit(BASE.replace(algorithm="cta", cta_lr=0.9),
              problem=built.problem)
    np.testing.assert_array_equal(np.asarray(legacy.train_mse),
                                  np.asarray(new.train_mse))
    np.testing.assert_array_equal(np.asarray(legacy.comms),
                                  np.asarray(new.comms))


def test_legacy_entry_points_warn(built):
    with pytest.warns(DeprecationWarning, match="repro.api.fit"):
        admm.run(built.problem, admm.dkla_schedule(), 2)
    with pytest.warns(DeprecationWarning, match="repro.api.fit"):
        cta.run(built.problem, built.graph, lr=0.9, num_iters=2)


# ---------------------------------------------------------------------------
# Compilation contract: censor sweeps share one compiled loop
# ---------------------------------------------------------------------------

def test_censor_sweep_reuses_one_compiled_loop(built):
    fit(BASE, problem=built.problem)  # warm the cache
    n0 = _simulator_chunk._cache_size()
    savings = []
    for v, mu in ((0.05, 0.99), (0.2, 0.98), (0.8, 0.96), (1.5, 0.95)):
        r = fit(BASE.replace(censor_v=v, censor_mu=mu),
                problem=built.problem)
        savings.append(int(r.comms[-1]))
    assert _simulator_chunk._cache_size() == n0, \
        "sweeping (v, mu) must not retrace the fit loop"
    # the sweep really did run different schedules
    assert len(set(savings)) > 1


# ---------------------------------------------------------------------------
# Backend parity on 4 agents (ring: what the SPMD runtime implements)
# ---------------------------------------------------------------------------

RING = FitConfig(
    krr=KRRConfig(num_agents=4, samples_per_agent=40, num_features=32,
                  lam=1e-2, rho=0.1, seed=0),
    graph="ring", algorithm="coke", censor_v=0.3, censor_mu=0.97,
    num_iters=80, primal="gradient", inner_steps=1, inner_lr=0.05)


@pytest.fixture(scope="module")
def ring_built():
    return build_problem(RING)


@pytest.mark.parametrize("algorithm", ["dkla", "coke"])
def test_backend_parity(ring_built, backend_pair, algorithm):
    """Every backend pair agrees on every iteration's send count exactly
    and on the trajectories/final thetas to float tolerance — the
    conformance contract new backends must pass."""
    assert_fit_parity(RING.replace(algorithm=algorithm), backend_pair,
                      problem=ring_built.problem, exact=("comms",),
                      theta_atol=1e-5, close={"train_mse": dict(atol=1e-6)})


def test_spmd_rejects_noncirculant_graph(built):
    # BASE's problem lives on an Erdos-Renyi graph
    with pytest.raises(ValueError, match="circulant"):
        fit(BASE.replace(backend="spmd"), problem=built.problem)


def test_cross_backend_comm_parity_bit_for_bit(ring_built):
    """Satellite: simulator, spmd and fused must agree bit-for-bit on send
    decisions and quantized payload accounting for a fixed policy key —
    all three run the SAME core.comm decision code on the same message."""
    from repro.api import Censor, Chain, Drop, Quantize

    cfg = RING.replace(
        censor_v=None, censor_mu=None,
        comm=Chain([Censor(0.3, 0.97), Quantize(bits=5, seed=7),
                    Drop(p=0.15, seed=11)]))
    # cumulative send decisions identical at every iteration => the
    # per-iteration decision sequence is identical; every transmission
    # accounted at the same bit width; the quantized broadcasts drive
    # near-identical trajectories
    runs = assert_fit_parity(cfg, ("simulator", "spmd", "fused"),
                             problem=ring_built.problem,
                             exact=("comms", "bits"), theta_atol=1e-5)
    # the policy actually engaged: some sends censored, some payloads lost
    sim = runs["simulator"]
    assert 0 < int(sim.comms[-1]) < RING.resolved_iters * 4


# ---------------------------------------------------------------------------
# Driver plumbing: chunked callbacks, oracle distance, remaining solvers
# ---------------------------------------------------------------------------

def test_chunked_fit_trajectory_identical_and_callbacks_fire(built):
    full = fit(BASE, problem=built.problem)
    seen = []
    chunked = fit(BASE.replace(chunk_size=25), problem=built.problem,
                  progress_cb=lambda k, m: seen.append((k, m)))
    assert [k for k, _ in seen] == [25, 50, 60]
    assert all("train_mse" in m for _, m in seen)
    np.testing.assert_array_equal(np.asarray(full.train_mse),
                                  np.asarray(chunked.train_mse))
    np.testing.assert_array_equal(np.asarray(full.comms),
                                  np.asarray(chunked.comms))


def test_chunk_boundary_parity_bit_identical(built):
    """chunk_size None / divisor / non-divisor must yield bit-identical
    trajectories and final thetas, and progress_cb must fire once per chunk
    with the running iteration count."""
    runs = {}
    fired = {}
    # 60 iters: None = one scan; 20 divides; 25 leaves a short tail chunk
    for cs, expected in ((None, [60]), (20, [20, 40, 60]), (25, [25, 50, 60])):
        seen = []
        runs[cs] = fit(BASE.replace(chunk_size=cs), problem=built.problem,
                       progress_cb=lambda k, m: seen.append(k))
        fired[cs] = seen
        assert seen == expected, (cs, seen)
    ref = runs[None]
    for cs in (20, 25):
        r = runs[cs]
        for key in ref.history:
            np.testing.assert_array_equal(np.asarray(ref.history[key]),
                                          np.asarray(r.history[key]),
                                          err_msg=f"chunk_size={cs}:{key}")
        np.testing.assert_array_equal(np.asarray(ref.theta),
                                      np.asarray(r.theta))


def test_oracle_distance_recorded_and_shrinks(built):
    """DKLA converges linearly to the ridge oracle (Thm 1). On this problem
    the distance falls by a factor of about 0.80 per 100 iterations, so it
    crosses 0.2 * d[0] near iteration 610 (0.208 at 600): 800 iterations
    put the 0.2 bound at a measured 0.13. It also falls at every step."""
    r = fit(BASE.replace(algorithm="dkla", num_iters=800,
                         record_oracle_distance=True),
            problem=built.problem)
    d = np.asarray(r.history["dist_to_oracle"])
    assert d.shape == (800,)
    assert np.all(np.diff(d) < 0)
    assert float(d[-1]) < 0.2 * float(d[0])


def test_ridge_oracle_solver_beats_iterates(built):
    oracle = fit(BASE.replace(algorithm="ridge_oracle", num_iters=1),
                 problem=built.problem)
    assert int(oracle.comms[-1]) == 0
    assert float(oracle.consensus_gap[-1]) < 1e-6  # identical on all agents
    dkla = fit(BASE.replace(algorithm="dkla", num_iters=30),
               problem=built.problem)
    # the oracle attains at-most the truncated iterate's training MSE
    assert float(oracle.train_mse[-1]) <= float(dkla.train_mse[-1]) + 1e-9


def test_online_coke_via_fit_learns_and_censors(built):
    r = fit(BASE.replace(algorithm="online_coke", num_iters=300,
                         online_lr=0.3, censor_v=0.2, censor_mu=0.995),
            problem=built.problem)
    inst = r.history["instant_mse"]
    assert float(jnp.mean(inst[-20:])) < float(jnp.mean(inst[1:21]))
    assert int(r.comms[-1]) < 300 * KRR.num_agents


def test_fit_zero_iters_yields_empty_history(built):
    r = fit(BASE.replace(num_iters=0), problem=built.problem)
    assert r.train_mse.shape == (0,)
    assert r.theta.shape == (KRR.num_agents, KRR.num_features)
    seen = []
    r = fit(BASE.replace(num_iters=0, chunk_size=8), problem=built.problem,
            progress_cb=lambda k, m: seen.append(k))
    assert r.train_mse.shape == (0,) and seen == []


def test_fit_builds_problem_from_config_alone():
    r = fit(FitConfig(krr=KRRConfig(num_agents=4, samples_per_agent=30,
                                    num_features=8),
                      algorithm="dkla", num_iters=5))
    assert r.train_mse.shape == (5,)
    assert r.theta.shape == (4, 8)
