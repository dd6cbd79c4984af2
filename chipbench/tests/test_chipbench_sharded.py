"""A fit cell whose configuration states a mesh: its features are built
straight into the feature sharding and every `fit()` gets the mesh; the
exact-primal reference it is compared with; and the configurations that
are refused before any set-up. At a tiny size on the CPU, the four chips
as four virtual devices in a process of their own."""
import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import data, fit_cell, harness, reference, spec
from chipbench.tests.conftest import drive, tiny_cell, tiny_sharded_cell

SEED = 2**31 + 7
ITERS = 20

# Runs in a process that sees four CPU devices. Prints one JSON line.
FOUR_DEVICES = """
import dataclasses, json, sys
import jax, numpy as np
from chipbench import fit_cell, harness
from chipbench.tests.conftest import drive, tiny_sharded_cell
import repro.api   # found through the path conftest sets

seed = int(sys.argv[1])
cell = tiny_sharded_cell()
devices = jax.devices()[:4]
_, problem, (phi, labels), mesh = fit_cell.build(cell.config, seed, devices)
one = dict(cell.config)
del one["mesh"]
phi_one = fit_cell.build(one, seed, devices[:1])[2][0]
out = {
    "devices": len(devices),
    "shards": sorted([list(s.data.shape), s.device.id]
                     for s in phi.addressable_shards),
    "labels_shards": sorted([list(s.data.shape), s.device.id]
                            for s in labels.addressable_shards),
    "phi_equal": bool(np.array_equal(np.asarray(phi), np.asarray(phi_one))),
    "mesh": dict(mesh.shape),
}
del problem, phi, labels, phi_one

meshes = []
real_fit = repro.api.fit


def spy(*args, mesh=None, **kw):
    meshes.append(None if mesh is None else dict(mesh.shape))
    return real_fit(*args, mesh=mesh, **kw)


repro.api.fit = spy
run, line = drive(cell, seed=seed)
out.update(correct=line["correct"], checks=line["checks"],
           attempted=line["attempted"], fits=meshes)
no_ref = dataclasses.replace(cell, config=dict(
    cell.config, fit=dict(cell.config["fit"], primal="auto")))
try:
    harness.runner(no_ref)
except harness.Refused as e:
    out["refused"] = str(e)
print(json.dumps(out))
"""


def test_sharded_cg_cell_on_four_devices():
    """Each device holds its own (N, T, D/4) slice of phi, equal bit for
    bit to the slice one device builds; every fit of the run, the warm-up
    and the window's, is handed the mesh; the run comes out correct; and
    a primal with no reference is refused."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", FOUR_DEVICES, str(SEED)],
                       cwd=spec.ROOT, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-4000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    cell = tiny_sharded_cell()
    N = cell.config["num_agents"]
    T = cell.config["samples_per_agent"]
    D = cell.config["num_features"]
    assert out["devices"] == 4 and out["mesh"] == {"data": 1, "model": 4}
    assert out["shards"] == [[[N, T, D // 4], i] for i in range(4)]
    assert out["labels_shards"] == [[[N, T], i] for i in range(4)]
    assert out["phi_equal"]
    assert out["correct"], out["checks"]
    assert out["fits"] == [out["mesh"]] * (out["attempted"] + 1)
    assert "no reference for primal='auto'" in out["refused"]


def _exact_config():
    """The tiny sharded cell's configuration on one device."""
    config = dict(tiny_sharded_cell().config)
    del config["mesh"]
    return config


@pytest.mark.parametrize("seed", [SEED, 2**33 + 1])
def test_exact_reference_matches_simulator_cholesky(seed):
    """`coke_exact_fit` (Woodbury, a T x T factor per agent) against the
    program's simulator with the closed-form primal (a D x D factor per
    agent): the same (21a) solves in float32 by two routes. Round-off of
    float32 (6e-8) grows with the normal matrix's condition and over the
    20 iterations; the two read at most 1.3e-5 apart on four seeds, so the
    tolerance is 3e-5. Every send decision agrees."""
    from repro.api import fit
    config = _exact_config()
    config["fit"] = dict(config["fit"], backend="simulator",
                         primal="cholesky")
    cfg, problem, (phi, labels), mesh = fit_cell.build(config, seed,
                                                       jax.devices()[:1])
    assert mesh is None
    res = fit(cfg.replace(num_iters=ITERS), problem=problem)
    ans = (np.asarray(res.theta), np.asarray(res.history["train_mse"]),
           np.asarray(res.history["comms"]))
    ref = fit_cell.reference_fit(config, phi, labels, ITERS,
                                 reference.REFERENCE)
    g = fit_cell.gaps(ans, ref)
    assert g["theta_gap"] < 3e-5 and g["mse_gap"] < 3e-5, g
    assert g["comms_gap"] == 0.0


def test_exact_control_fails_the_limits():
    """The exact reference computed in bfloat16 (the control) fails the
    tiny sharded cell's limits against the reference at float32
    `highest`."""
    config = _exact_config()
    _, _, (phi, labels), _ = fit_cell.build(config, SEED, jax.devices()[:1])
    ref = fit_cell.reference_fit(config, phi, labels, ITERS,
                                 reference.REFERENCE)
    ctl = fit_cell.reference_fit(config, phi, labels, ITERS,
                                 reference.CONTROL)
    limits = config["limits"]["fit"]
    assert [k for k, v in fit_cell.gaps(ctl, ref).items()
            if not v <= limits[k]]


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "altered"])
def test_exact_primal_fault_is_not_correct(monkeypatch, fault):
    """With the program's CG primal broken underneath, a run of the exact
    cell (on one device) comes out not correct: a step that returns its
    state unchanged, the mean over half of each agent's samples, one
    weight altered where the solve makes it."""
    from repro.core import admm
    real = admm._primal_cg

    def broken(problem, gamma, theta_ref, nbr_sum, deg=None, theta0=None,
               **kw):
        if fault == "unchanged":
            return theta0
        if fault == "half_batch":
            half = problem.feats.shape[1] // 2
            problem = dataclasses.replace(
                problem, feats=problem.feats[:, :half],
                labels=problem.labels[:, :half])
        theta = real(problem, gamma, theta_ref, nbr_sum, deg, theta0=theta0,
                     **kw)
        return theta.at[0, 0].add(1e-2) if fault == "altered" else theta

    jax.clear_caches()   # the fault lives in traced Python
    monkeypatch.setattr(admm, "_primal_cg", broken)
    cell = dataclasses.replace(tiny_sharded_cell(), chips=1,
                               config=_exact_config())
    try:
        _, line = drive(cell, seed=SEED)
    finally:
        jax.clear_caches()
    assert not line["correct"], (fault, line["checks"])


def test_build_without_mesh_is_one_device_features():
    """Without a mesh the features are `data.features` on the run's one
    device, bit for bit, and no mesh is handed on."""
    config = tiny_cell("d16k.fit").config
    devices = jax.devices()[:1]
    _, problem, (phi, labels), mesh = fit_cell.build(config, SEED, devices)
    assert mesh is None and problem.feats is phi
    x, y = data.paper_synthetic(config["num_agents"],
                                config["samples_per_agent"], SEED,
                                input_dim=config["input_dim"])
    omega, bias = data.rff_draw(SEED, config["input_dim"],
                                config["num_features"], config["bandwidth"])
    want = data.features(jnp.asarray(x), omega, bias)
    assert phi.devices() == set(devices)
    assert np.array_equal(np.asarray(phi), np.asarray(want))
    assert np.array_equal(np.asarray(labels), y)


def _variant(chips=1, primal="gradient", **config):
    cell = tiny_cell("d16k.fit")
    config = dict(cell.config, fit=dict(cell.config["fit"], primal=primal),
                  **config)
    return dataclasses.replace(cell, chips=chips, config=config)


@pytest.mark.parametrize("cell,why", [
    (_variant(mesh={"data": 1, "model": 4}), "is not the cell's 1 chips"),
    (_variant(chips=4), "is not the cell's 4 chips"),
    (_variant(chips=4, mesh={"data": 1, "model": 4}, primal="auto"),
     "no reference for primal='auto'"),
    (_variant(chips=4, mesh={"data": 1, "tensor": 4}), "other axes"),
    (_variant(chips=4, mesh={"data": 4, "model": 1}, num_agents=6),
     "does not divide"),
], ids=["mesh-not-chips", "chips-without-mesh", "no-reference",
        "unknown-axis", "agents-not-divided"])
def test_refused_before_set_up(monkeypatch, capsys, cell, why):
    """The run exits 2 with the reason and prints no result, before it
    looks for a chip, builds a feature or compiles anything."""
    monkeypatch.setattr(spec, "resolve", lambda name: cell)
    monkeypatch.setattr(fit_cell, "build", pytest.fail)
    rc = harness.main(["--workload", cell.name, "--seed", "1", "--seconds",
                       "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc == 2 and out.out == ""
    assert why in out.err and "no TPU" not in out.err
