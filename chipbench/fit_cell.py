"""Fit cells: back-to-back `repro.api.fit` calls of the traffic's fixed
iteration count on one problem built in set-up.

Set-up draws the data and the random features from the seed, builds the
training features on the device, hands them to the program as a
`Problem` and runs one warm-up fit, which compiles. The window then runs fits back
to back; the fit that is running when `--seconds` pass finishes and
counts. Every fit of the window is compared with the plain reference.

A configuration that states `"mesh": {"data": a, "model": b}` runs on a
mesh of its cell's a x b chips: the training features are built straight
into the program's feature sharding (agents over "data", features over
"model") and every `fit()` gets the mesh. Without it the fit runs on one
chip.

A `"primal": "cg"` configuration may state the CG primal's stop,
`"cg_tol"` and `"cg_maxiter"`, which `FitConfig` gets as stated; where it
states none, `FitConfig`'s defaults hold. `"cg_tol": 0` with
`"cg_maxiter": K` runs exactly K CG steps in every ADMM iteration, on
every seed. A `"fit"` key that neither the run nor the reference reads
is refused, as is any graph but the ring, which both build.
"""
from __future__ import annotations

import math
import time

import numpy as np

from chipbench import data, harness, reference, work

MESH_AXES = ("data", "model")   # the axes the program's sharding rules use
# the plain reference of each primal the program can be asked for
REFERENCES = {"gradient": reference.coke_gradient_fit,
              "cg": reference.coke_exact_fit,
              "cholesky": reference.coke_exact_fit}
# the CG primal's stop, as `FitConfig` takes it
CG_STOP = {"cg_tol": float, "cg_maxiter": int}
# every key of a configuration's "fit" that `build` or `reference_fit` reads
FIT_KEYS = {"algorithm", "backend", "primal", "graph", "lam", "rho",
            "censor_v", "censor_mu", "inner_lr", *CG_STOP}


def check(cell) -> None:
    """Refuse, before any set-up, a configuration the run could not
    measure or could not compare: a mesh that is not the cell's chips or
    does not divide the agents and features, a primal with no reference,
    a fit key the run would ignore, a graph other than the ring, a CG
    stop for another primal or one that is not a stop."""
    config = cell.config
    f = config["fit"]
    primal = f["primal"]
    if primal not in REFERENCES:
        raise harness.Refused(f"no reference for primal={primal!r} (known: "
                              f"{sorted(REFERENCES)})")
    unknown = sorted(set(f) - FIT_KEYS)
    if unknown:
        raise harness.Refused(f"fit keys {unknown} are read by nothing "
                              f"(known: {sorted(FIT_KEYS)})")
    if f.get("graph", "ring") != "ring":
        raise harness.Refused(f"graph={f['graph']!r}: the run and its "
                              f"references build a ring")
    for key in CG_STOP:
        if key in f and primal != "cg":
            raise harness.Refused(f"{key} is the CG primal's stop, and "
                                  f"primal={primal!r}")
    maxiter = f.get("cg_maxiter", 1)
    if type(maxiter) is not int or maxiter < 1:
        raise harness.Refused(f"cg_maxiter={maxiter!r} is not an integer "
                              f"of at least 1")
    tol = f.get("cg_tol", 0.0)
    if type(tol) not in (int, float) or not 0 <= tol < math.inf:
        raise harness.Refused(f"cg_tol={tol!r} is not a finite number of "
                              f"at least 0")
    mesh = config.get("mesh", {"data": 1, "model": 1})
    if sorted(mesh) != sorted(MESH_AXES):
        raise harness.Refused(f"mesh {mesh} names other axes than "
                              f"{MESH_AXES}")
    if mesh["data"] * mesh["model"] != cell.chips:
        raise harness.Refused(f"mesh {mesh} is not the cell's {cell.chips} "
                              f"chips")
    if config["num_agents"] % mesh["data"] or \
            config["num_features"] % mesh["model"]:
        raise harness.Refused(f"mesh {mesh} does not divide "
                              f"{config['num_agents']} agents and "
                              f"{config['num_features']} features")


def build(config: dict, seed: int, devices):
    """-> (FitConfig, Problem, inputs, mesh) from the configuration; the
    mesh is None where the configuration states none."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import AxisType, Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.api import FitConfig, KRRConfig, make_problem
    from repro.core.graph import ring

    d, D = config["input_dim"], config["num_features"]
    N, T = config["num_agents"], config["samples_per_agent"]
    f = config["fit"]
    x, y = data.paper_synthetic(N, T, seed, input_dim=d)
    omega, bias = data.rff_draw(seed, d, D, config["bandwidth"])
    if "mesh" not in config:
        mesh = None
        with jax.default_device(devices[0]):
            phi = data.features(jnp.asarray(x), omega, bias)
            labels = jnp.asarray(y)
    else:
        shape = tuple(config["mesh"][a] for a in MESH_AXES)
        mesh = Mesh(np.asarray(devices).reshape(shape), MESH_AXES,
                    axis_types=(AxisType.Auto,) * len(MESH_AXES))

        def put(a, *spec):
            return jax.device_put(a, NamedSharding(mesh, P(*spec)))
        # the layout `fit(mesh=)` gives the problem, so it moves nothing
        phi = data.features(
            put(x, "data", None, None), put(omega, None, "model"),
            put(bias, "model"),
            sharding=NamedSharding(mesh, P("data", None, "model")))
        labels = put(y, "data", None)
    problem = make_problem(phi, labels, ring(N), lam=f["lam"], rho=f["rho"])
    stop = {k: cast(f[k]) for k, cast in CG_STOP.items() if k in f}
    cfg = FitConfig(
        algorithm=f["algorithm"], backend=f["backend"], primal=f["primal"],
        graph="ring", num_iters=None, inner_lr=f["inner_lr"],
        krr=KRRConfig(num_agents=N, samples_per_agent=T, num_features=D,
                      bandwidth=config["bandwidth"], lam=f["lam"],
                      rho=f["rho"], censor_v=f["censor_v"],
                      censor_mu=f["censor_mu"], seed=seed,
                      mapping=config["mapping"]), **stop)
    return cfg, problem, (phi, labels), mesh


def reference_fit(config: dict, phi, labels, iters: int, arith):
    """The reference's (theta, train MSE, cumulative sends) for one fit."""
    f = config["fit"]
    dtype, precision = arith
    step = {"lr": f["inner_lr"]} if f["primal"] == "gradient" else {}
    out = REFERENCES[f["primal"]](
        phi, labels, lam=f["lam"], rho=f["rho"], v=f["censor_v"],
        mu=f["censor_mu"], iters=iters, dtype=dtype, precision=precision,
        **step)
    return tuple(np.asarray(a) for a in out)


def gaps(answer, ref) -> dict:
    """The numbers compared: the worst agent's relative theta error, the
    worst iteration's relative train-MSE error, and the relative error of
    the total number of messages sent."""
    theta, mse, comms = answer
    r_theta, r_mse, r_comms = ref
    return {
        "theta_gap": float(np.max(
            np.linalg.norm(theta - r_theta, axis=-1)
            / np.linalg.norm(r_theta, axis=-1))),
        "mse_gap": float(np.max(np.abs(mse - r_mse) / r_mse)),
        "comms_gap": float(abs(int(comms[-1]) - int(r_comms[-1]))
                           / max(int(r_comms[-1]), 1)),
    }


def run(r: harness.Run, devices, *, t0: float, tracer) -> None:
    from repro.api import fit

    config, tr = r.cell.config, r.cell.traffic
    iters = int(tr["num_iters"])
    cfg, problem, (phi, labels), mesh = build(config, r.seed, devices)
    cfg = cfg.replace(num_iters=iters)
    harness.ready(fit(cfg, problem=problem, mesh=mesh))   # compiles
    answers, durations, res = [], [], None
    r.setup_s = time.monotonic() - t0
    stop = (f"; CG stop tol {cfg.cg_tol!r}, maxiter {cfg.cg_maxiter}"
            if cfg.primal == "cg" else "")
    harness.log(f"[{r.cell.name}] set-up {r.setup_s:.3f} s on {r.on}{stop}")
    tracer.start()
    with harness.CompileCounter() as counter, \
            harness.span("window", tracer.on):
        start = time.perf_counter()
        while True:
            t = time.perf_counter()
            r.attempted += 1
            try:
                with harness.span("fit", tracer.on):
                    res = harness.ready(fit(cfg, problem=problem,
                                            mesh=mesh))
            except Exception as e:  # a failed fit counts; go on
                r.failed += 1
                harness.log(f"[{r.cell.name}] fit failed: {e!r}")
            else:
                answers.append((res.theta, res.history["train_mse"],
                                res.history["comms"]))
            durations.append(time.perf_counter() - t)
            if time.perf_counter() - start >= r.seconds:
                break
        r.window_s = time.perf_counter() - start
    r.trace = tracer.stop()
    r.mem_peak = harness.memory_peak(devices)
    N, T, D = problem.feats.shape
    r.fit = {"iterations": iters * len(durations),
             "work": work.admm_iteration(N, T, D),
             "megastep_call": work.megastep_call(N, T, D)}
    harness.log(
        f"[{r.cell.name}] window {r.window_s:.6f} s, {len(durations)} fits "
        f"of {iters} iterations, per fit {np.round(durations, 6).tolist()} "
        f"s; compilations in the window {counter.counts}; memory peak "
        f"{r.mem_peak} bytes on {r.on}")

    # the program's answers to the host, its state freed, then the
    # reference on the benchmark's own inputs
    answers = [tuple(np.asarray(a) for a in ans) for ans in answers]
    del res, problem
    t = time.perf_counter()
    ref = reference_fit(config, phi, labels, iters, reference.REFERENCE)
    harness.log(f"[{r.cell.name}] reference {time.perf_counter() - t:.3f} "
                f"s on {r.on}; sends {int(ref[2][-1])} of {N * iters}, "
                f"final train MSE {float(ref[1][-1])!r}")
    worst: dict = {}
    for ans in answers:
        for k, v in gaps(ans, ref).items():
            worst[k] = max(worst.get(k, 0.0), v)
    limits = config["limits"]["fit"]
    r.checks = {k: (worst.get(k, float("inf")), float(limits[k]))
                for k in limits}
