"""Fit cells: back-to-back `repro.api.fit` calls of the traffic's fixed
iteration count on one problem built in set-up.

Set-up draws the data and the random features from the seed, builds the
training features on the device, hands them to the program as a
`Problem` and runs one warm-up fit, which compiles. The window then runs fits back
to back; the fit that is running when `--seconds` pass finishes and
counts. Every fit of the window is compared with the plain reference.
"""
from __future__ import annotations

import time

import numpy as np

from chipbench import data, harness, reference, work


def build(config: dict, seed: int, devices):
    """-> (FitConfig, Problem, inputs) from the configuration."""
    import jax
    import jax.numpy as jnp

    from repro.api import FitConfig, KRRConfig, make_problem
    from repro.core.graph import ring

    d, D = config["input_dim"], config["num_features"]
    N, T = config["num_agents"], config["samples_per_agent"]
    f = config["fit"]
    x, y = data.paper_synthetic(N, T, seed, input_dim=d)
    omega, bias = data.rff_draw(seed, d, D, config["bandwidth"])
    with jax.default_device(devices[0]):
        phi = data.features(jnp.asarray(x), omega, bias)
        labels = jnp.asarray(y)
    problem = make_problem(phi, labels, ring(N), lam=f["lam"], rho=f["rho"])
    cfg = FitConfig(
        algorithm=f["algorithm"], backend=f["backend"], primal=f["primal"],
        graph="ring", num_iters=None, inner_lr=f["inner_lr"],
        krr=KRRConfig(num_agents=N, samples_per_agent=T, num_features=D,
                      bandwidth=config["bandwidth"], lam=f["lam"],
                      rho=f["rho"], censor_v=f["censor_v"],
                      censor_mu=f["censor_mu"], seed=seed,
                      mapping=config["mapping"]))
    return cfg, problem, (phi, labels)


def reference_fit(config: dict, phi, labels, iters: int, arith):
    """The reference's (theta, train MSE, cumulative sends) for one fit."""
    f = config["fit"]
    if f["primal"] != "gradient":
        raise NotImplementedError(f"no reference for primal={f['primal']!r}")
    dtype, precision = arith
    theta, mse, comms = reference.coke_gradient_fit(
        phi, labels, lam=f["lam"], rho=f["rho"], v=f["censor_v"],
        mu=f["censor_mu"], lr=f["inner_lr"], iters=iters, dtype=dtype,
        precision=precision)
    return np.asarray(theta), np.asarray(mse), np.asarray(comms)


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
    cfg, problem, (phi, labels) = build(config, r.seed, devices)
    cfg = cfg.replace(num_iters=iters)
    harness.ready(fit(cfg, problem=problem))   # compiles
    answers, durations, res = [], [], None
    r.setup_s = time.monotonic() - t0
    harness.log(f"[{r.cell.name}] set-up {r.setup_s:.3f} s on {r.on}")
    tracer.start()
    with harness.CompileCounter() as counter, \
            harness.span("window", tracer.on):
        start = time.perf_counter()
        while True:
            t = time.perf_counter()
            r.attempted += 1
            try:
                with harness.span("fit", tracer.on):
                    res = harness.ready(fit(cfg, problem=problem))
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
