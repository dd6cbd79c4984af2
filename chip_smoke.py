#!/usr/bin/env python3
"""Smoke run of the main path on the TPU, through the entry points a user
calls (`repro.api.fit`, `KernelModel`, `KernelServer`), at real size.

    python chip_smoke.py [--seed S]              # one chip, phases 1-4
    python chip_smoke.py --chips 4 [--seed S]    # the sharded big-D path

One process, no child processes. Phases on one chip:

  1. device check: a TPU is attached and the Pallas kernels compile (no
     interpret mode, which `$REPRO_PALLAS_INTERPRET` could force);
  2. fused fit at big D: COKE on `backend="fused"`, N=16 agents x 2048
     samples x D=16384 features (a 2.15 GB feature array), through the
     `coke_megastep` megakernel; one megastep is checked against the
     blockwise reference on the same full-size inputs;
  3. the paper's synthetic setup (DKLA and COKE on the simulator with the
     Cholesky primal) and the phase-2 problem on `backend="spmd"` with
     the CG primal;
  4. serving: per-agent thetas of phase 2 published to a `ModelRegistry`,
     scored by a multi-tenant `KernelServer` with the fused featurizer,
     every answer checked against `KernelModel.score_rows`.

With `--chips 4` only the feature-sharded big-D fit and serving path
runs (N=8 x 1024 x D=65536 over a 1x4 mesh), compared with the same fit
unsharded on one device.

Every failed check raises and the script exits non-zero. Timings end in
`block_until_ready` and name the device they ran on. The last line of
standard output is one JSON object:
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.api import FitConfig, KRRConfig, build_problem, fit  # noqa: E402
from repro.api import backends  # noqa: E402
from repro.configs.coke_krr import PAPER_SETUPS  # noqa: E402
from repro.kernels.coke_update.coke_update import coke_megastep  # noqa: E402
from repro.kernels.coke_update.ref import coke_megastep_ref  # noqa: E402
from repro.kernels.runtime import resolve_interpret  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402
from repro.serve import (KernelServeConfig, KernelServer,  # noqa: E402
                         ModelRegistry)

TRAIN_FRAC = 0.7  # data.synthetic._split keeps this share for training


def samples_for(train: int) -> int:
    """samples_per_agent whose training shard holds exactly `train` rows."""
    n = int(np.ceil(train / TRAIN_FRAC))
    while int(n * TRAIN_FRAC) > train:
        n -= 1
    while int(n * TRAIN_FRAC) < train:
        n += 1
    return n


# the big-D deployment of phase 2 (one chip) and of --chips 4
BIG_D = FitConfig(
    algorithm="coke", backend="fused", primal="gradient", graph="ring",
    krr=KRRConfig(num_agents=16, samples_per_agent=samples_for(2048),
                  num_features=16384, lam=5e-5, rho=1e-2, censor_v=0.1,
                  censor_mu=0.95),
    num_iters=30, inner_lr=0.1)
SHARDED = BIG_D.replace(
    backend="spmd", primal="cg", num_iters=5,
    krr=KRRConfig(num_agents=8, samples_per_agent=samples_for(1024),
                  num_features=65536, lam=5e-5, rho=1e-2, censor_v=0.1,
                  censor_mu=0.95))


def fail(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def check(cond, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)
    print(f"  ok: {msg}", flush=True)


def ready(out):
    """block_until_ready that also reaches into result dataclasses
    (`FitResult`, `BuiltProblem`), which are not pytrees."""
    if dataclasses.is_dataclass(out) and not isinstance(out, type):
        for field in dataclasses.fields(out):
            ready(getattr(out, field.name))
    else:
        jax.block_until_ready(out)
    return out


def timed(fn):
    t0 = time.perf_counter()
    out = ready(fn())
    return out, time.perf_counter() - t0


def fit_twice(cfg, problem, mesh=None):
    """First call compiles and runs, the second runs the cached program:
    -> (result, first-call seconds, steady seconds per iteration). The
    steady figure is the second call's wall time, host set-up included,
    over its iterations."""
    _, first = timed(lambda: fit(cfg, problem=problem, mesh=mesh))
    res, second = timed(lambda: fit(cfg, problem=problem, mesh=mesh))
    return res, first, second / cfg.resolved_iters


def check_trajectory(name: str, res, n_agents: int) -> None:
    mse = np.asarray(res.train_mse)
    check(np.all(np.isfinite(mse)), f"{name}: train_mse finite")
    check(mse[-1] < mse[0],
          f"{name}: train_mse falls {mse[0]:.6g} -> {mse[-1]:.6g}")
    sends = int(res.comms[-1])
    print(f"  {name}: {sends} of {n_agents * len(mse)} messages sent",
          flush=True)


# ---------------------------------------------------------------------------
# phase 1
# ---------------------------------------------------------------------------

def device_check(chips: int) -> str:
    try:
        devices = jax.devices()
    except RuntimeError as e:
        fail(f"no TPU found: {e}")
    dev = devices[0]
    if dev.platform != "tpu":
        fail(f"no TPU found: JAX's devices are {devices}")
    if len(devices) < chips:
        fail(f"--chips {chips} needs {chips} TPU devices, found "
             f"{len(devices)}")
    if resolve_interpret(None):
        fail("Pallas kernels would run in interpret mode "
             f"(REPRO_PALLAS_INTERPRET={os.environ.get('REPRO_PALLAS_INTERPRET')!r}); "
             "this run measures compiled kernels only")
    try:
        from importlib.metadata import version
        libtpu = version("libtpu")
    except Exception as e:  # reported, not needed by any phase
        libtpu = f"unknown ({type(e).__name__})"
    print(f"[device] {devices}", flush=True)
    print(f"[device] kind={dev.device_kind!r} count={len(devices)} "
          f"jax={jax.__version__} libtpu={libtpu}", flush=True)
    return f"{dev.device_kind} x{len(devices)}"


# ---------------------------------------------------------------------------
# phase 2
# ---------------------------------------------------------------------------

def fused_fit(seed: int, on: str):
    cfg = BIG_D.replace(krr=dataclasses.replace(BIG_D.krr, seed=seed))
    built, setup = timed(lambda: build_problem(cfg))
    problem = built.problem
    N, T, D = problem.feats.shape
    print(f"[fused-fit] N={N} T={T} D={D}: features "
          f"{problem.feats.nbytes / 1e9:.2f} GB, set-up {setup:.3f} s "
          f"on {on}", flush=True)

    # observe the megakernel chunk the fit dispatches: the admission gate
    # falls back to the spmd path without a word, so a fit that never
    # calls it did not take the megakernel
    calls = []
    chunk = backends._megastep_chunk

    def spy(*args, **kwargs):
        calls.append((args, kwargs))
        return chunk(*args, **kwargs)

    backends._megastep_chunk = spy
    try:
        res, first, per_iter = fit_twice(cfg, problem)
    finally:
        backends._megastep_chunk = chunk
    check(len(calls) == 2, "fused fit dispatched the megakernel chunk")
    args, kwargs = calls[0]
    hlo = chunk.lower(*args, **kwargs).compile().as_text()
    n_kernels = hlo.count('custom_call_target="tpu_custom_call"')
    check(n_kernels == 1,
          f"compiled chunk holds {n_kernels} tpu_custom_call (the "
          "megakernel, once in the scan's iteration body)")
    print(f"[fused-fit] compile+first run {first:.3f} s, steady "
          f"{per_iter * 1e3:.3f} ms/iter on {on}", flush=True)
    check_trajectory("fused-fit", res, N)
    sends = int(res.comms[-1])
    check(0 < sends < N * cfg.resolved_iters,
          f"censoring sent {sends} < N x iters = "
          f"{N * cfg.resolved_iters} messages")
    stats = jax.devices()[0].memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        print(f"[fused-fit] peak device memory "
              f"{stats['peak_bytes_in_use'] / 1e9:.3f} GB on {on}",
              flush=True)

    # one megastep on the full-size inputs vs the jitted blockwise
    # reference, from the fit's final carry
    params, cstate = res.state
    theta = params["theta"]
    hat = cstate["theta_hat"]["theta"]
    gamma = cstate["gamma"]["theta"]
    kw = dict(rho=problem.rho, lam=problem.lam, lr=cfg.inner_lr,
              offsets=cfg.graph_offsets)
    (out_k, xi_k), t_kernel = timed(lambda: coke_megastep(
        theta, hat, gamma, problem.feats, problem.labels, **kw))
    # The reference runs its dots at full f32 ("highest"); XLA's TPU
    # default would round them to bf16. The kernel's Mosaic dots keep f32
    # operands but may contract in bf16 passes, and sum in another order.
    # The update theta_new - theta is what the dots produce; a single
    # bf16 pass rounds each operand to 2^-9 relative, and the residual
    # phi theta - y cancels part of phi theta, so 2e-2 relative bounds
    # the update error. xi_sq is a sum of squares of that update's
    # difference from theta_hat: same bound.
    with jax.default_matmul_precision("highest"):
        out_r, xi_r = jax.block_until_ready(coke_megastep_ref(
            theta, hat, gamma, problem.feats, problem.labels, **kw))
    upd_k, upd_r = out_k - theta, out_r - theta
    err = float(jnp.linalg.norm(upd_k - upd_r) / jnp.linalg.norm(upd_r))
    xi_err = float(jnp.max(jnp.abs(xi_k - xi_r) / jnp.abs(xi_r)))
    print(f"[megastep] one call {t_kernel:.3f} s (compile included) on "
          f"{on}; update rel err {err:.3e}, xi_sq max rel err "
          f"{xi_err:.3e}", flush=True)
    check(err <= 2e-2, f"megastep update within 2e-2 of the reference "
          f"({err:.3e})")
    check(xi_err <= 2e-2, f"megastep xi_sq within 2e-2 of the reference "
          f"({xi_err:.3e})")
    check(bool(jnp.all(jnp.isfinite(out_k))), "megastep theta finite")
    return cfg, built, res


# ---------------------------------------------------------------------------
# phase 3
# ---------------------------------------------------------------------------

def simulator_and_spmd(seed: int, big_cfg, big_built, on: str) -> None:
    paper = dataclasses.replace(PAPER_SETUPS["synthetic"], seed=seed)
    sim = FitConfig(algorithm="dkla", krr=paper, backend="simulator",
                    primal="cholesky")
    problem = build_problem(sim).problem
    comms = {}
    for alg in ("dkla", "coke"):
        res, first, per_iter = fit_twice(sim.replace(algorithm=alg),
                                         problem)
        print(f"[simulator/{alg}] N={paper.num_agents} "
              f"D={paper.num_features} iters={sim.resolved_iters}: "
              f"compile+first run {first:.3f} s, steady "
              f"{per_iter * 1e3:.3f} ms/iter on {on}", flush=True)
        check_trajectory(f"simulator/{alg}", res, paper.num_agents)
        comms[alg] = int(res.comms[-1])
    check(comms["coke"] < comms["dkla"],
          f"COKE censors: {comms['coke']} < DKLA's {comms['dkla']} sends")

    spmd = big_cfg.replace(backend="spmd", primal="cg", num_iters=5)
    res, first, per_iter = fit_twice(spmd, big_built.problem)
    print(f"[spmd/cg] N={big_cfg.krr.num_agents} "
          f"D={big_cfg.krr.num_features} iters=5: compile+first run "
          f"{first:.3f} s, steady {per_iter * 1e3:.3f} ms/iter on {on}",
          flush=True)
    check_trajectory("spmd/cg", res, big_cfg.krr.num_agents)


# ---------------------------------------------------------------------------
# phase 4
# ---------------------------------------------------------------------------

def serving(seed: int, big_res, big_built, on: str) -> None:
    model = big_res.to_model(big_built.rff_params)
    thetas = np.asarray(model.thetas)
    rng = np.random.default_rng(seed)
    with tempfile.TemporaryDirectory() as root:
        registry = ModelRegistry(root)
        ids = [f"agent-{i:02d}" for i in range(4)]
        for i, mid in enumerate(ids):
            registry.publish(mid, dataclasses.replace(
                model, theta=jnp.asarray(thetas[i]), thetas=None))
        by_id = {mid: thetas[i] for i, mid in enumerate(ids)}
        server = KernelServer(model, KernelServeConfig(backend="fused"),
                              registry=registry, autostart=False)
        requests = []
        for _ in range(36):
            mid = ids[int(rng.integers(len(ids)))]
            x = rng.uniform(size=(int(rng.integers(200, 401)),
                                  model.input_dim)).astype(np.float32)
            requests.append((mid, x))

        def serve_all():
            futs = [(mid, x, server.submit(x, mid)) for mid, x in requests]
            return [(mid, x, np.asarray(f.result())) for mid, x, f in futs]

        server.start()
        try:
            _, first = timed(serve_all)
            answers, steady = timed(serve_all)
        finally:
            server.stop()
    rows = sum(x.shape[0] for _, x in requests)
    print(f"[serving] {len(requests)} requests, {rows} rows, D="
          f"{model.num_features}: first pass {first:.3f} s (compile "
          f"included), steady pass {steady:.3f} s on {on}", flush=True)

    # The server and score_rows run the same Pallas featurizer and the
    # same row-wise product; they differ only in padded batch shape, so
    # the row reductions may round differently: 1e-5 relative.
    worst = 0.0
    for mid, x, out in answers:
        ref = np.asarray(model.score_rows(
            x, np.broadcast_to(by_id[mid], (x.shape[0], thetas.shape[1])),
            backend="fused"))
        worst = max(worst, float(np.max(np.abs(out - ref))
                                 / max(float(np.max(np.abs(ref))), 1e-30)))
    check(worst <= 1e-5, f"every served answer matches score_rows "
          f"(fused) within 1e-5 relative ({worst:.3e})")

    # The Pallas featurizer against the XLA one at full f32 precision: the
    # kernel's x @ omega may contract in a bf16 pass, which moves the
    # cosine's argument by up to 2^-9 of |x omega| (<= ~10 here); the
    # errors of D=16384 features average out in the sum phi @ theta, so
    # 1e-2 of the largest prediction bounds it.
    mid, x, out = answers[0]
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(model.score_rows(
            x, np.broadcast_to(by_id[mid], (x.shape[0], thetas.shape[1])),
            backend="ref"))
    err = float(np.max(np.abs(out - ref)) / np.max(np.abs(ref)))
    check(err <= 1e-2, f"fused featurizer matches the f32 reference "
          f"within 1e-2 relative ({err:.3e})")


# ---------------------------------------------------------------------------
# --chips 4
# ---------------------------------------------------------------------------

def sharded(seed: int, on: str) -> None:
    cfg = SHARDED.replace(krr=dataclasses.replace(SHARDED.krr, seed=seed))
    built = build_problem(cfg)
    N, T, D = built.problem.feats.shape
    mesh = make_host_mesh(model=4)
    print(f"[sharded] N={N} T={T} D={D}: features "
          f"{built.problem.feats.nbytes / 1e9:.2f} GB, mesh "
          f"{dict(mesh.shape)}", flush=True)
    # Everything in this phase at full f32 ("highest") so that sharded
    # and unsharded differ only in layout: the sharded contraction over D
    # sums its four partial sums in another order. 5 warm-started CG
    # solves of 64 steps each on float32 keep that drift near 1e-5 of
    # theta's scale: 1e-4. Set process-wide, not as a context manager,
    # which is thread-local: the KernelServer traces its scorer on its
    # own collector thread.
    jax.config.update("jax_default_matmul_precision", "highest")
    shard_res, first_s, per_s = fit_twice(cfg, built.problem, mesh)
    plain_res, first_p, per_p = fit_twice(cfg, built.problem)
    print(f"[sharded] compile+first run {first_s:.3f} s, steady "
          f"{per_s * 1e3:.3f} ms/iter on {on} (1x4 mesh, f32 'highest' "
          "dots)", flush=True)
    print(f"[unsharded] compile+first run {first_p:.3f} s, steady "
          f"{per_p * 1e3:.3f} ms/iter on {jax.devices()[0].device_kind} "
          "x1 (f32 'highest' dots)", flush=True)

    theta = shard_res.theta
    devices = {s.device for s in theta.addressable_shards}
    shapes = {tuple(s.data.shape) for s in theta.addressable_shards}
    check(len(devices) == 4 and shapes == {(N, D // 4)},
          f"sharded theta is placed as {shapes} on {len(devices)} devices")
    a, b = np.asarray(plain_res.theta), np.asarray(theta)
    err = float(np.max(np.abs(a - b)) / np.max(np.abs(a)))
    check(err <= 1e-4, f"sharded theta matches unsharded within 1e-4 "
          f"relative ({err:.3e})")
    check(np.array_equal(np.asarray(plain_res.comms),
                         np.asarray(shard_res.comms)),
          "sharded and unsharded send decisions agree")
    check_trajectory("sharded/cg", shard_res, N)

    model = plain_res.to_model(built.rff_params)
    smodel = shard_res.to_model(built.rff_params).shard(mesh)
    for name, arr in (("omega", smodel.rff_params.omega),
                      ("theta", smodel.theta), ("thetas", smodel.thetas)):
        n_dev = len({s.device for s in arr.addressable_shards})
        check(n_dev == 4 and not arr.sharding.is_fully_replicated,
              f"model {name} {tuple(arr.shape)} split over {n_dev} devices")
    x = np.asarray(built.x_test).reshape(-1, built.x_test.shape[-1])[:512]
    want = np.asarray(model.predict(x))
    got = np.asarray(smodel.predict(x))
    with KernelServer(smodel, mesh=mesh) as server:
        served, t_serve = timed(lambda: server.predict(x))
    scale = float(np.max(np.abs(want)))
    for name, y in (("predict", got), ("KernelServer", served)):
        err = float(np.max(np.abs(y - want))) / scale
        check(err <= 1e-4, f"sharded {name} matches unsharded predict "
              f"within 1e-4 relative ({err:.3e})")
    print(f"[sharded] KernelServer answered {x.shape[0]} rows in "
          f"{t_serve:.3f} s (compile included) on {on}", flush=True)


# ---------------------------------------------------------------------------

def main(argv=None) -> None:
    parser = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    parser.add_argument("--seed", type=int, default=0,
                        help="seed of the synthetic data and RFF draw")
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4: run only the sharded big-D path")
    args = parser.parse_args(argv)

    on = device_check(args.chips)
    cache = enable_compile_cache()
    entries = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    print(f"[device] compile cache "
          f"{'on' if jax.config.jax_enable_compilation_cache else 'off'} "
          f"at {cache} ({entries} entries at start)", flush=True)

    if args.chips == 4:
        sharded(args.seed, on)
    else:
        cfg, built, res = fused_fit(args.seed, on)
        simulator_and_spmd(args.seed, cfg, built, on)
        serving(args.seed, res, built, on)

    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
