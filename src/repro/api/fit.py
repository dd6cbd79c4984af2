"""`fit(config) -> FitResult` — the one driver for every algorithm/backend
— and its streaming sibling `fit_stream(config) -> FitResult` for the
online family over per-agent minibatch streams.

The driver owns the `lax.scan` iteration loop, the per-iteration metric
recording (train MSE, cumulative transmissions, consensus gap, optional
distance-to-oracle; for streams the regret-protocol instantaneous MSE and
cumulative bits), and optional chunked host callbacks for streaming
progress. Algorithm math lives in the registered solvers; distributed
execution lives in repro.api.backends.

Compilation contract: the censor thresholds (v, mu) enter the compiled loop
as traced array data, so a sweep over censor schedules — the paper's tuning
protocol — reuses ONE compiled fit loop per (problem shape, algorithm,
num_iters) instead of retracing per float pair as the legacy
`core.admm.run(schedule-as-static)` entry point did.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp

from repro.api.backends import consensus_runner, stream_consensus_runner
from repro.api.capabilities import check_fit, check_stream
from repro.api.config import FitConfig, FitResult, SolveContext
from repro.api.problems import StreamProblem, build_problem, build_stream
from repro.api.registry import Solver, get_solver
from repro.core import ridge
from repro.core.admm import Problem

ProgressCb = Callable[[int, dict], None]


@partial(jax.jit, static_argnames=("solver", "num_iters"))
def _simulator_chunk(solver: Solver, problem: Problem, ctx: SolveContext,
                     host_aux, state, oracle, num_iters: int):
    aux = solver.prepare_traced(problem, ctx, host_aux)

    def body(state, _):
        state = solver.step(problem, ctx, aux, state)
        with jax.named_scope("coke.history"):
            m = solver.metrics(problem, ctx, aux, state)
            if oracle is not None:
                m["dist_to_oracle"] = jnp.max(jnp.linalg.norm(
                    solver.theta_of(state) - oracle, axis=-1))
        return state, m

    return jax.lax.scan(body, state, None, length=num_iters)


def _simulator_runner(config: FitConfig, solver: Solver, problem: Problem,
                      ctx: SolveContext, oracle, mesh=None):
    host_aux = solver.prepare_host(problem, ctx)
    state0 = solver.init_state(problem, ctx)
    if mesh is not None:
        from repro.distributed.sharding import shard_features, shard_problem

        problem = shard_problem(problem, mesh)
        state0 = shard_features(state0, mesh, problem.num_agents)

    def chunk_fn(state, n):
        return _simulator_chunk(solver, problem, ctx, host_aux, state,
                                oracle, num_iters=n)

    return state0, chunk_fn, solver.theta_of


def _chunked_scan(chunk_fn, carry, num_iters: int, chunk_size: int | None,
                  progress_cb: ProgressCb | None):
    """Run the scan in host-visible chunks; with chunk_size=None this is a
    single scan, trajectory-identical to the legacy monolithic drivers."""
    hists, done = [], 0
    while True:
        n = num_iters - done if chunk_size is None else min(
            chunk_size, num_iters - done)
        with jax.profiler.TraceAnnotation("repro.fit.chunk"):
            # n == 0 still yields (0,)-histories
            carry, h = chunk_fn(carry, n)
        done += n
        hists.append(h)
        if progress_cb is not None and n > 0:
            progress_cb(done, jax.tree.map(lambda a: a[-1], h))
        if done >= num_iters:
            break
    if len(hists) == 1:
        return carry, hists[0]
    return carry, jax.tree.map(lambda *xs: jnp.concatenate(xs), *hists)


def _pz_enter_live(carry, adjacency):
    """Attach the starting adjacency when a personalized fit crosses the
    warmup -> live boundary: the live program's carry holds the learned
    graph as loop state, the warmup program's carry does not."""
    from repro.api.solvers import OnlineFitState
    from repro.core.admm import COKEState
    from repro.core.personalize import PersonalizedState

    A0 = jnp.asarray(adjacency, jnp.float32)
    if isinstance(carry, OnlineFitState):
        return carry._replace(adjacency=A0)
    if isinstance(carry, COKEState):
        return PersonalizedState(carry, A0)
    params, cstate = carry  # spmd/fused (params, cstate) carry
    return params, dict(cstate, adjacency=A0)


def phase_plan(ctx: SolveContext, num_iters: int, adjacency):
    """Decompose one fit into its phased program: a tuple of
    (phase_ctx, num_iters, enter_fn) where enter_fn (None on the first
    phase) transforms the carry at the phase boundary. Ordinary fits are
    one phase; a personalized fit with warmup > 0 is the two-phase
    warmup -> live program. The plan is the *data* both drivers share:
    fit()/fit_stream() walk it through the chunked host loop, and
    sweep()'s vmapped scan replays the same phases inside one compiled
    program — which is what makes personalization-aware sweeps possible.

    Iterations 1..warmup run a SEPARATE compiled program
    (ctx.pz_warmup=True) that takes the exact static-consensus code path —
    no graph machinery in its trace — so the warmup prefix is
    bit-identical to a personalization=None run by construction rather
    than by XLA fusion luck (a lax.cond in the scan body measurably
    perturbs float rounding). A zero-length live phase (warmup >=
    num_iters) still applies its carry transform, so the final state
    carries the adjacency either way."""
    if ctx.personalization is None:
        return ((ctx, num_iters, None),)
    W = min(int(ctx.personalization.warmup), num_iters)
    if W <= 0:
        return ((ctx, num_iters, None),)
    ctx_warm = dataclasses.replace(ctx, pz_warmup=True)
    return ((ctx_warm, W, None),
            (ctx, num_iters - W,
             lambda carry: _pz_enter_live(carry, adjacency)))


def _phased_runner(make_runner, plan):
    """Drive a phase_plan through the chunked host loop: one runner per
    phase, carries handed across boundaries through the plan's enter
    transforms, histories concatenated (phase metrics share one key set —
    the key-parity contract the personalized metrics keep)."""
    if len(plan) == 1 and plan[0][2] is None:
        return make_runner(plan[0][0])
    runners = [make_runner(c) for c, _, _ in plan]
    ends, total = [], 0
    for _, n, _ in plan:
        total += n
        ends.append(total)
    pos = {"done": 0, "phase": 0}

    def chunk_fn(carry, n):
        hists, left = [], n
        while True:
            i = pos["phase"]
            m = min(left, ends[i] - pos["done"])
            carry, h = runners[i][1](carry, m)
            pos["done"] += m
            left -= m
            hists.append(h)
            # cross every boundary reached — including with 0 iterations
            # left, so a final chunk still applies the carry transform
            while (pos["phase"] < len(ends) - 1
                   and pos["done"] >= ends[pos["phase"]]):
                pos["phase"] += 1
                enter = plan[pos["phase"]][2]
                if enter is not None:
                    carry = enter(carry)
            if left == 0:
                break
        if len(hists) == 1:
            return carry, hists[0]
        return carry, jax.tree.map(lambda *xs: jnp.concatenate(xs), *hists)

    return runners[0][0], chunk_fn, runners[-1][2]


def _prepare_fit(config: FitConfig, problem: Problem | None, oracle,
                 mesh):
    """fit()'s host work before the first chunk dispatch: admission, the
    problem and oracle, the runners and their initial carry. -> (carry0,
    chunk_fn, theta_fn, rff_params)."""
    if isinstance(problem, StreamProblem):
        raise ValueError(
            "fit() drives batch problems; run a StreamProblem through "
            "fit_stream(config, stream=...)")
    solver = get_solver(config.algorithm)
    check_fit(config, solver)
    rff_params = None
    if problem is None:
        built = build_problem(config)
        problem, rff_params = built.problem, built.rff_params
    if oracle is None and config.record_oracle_distance:
        oracle = ridge.rf_ridge(problem.feats, problem.labels, problem.lam)
    if config.topology is not None and (
            config.topology.num_agents != problem.num_agents):
        raise ValueError(
            f"topology schedule is over {config.topology.num_agents} "
            f"agents but the problem has {problem.num_agents}")

    ctx = SolveContext.from_config(config, num_agents=problem.num_agents)

    def make_runner(c: SolveContext):
        if config.backend == "simulator":
            return _simulator_runner(config, solver, problem, c, oracle,
                                     mesh=mesh)
        return consensus_runner(config, solver, problem, c, oracle,
                                mesh=mesh)

    carry0, chunk_fn, theta_fn = _phased_runner(
        make_runner, phase_plan(ctx, config.resolved_iters,
                                problem.adjacency))
    return carry0, chunk_fn, theta_fn, rff_params


def fit(config: FitConfig, problem: Problem | None = None, *,
        progress_cb: ProgressCb | None = None,
        oracle: jax.Array | None = None,
        mesh=None) -> FitResult:
    """Run `config.algorithm` on `config.backend` and record the paper's
    evaluation trajectories.

    problem     — an existing `admm.Problem`; None builds one from
                  config.krr / config.graph (see repro.api.build_problem).
    progress_cb — called as progress_cb(iters_done, last_metrics) after
                  every `config.chunk_size` iterations.
    oracle      — theta* (D,) for per-iteration distance-to-oracle; computed
                  via the closed form when `config.record_oracle_distance`
                  is set and no oracle is passed.
    mesh        — optional jax mesh for the big-D path: the problem's
                  feature dim shards over the mesh's "model" axis and the
                  agent dim over its batch axes (theta/theta_hat/gamma live
                  as (N, D/shards) per device; see
                  distributed.sharding.feature_spec). Pair with
                  primal="cg" — a sharded (D, D) Cholesky factor would
                  defeat the point.

    In a `jax.profiler` trace the call is the host span `repro.fit`, its
    work before the first dispatch `repro.fit.prepare`, and each chunk
    dispatch `repro.fit.chunk`.
    """
    with jax.profiler.TraceAnnotation("repro.fit"):
        with jax.profiler.TraceAnnotation("repro.fit.prepare"):
            carry0, chunk_fn, theta_fn, rff_params = _prepare_fit(
                config, problem, oracle, mesh)
        carry, history = _chunked_scan(chunk_fn, carry0,
                                       config.resolved_iters,
                                       config.chunk_size, progress_cb)
        return FitResult(config=config, state=carry, history=history,
                         theta=theta_fn(carry), rff_params=rff_params)


def fit_stream(config: FitConfig, stream: StreamProblem | None = None, *,
               theta0: jax.Array | None = None,
               progress_cb: ProgressCb | None = None) -> FitResult:
    """Run a streaming solver (`online_dkla` / `online_coke` / `qc_odkla`)
    over a per-agent minibatch stream and record the regret-style history
    (instantaneous pre-update MSE, cumulative comms/bits, consensus gap)
    through the same chunked-scan driver as `fit()`.

    stream      — an existing `StreamProblem`; None builds one from
                  config.krr / config.stream / config.online_batch with
                  one round per iteration (see repro.api.build_stream).
    theta0      — optional warm start: (D,) or (N, D) parameters every
                  agent begins from (theta AND last-broadcast theta_hat) —
                  what `KernelModel.partial_fit` passes.
    progress_cb — as in fit(): called after every config.chunk_size
                  iterations with (iters_done, last_metrics).

    The result deploys exactly like a batch fit: `fit_stream(...)
    .to_model()` yields a `KernelModel` (predict / evaluate / save /
    serve) whose RFF map is the stream's featurization.
    """
    solver = get_solver(config.algorithm)
    check_stream(config, solver)
    rff_params = None
    if stream is None:
        built = build_stream(config)
        stream, rff_params = built.stream, built.rff_params
    if stream.adjacency.shape != (stream.num_agents, stream.num_agents):
        raise ValueError(
            f"stream adjacency {stream.adjacency.shape} does not match its "
            f"{stream.num_agents} agents")

    ctx = SolveContext.from_config(config, num_agents=stream.num_agents)

    def make_runner(c: SolveContext):
        if config.backend == "simulator":
            return _simulator_runner(config, solver, stream, c, None)
        return stream_consensus_runner(config, solver, stream, c,
                                       theta0=theta0)

    carry0, chunk_fn, theta_fn = _phased_runner(
        make_runner, phase_plan(ctx, config.resolved_iters,
                                stream.adjacency))
    if config.backend == "simulator" and theta0 is not None:
        carry0 = solver.warm_start(carry0, theta0)

    carry, history = _chunked_scan(chunk_fn, carry0, config.resolved_iters,
                                   config.chunk_size, progress_cb)
    return FitResult(config=config, state=carry, history=history,
                     theta=theta_fn(carry), rff_params=rff_params)
