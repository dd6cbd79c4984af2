"""Backend routing for `fit()`: the same FitConfig runs on

  simulator — the in-process reference (all agents as a leading batch axis,
              neighbor exchange = adjacency matmul); driven by the Solver
              protocol directly from repro.api.fit.
  spmd      — the repro.distributed.consensus runtime: agent axis sharded
              over the mesh, neighbor exchange as jnp.roll (lowers to
              collective-permute), inexact one-step primal update.
  fused     — the Pallas hot path. On megakernel-admissible configs
              (dkla/coke, gradient primal, quadratic loss, static ring,
              no mesh/personalization) the whole ADMM iteration runs as
              ONE `coke_megastep` pallas_call substituted into the
              `core.step.StepProgram` primal+exchange stages, bit-equal
              to the unfused blockwise StepProgram reference
              (`kernels.coke_update.ref.coke_megastep_ref`). Everything
              else falls back to spmd with the augmented-gradient +
              censor-norm combine in the `coke_update` kernel. Kernels
              compile on TPU/GPU and interpret on CPU
              (repro.kernels.runtime.resolve_interpret).

The spmd/fused backends require a circulant graph family — the topology the
ring collectives implement — and are validated against the problem's
adjacency so a mismatched FitConfig fails loudly instead of silently
solving a different consensus problem.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.config import FitConfig, SolveContext
from repro.api.registry import Solver
from repro.api.solvers import (_per_agent_mse, _stacked_metrics,
                               _uncompressed_bits)
from repro.core import admm
from repro.core import comm as comm_mod
from repro.core import gossip as gossip_mod
from repro.core import losses as losses_mod
from repro.core import personalize as personalize_mod
from repro.core import step as step_mod
from repro.core.admm import Problem
from repro.core.graph import circulant
from repro.distributed import consensus as cns
from repro.distributed.sharding import shard_features, shard_problem
from repro.kernels.coke_update.coke_update import coke_megastep
from repro.kernels.coke_update.ref import coke_megastep_ref
from repro.optim.optimizers import OptConfig

#: debug/bench knob: route megakernel-admissible fused fits through the
#: blockwise unfused StepProgram reference (`coke_megastep_ref`) instead
#: of the pallas_call. Bit-identical by contract — the conformance tests
#: and `benchmarks/fused_bench.py` flip this to pin/time the two paths.
_MEGASTEP_USE_KERNEL = True


def _validate_topology(problem: Problem, offsets: tuple[int, ...]) -> None:
    N = problem.num_agents
    want = circulant(N, offsets).adjacency
    have = np.asarray(problem.adjacency)
    if not np.array_equal(have, want):
        raise ValueError(
            "spmd/fused backends implement circulant topologies (ring "
            f"collectives with offsets {offsets}); the problem's adjacency "
            "does not match — build it with FitConfig(graph='ring'/"
            "'circulant') or use backend='simulator'")


def _validate_schedule(problem: Problem, topology) -> None:
    """Each scheduled graph must be the circulant its offsets claim —
    otherwise the ring runtime silently solves a different consensus
    problem than the simulator."""
    N = problem.num_agents
    for i, off in enumerate(topology.offsets):
        off = tuple(off)
        seen = set()
        for o in off:
            pair = frozenset(((o % N), (-o) % N))
            if (2 * o) % N == 0 or pair in seen:
                raise ValueError(
                    f"offset {o} is degenerate on N={N} agents (the ±{o} "
                    "permutes alias the same neighbor, double-counting it "
                    "in the ring runtime); choose offsets with 2*o % N != 0")
            seen.add(pair)
        want = circulant(N, off).adjacency
        have = np.asarray(topology.adjacencies[i])
        if not np.array_equal(have, want):
            raise ValueError(
                f"topology schedule graph {i} does not match the circulant "
                f"with offsets {tuple(off)}; build the schedule with "
                "TopologySchedule.circulant_cycle or use "
                "backend='simulator'")


def _local_grads(problem: Problem, theta: jax.Array) -> jax.Array:
    N = problem.num_agents

    def g1(theta_i, phi, y):
        return jax.grad(losses_mod.local_empirical_risk)(
            theta_i, phi, y, problem.lam / N, problem.loss)

    return jax.vmap(g1)(theta, problem.feats, problem.labels)


def _resolve_consensus_primal(config: FitConfig, problem: Problem,
                              strategy: str) -> str:
    """The primal mode the distributed runtimes execute. "auto" keeps the
    legacy one-step inexact update up to the big-D crossover (bit-parity
    with existing spmd/fused trajectories), then switches to the exact
    matrix-free CG solve — the regime where one gradient step per round is
    both slow to converge and the only thing that used to exist. Explicit
    "cholesky" is rejected: these backends never materialize (D, D)."""
    if strategy not in ("dkla", "coke", "coke_et"):
        return "gradient"
    if config.primal == "cholesky":
        raise ValueError(
            "the spmd/fused backends never materialize per-agent (D, D) "
            "factors; use primal='cg' (exact, matrix-free) or "
            "'gradient'/'auto' (one-step inexact)")
    if config.primal == "cg":
        return admm.resolve_primal("cg", problem.feature_dim, problem.loss)
    if (config.primal == "auto" and problem.loss == "quadratic"
            and problem.feature_dim > admm.CG_CROSSOVER_DIM):
        return "cg"
    return "gradient"


def _cg_primal_solve(problem: Problem, cg_tol: float, cg_maxiter: int):
    """Adapt the matrix-free CG solve of (21a) to the consensus runtime's
    agent-stacked tree form: the runtime hands over (params, theta_hat,
    gamma, summed neighbor theta_hat, degree) and gets the exact primal
    back — no (D, D) array, warm-started from the previous iterate.

    Call this with the TRACED problem inside the jitted chunk — closing
    over a concrete Problem would embed feats (268 MB at D=65536) as a
    trace-time constant and, passed as a jit static arg, the fresh closure
    would miss the compilation cache on every fit()."""
    def solve(params, theta_hat, gamma, nbr_sum, deg):
        deg_vec = jnp.broadcast_to(
            jnp.asarray(deg, problem.feats.dtype),
            (problem.num_agents,))
        theta = admm._primal_cg(
            problem, gamma["theta"], theta_hat["theta"], nbr_sum["theta"],
            deg_vec, theta0=params["theta"],
            tol=cg_tol, maxiter=cg_maxiter)
        return {"theta": theta.astype(params["theta"].dtype)}

    return solve


@partial(jax.jit, static_argnames=("ccfg", "opt_cfg", "num_iters",
                                   "primal_mode", "cg_tol", "cg_maxiter",
                                   "pz_metric"))
def _consensus_chunk(problem, params, cstate, oracle, comm, gossip,
                     personalize, ccfg, opt_cfg, num_iters,
                     primal_mode=None, cg_tol=1e-8, cg_maxiter=64,
                     pz_metric=False):
    # the exact primal is built HERE, from the traced problem argument:
    # the static jit key stays the value-hashable (ccfg, opt_cfg, mode,
    # tol, maxiter) tuple, so repeated fits share one compilation
    primal_solve = (_cg_primal_solve(problem, cg_tol, cg_maxiter)
                    if primal_mode == "cg" else None)
    n_agents = problem.num_agents

    def body(carry, _):
        params, cstate = carry
        # gossip: the round's participation mask, drawn from the SAME
        # CommState key + iteration fold as the simulator path — both
        # backends sample identical wake-up schedules, so comms/bits
        # histories agree exactly across backends. Under churn, the same
        # alive/joined masks as the simulator's table_view thread into
        # the ring exchange (alive-weighted degrees + masked permutes).
        participate = alive = joined = None
        if gossip is not None:
            k = cstate["step"] + 1
            if gossip.has_churn:
                alive = gossip.alive_at(k)
                joined = alive & ~gossip.alive_at(k - 1)
            participate = gossip_mod.participation_mask(
                cstate["comm"].key, k, n_agents, gossip, alive)
        # personalization: refresh the learned graph if due (same cadence
        # and affinity computation as the simulator — graphs match
        # bit-for-bit), then run the round dense on it
        adjacency = None
        if personalize is not None:
            adjacency = personalize_mod.maybe_update(
                personalize, params["theta"], cstate["step"] + 1,
                cstate["adjacency"])
        if primal_solve is None:
            grads = {"theta": _local_grads(problem, params["theta"])}
        else:  # exact primal: the local gradient is folded into the solve
            grads = {"theta": jnp.zeros_like(params["theta"])}
        params, cstate, extra = cns.consensus_update(
            ccfg, opt_cfg, params, grads, cstate, comm=comm,
            primal_solve=primal_solve, participate=participate,
            adjacency=adjacency, alive=alive, joined=joined)
        if personalize is not None:
            cstate = dict(cstate, adjacency=adjacency)
        with jax.named_scope("coke.history"):
            bits = extra.get("bits")
            if bits is None:  # policy-unaware strategy (cta): full precision
                bits = _uncompressed_bits(problem, cstate["comms"])
            m = _stacked_metrics(problem, params["theta"], cstate["comms"],
                                 bits)
            m.update(extra)
            if pz_metric:  # key-parity with the simulator personalized path
                m["per_agent_mse"] = _per_agent_mse(problem,
                                                    params["theta"])
            if oracle is not None:
                m["dist_to_oracle"] = jnp.max(jnp.linalg.norm(
                    params["theta"] - oracle, axis=-1))
        return (params, cstate), m

    (params, cstate), hist = jax.lax.scan(body, (params, cstate), None,
                                          length=num_iters)
    return (params, cstate), hist


class _FusedCarry(NamedTuple):
    """core.step.run_step carry for the megakernel path — the six
    canonical fields as bare (N, D) arrays (the consensus-state dicts are
    unwrapped at the chunk boundary and rewrapped after the scan)."""
    theta: jax.Array
    theta_hat: jax.Array
    gamma: jax.Array
    step: jax.Array
    comms: jax.Array
    comm: object


@partial(jax.jit, static_argnames=("ccfg", "num_iters", "lr",
                                   "use_kernel"))
def _megastep_chunk(problem, params, cstate, oracle, comm, gossip, ccfg,
                    num_iters, lr, use_kernel=True):
    """The fused-backend megakernel chunk: one `coke_megastep`
    pallas_call per iteration, substituted into the StepProgram
    primal+exchange stages (`primal_owns_exchange=True` — the kernel
    reads the ring-rolled neighbor rows itself, so `run_step` skips the
    pre-primal permute). With use_kernel=False the same program runs the
    blockwise unfused reference — bitwise-identical histories, which is
    the megakernel's conformance contract.

    Metric keys match `_consensus_chunk` exactly (train_mse / comms /
    consensus_gap / bits / send_frac [+ dist_to_oracle]), so every
    cross-backend history comparison works unchanged. The circulant
    neighbor caches (nbr_left/nbr_right) in the consensus state are
    carried untouched: the kernel re-reads theta_hat rows each step
    instead of consuming the cached dual-update fetch."""
    chain = (ccfg.comm_chain() if comm is None
             else comm_mod.as_chain(comm))
    n_agents = problem.num_agents
    offsets = ccfg.offsets
    fn = coke_megastep if use_kernel else coke_megastep_ref

    def nbr_sum(x):
        out = None
        for o in offsets:
            both = jnp.roll(x, o, axis=0) + jnp.roll(x, -o, axis=0)
            out = both if out is None else out + both
        return out

    view = step_mod.GraphView(
        deg=jnp.full((n_agents,), ccfg.degree, jnp.float32),
        nbr_sum=nbr_sum)

    def primal(k, g, theta0, theta_hat0, gamma0, nbr_hat):
        theta_new, _xi_sq = fn(
            theta0, theta_hat0, gamma0, problem.feats, problem.labels,
            rho=ccfg.rho, lam=problem.lam, lr=lr, offsets=offsets)
        # _xi_sq — the kernel's fused censor-norm partial sums,
        # ||theta_new - theta_hat||^2 — is validated against the censor
        # policy in tests; the portable `chain.apply` recomputes the
        # norm so the decision bits stay identical on every backend.
        return theta_new.astype(theta0.dtype), {}

    program = step_mod.StepProgram(
        chain=chain, rho=ccfg.rho, exchange=lambda state, k: view,
        primal=primal,
        comm_decide=(None if gossip is None
                     else step_mod.sampled_stage(gossip)),
        primal_owns_exchange=True)

    def body(carry, _):
        st, opt = carry
        new_st, _ = step_mod.run_step(program, st)
        # the optimizer step is fused into the kernel (theta - lr*g_aug,
        # bitwise sgd); keep the carried slot's step count in sync
        if isinstance(opt, dict) and "count" in opt:
            opt = dict(opt, count=opt["count"] + 1)
        with jax.named_scope("coke.history"):
            bits = jnp.sum(new_st.comm.bits)
            m = _stacked_metrics(problem, new_st.theta, new_st.comms, bits)
            m["send_frac"] = ((new_st.comms - st.comms).astype(jnp.float32)
                              / n_agents)
            m["bits"] = bits
            if oracle is not None:
                m["dist_to_oracle"] = jnp.max(jnp.linalg.norm(
                    new_st.theta - oracle, axis=-1))
        return (new_st, opt), m

    st0 = _FusedCarry(
        theta=params["theta"], theta_hat=cstate["theta_hat"]["theta"],
        gamma=cstate["gamma"]["theta"], step=cstate["step"],
        comms=cstate["comms"], comm=cstate["comm"])
    (st, opt), hist = jax.lax.scan(body, (st0, cstate["opt"]), None,
                                   length=num_iters)
    new_params = {"theta": st.theta}
    new_cstate = dict(cstate, opt=opt, step=st.step, comms=st.comms,
                      comm=st.comm, theta_hat={"theta": st.theta_hat},
                      gamma={"theta": st.gamma})
    return (new_params, new_cstate), hist


@partial(jax.jit, static_argnames=("ccfg", "num_iters", "lam", "lr",
                                   "eta"))
def _stream_chunk(stream, params, cstate, comm, gossip, personalize,
                  ccfg, num_iters, lam, lr, eta):
    n_agents = stream.num_agents

    def body(carry, _):
        params, cstate = carry
        participate = alive = joined = None
        if gossip is not None:  # same draw/masks as the simulator
            k = cstate["step"] + 1
            if gossip.has_churn:
                alive = gossip.alive_at(k)
                joined = alive & ~gossip.alive_at(k - 1)
            participate = gossip_mod.participation_mask(
                cstate["comm"].key, k, n_agents, gossip, alive)
        adjacency = None
        if personalize is not None:  # same refresh as the simulator
            adjacency = personalize_mod.maybe_update(
                personalize, params["theta"], cstate["step"] + 1,
                cstate["adjacency"])
        feats, labels = stream.round_batch(cstate["step"])
        params, cstate, extra = cns.stream_update(
            ccfg, params, cstate, feats, labels,
            lam=lam, lr=lr, eta=eta, comm=comm, participate=participate,
            adjacency=adjacency, alive=alive, joined=joined)
        if personalize is not None:
            cstate = dict(cstate, adjacency=adjacency)
        # exactly the simulator's _stream_metrics keys — streaming
        # histories are key-identical across backends, so the conformance
        # harness can compare any pair with exact="*"
        m = {"train_mse": extra["instant_mse"],
             "instant_mse": extra["instant_mse"],
             "comms": cstate["comms"],
             "consensus_gap": cns.consensus_gap(params),
             "bits": extra["bits"]}
        return (params, cstate), m

    return jax.lax.scan(body, (params, cstate), None, length=num_iters)


def stream_consensus_runner(config: FitConfig, solver: Solver, stream,
                            ctx: SolveContext, theta0=None):
    """-> (carry0, chunk_fn, theta_fn) for fit_stream's spmd backend: the
    ring runtime's `stream_update` (collective-permute neighbor exchange,
    shared `core.comm` decision code) over the StreamProblem's rounds.
    Requires the circulant graph family, like the batch consensus path —
    personalized runs included: their warmup phase executes the exact
    ring-permute program before the learned dense graph takes over."""
    offsets = config.graph_offsets
    _validate_topology(stream, offsets)

    # stream_update reads only rho / offsets / degree from the config —
    # strategy and the CTA mix_weight play no role on the streaming path
    ccfg = cns.ConsensusConfig(rho=stream.rho, offsets=offsets)

    # the solver's policy view of the configured chain (online_dkla strips
    # censor thresholds), traced into the compiled chunk
    chain = solver._policy(ctx)
    eta = solver._eta(ctx)

    N, D = stream.num_agents, stream.feature_dim
    if theta0 is None:
        theta = jnp.zeros((N, D), stream.feats.dtype)
    else:
        theta = jnp.broadcast_to(
            jnp.asarray(theta0, stream.feats.dtype), (N, D))
    params = {"theta": theta}
    cstate = cns.init_stream_state(ccfg, theta, comm=chain)
    pz_live = ctx.personalization is not None and not ctx.pz_warmup
    if pz_live:
        cstate["adjacency"] = jnp.asarray(stream.adjacency, jnp.float32)
    personalize = ctx.personalization if pz_live else None

    gplan = ctx.gossip if ctx.exec == "gossip" else None

    def chunk_fn(carry, n):
        params, cstate = carry
        return _stream_chunk(stream, params, cstate, chain, gplan,
                             personalize, ccfg=ccfg, num_iters=n,
                             lam=stream.lam, lr=ctx.online_lr, eta=eta)

    return (params, cstate), chunk_fn, lambda carry: carry[0]["theta"]


def consensus_runner(config: FitConfig, solver: Solver, problem: Problem,
                     ctx: SolveContext, oracle: jax.Array | None,
                     mesh=None):
    """-> (carry0, chunk_fn, theta_fn) for the spmd / fused backends.

    mesh — optional jax mesh; when given, the Problem and the consensus
    carry (theta / theta_hat / gamma / neighbor caches) are placed with the
    feature dim sharded over the mesh's "model" axis and the agent dim over
    its batch axes (distributed.sharding.feature_spec), so each device
    holds (N, D/shards) slices and the censor norm reduces with one psum.
    """
    strategy = solver.consensus_strategy
    if strategy is None:
        raise ValueError(
            f"solver {solver.name!r} has no distributed strategy; "
            "use backend='simulator'")
    primal_mode = _resolve_consensus_primal(config, problem, strategy)
    offset_schedule = None
    if config.topology is not None:
        offset_schedule = config.topology.offsets
        if offset_schedule is None:
            raise ValueError(
                "the spmd/fused backends implement circulant topologies; "
                "give the TopologySchedule its per-graph `offsets` (e.g. "
                "TopologySchedule.circulant_cycle) or use "
                "backend='simulator'")
        _validate_schedule(problem, config.topology)
        offsets = offset_schedule[0]
    else:
        offsets = config.graph_offsets
        _validate_topology(problem, offsets)

    v, mu = config.resolved_censor
    k = len(offsets)
    ccfg = cns.ConsensusConfig(
        strategy=strategy, rho=problem.rho, censor_v=v, censor_mu=mu,
        offsets=offsets, offset_schedule=offset_schedule,
        # per-neighbor Metropolis weight on a 2k-regular circulant
        mix_weight=k / (2.0 * k + 1.0),
        use_fused_kernel=config.backend == "fused")
    lr = ctx.cta_lr if strategy == "cta" else ctx.inner_lr
    opt_cfg = OptConfig(kind="sgd", lr=lr)

    # the solver's policy view of the configured chain (e.g. DKLA strips
    # the censor thresholds), traced into the compiled chunk
    chain = (solver._policy(ctx) if getattr(solver, "comm_aware", False)
             else None)

    N, _, D = problem.feats.shape
    params = {"theta": jnp.zeros((N, D), problem.feats.dtype)}
    cstate = cns.init_consensus_state(ccfg, opt_cfg, params, comm=chain)

    if mesh is not None:
        # the sharded problem flows into the chunk as an argument, so the
        # CG matvec built inside runs on the (N, D/shards) slices
        problem = shard_problem(problem, mesh)
        params = shard_features(params, mesh, N)
        cstate = shard_features(cstate, mesh, N)

    # personalized live phase: the learned (N, N) graph rides in the
    # carry, added after the feature-dim placement above (it has no
    # feature dim to shard). The warmup phase runs the exact static
    # program — no adjacency in the carry, no graph machinery traced.
    pz_live = ctx.personalization is not None and not ctx.pz_warmup
    if pz_live:
        cstate["adjacency"] = jnp.asarray(problem.adjacency, jnp.float32)
    personalize = ctx.personalization if pz_live else None
    pz_metric = ctx.personalization is not None

    gplan = ctx.gossip if ctx.exec == "gossip" else None

    # megakernel admission: one pallas_call per iteration, substituted
    # into the StepProgram primal+exchange stages. The gate mirrors what
    # the kernel bakes in statically: a fixed circulant (no schedule, no
    # learned graph, no churn — churn-fused is already rejected by the
    # capabilities table), the one-step gradient primal on the quadratic
    # loss, and an unsharded carry. Everything outside falls back to the
    # legacy spmd+coke_update path below, bit-identical to before.
    use_mega = (config.backend == "fused"
                and strategy in ("dkla", "coke")
                and primal_mode == "gradient"
                and problem.loss == "quadratic"
                and offset_schedule is None
                and mesh is None
                and ctx.personalization is None)
    if use_mega:
        def mega_chunk_fn(carry, n):
            params, cstate = carry
            return _megastep_chunk(problem, params, cstate, oracle,
                                   chain, gplan, ccfg=ccfg, num_iters=n,
                                   lr=lr,
                                   use_kernel=_MEGASTEP_USE_KERNEL)
        return (params, cstate), mega_chunk_fn, \
            lambda carry: carry[0]["theta"]

    def chunk_fn(carry, n):
        params, cstate = carry
        return _consensus_chunk(problem, params, cstate, oracle, chain,
                                gplan, personalize, ccfg=ccfg,
                                opt_cfg=opt_cfg, num_iters=n,
                                primal_mode=primal_mode,
                                cg_tol=ctx.cg_tol,
                                cg_maxiter=ctx.cg_maxiter,
                                pz_metric=pz_metric)

    return (params, cstate), chunk_fn, lambda carry: carry[0]["theta"]
