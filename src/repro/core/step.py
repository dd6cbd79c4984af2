"""The step-composition layer: ONE per-iteration skeleton for the whole
solver family.

Every algorithm in this repo — DKLA / COKE (batch ADMM), the online
variants and QC-ODKLA (streaming), their gossip forms, and the
personalized learned-graph forms — iterates the same six named stages:

    featurize    minibatch predictions / residual gradient (streaming
                 only; batch solvers read pre-featurized Problem.feats)
    primal       the (21a) argmin (closed form / CG / gradient) or the
                 streaming augmented-Lagrangian step
    comm_decide  who speaks: gossip participation sampling (and, inside
                 the comm chain, the censor/quantize/drop decisions)
    exchange     the neighbor view: dense `A @ x` on the simulator,
                 NeighborTable gathers under gossip, ring permutes on the
                 spmd backend, a per-k scheduled graph under topology
    dual         the (21b) dual ascent against the fresh broadcasts
    record       transmission / bit accounting

Before this layer the skeleton was hand-wired once per (backend × exec ×
workload) cell; now `run_step` owns the ordering and the masking/dual/
record tail, and each solver step is a thin *stage assembly*: an
`exchange` stage producing a `GraphView`, a `primal` stage, and an
optional `comm_decide` stage.

Bit-exactness contract: `run_step` computes the exact expressions the
hand-written steps computed, in the same order — `chain.ensure_state` is
value-pure (state restructuring, no RNG, no float math), so its position
relative to the primal is free; everything that touches floats or the
PRNG is ordered identically. All existing parity pins (legacy `admm.run`,
cross-backend, degenerate gossip, personalization warmup prefix) ride on
this.

Carry contract: the state is any NamedTuple with the six COKEState /
OnlineState fields `(theta, theta_hat, gamma, step, comms, comm)`,
agent-stacked on the leading axis; `run_step` rebuilds the same type.
Stages communicate only through explicit values (the GraphView and the
(theta0, theta_hat0, gamma0) snapshot) — no hidden module state, which is
what lets `sweep()` vmap whole programs and the backends swap stages
without re-deriving the skeleton.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import comm as comm_mod

#: fold-in tag separating the participation stream from the comm stages'
#: per-round streams (Chain.apply folds the stage *index*; this sentinel
#: can never collide with one)
PARTICIPATION_TAG = np.uint32(0x9E3779B1)


def participation_mask(key: jax.Array, k, num_agents: int,
                       plan, alive: jax.Array | None = None) -> jax.Array:
    """(N,) bool — who computes and broadcasts this round.

    key is the chain-level `CommState.key`: folding (iteration k,
    PARTICIPATION_TAG, the rate's f32 bit pattern) gives a stream that is
    (a) independent of the comm stages' draws, (b) per-cell under sweep's
    vmap (the chain key already folds every policy parameter), and (c)
    identical on every backend carrying the same CommState. Straggler
    slowdowns scale the *threshold/score*, not the stream — common random
    numbers across slowdown scenarios: in Bernoulli mode the acceptance
    probability divides by the slowdown, in fixed-size (top-k) mode the
    draw is multiplied by it so slowed agents sink in the ranking while
    exactly `size` agents still fire each round. slowdown=None is
    bit-identical to the unscaled draw in both modes. rate = 1.0 is
    exactly the all-ones mask (uniform draws live in [0, 1)), the
    degeneracy contract."""
    r = jax.random.fold_in(key, jnp.asarray(k, jnp.uint32))
    r = jax.random.fold_in(r, PARTICIPATION_TAG)
    r = comm_mod._fold_value(r, plan.participation)
    u = jax.random.uniform(r, (num_agents,))
    if plan.size is not None:
        score = u if plan.slowdown is None else u * plan.slowdown
        if alive is not None:
            score = jnp.where(alive, score, jnp.inf)
        _, sel = jax.lax.top_k(-score, plan.size)
        m = jnp.zeros((num_agents,), bool).at[sel].set(True)
    else:
        p = jnp.asarray(plan.participation, jnp.float32)
        if plan.slowdown is not None:
            p = jnp.minimum(p / plan.slowdown, 1.0)
        m = u < p
    if alive is not None:
        m = m & alive
    return m


def _mask_rows(m: jax.Array, new, old):
    """Row-select over agent-stacked pytrees: agent i's leaves take `new`
    iff m[i]; scalar leaves pass through. With an all-true mask this is
    bitwise `new` — the degenerate-gossip contract."""
    def sel(a, b):
        if a.ndim == 0:
            return a
        return jnp.where(m.reshape(m.shape + (1,) * (a.ndim - 1)), a, b)
    return jax.tree.map(sel, new, old)


# ---------------------------------------------------------------------------
# The exchange stage's product: one iteration's view of the graph
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class GraphView:
    """What one iteration sees of the consensus graph: per-agent (N,)
    degrees and a neighbor-sum operator, plus (under churn) the liveness
    mask and the rows that (re)joined this iteration, and (under a
    topology schedule with the closed-form primal) the per-k Cholesky
    factor stack."""

    deg: jax.Array                              # (N,) weighted degrees
    nbr_sum: Callable[[jax.Array], jax.Array]   # x (N, ...) -> sum_n w x_n
    alive: jax.Array | None = None              # (N,) bool liveness
    joined: jax.Array | None = None             # (N,) bool cold (re)joiners
    chol: jax.Array | None = None               # (N, D, D) resolved factors


def dense_view(adjacency: jax.Array, deg: jax.Array | None = None,
               chol: jax.Array | None = None) -> GraphView:
    """Dense (possibly weighted / learned) graph: `A @ x` neighbor sums."""
    d = jnp.sum(adjacency, axis=1) if deg is None else deg
    return GraphView(deg=d, nbr_sum=lambda x: adjacency @ x, chol=chol)


def table_view(table, plan, k) -> GraphView:
    """Padded NeighborTable gathers under a gossip plan: alive-weighted
    degrees and sums, never materializing (N, N); `joined` marks the rows
    whose churn event fired at exactly iteration k."""
    alive = plan.alive_at(k)
    joined = None
    if plan.has_churn:
        joined = alive & ~plan.alive_at(k - 1)
    return GraphView(deg=table.degrees(alive),
                     nbr_sum=lambda x: table.nbr_sum(x, alive),
                     alive=alive, joined=joined)


def sampled_stage(plan) -> Callable:
    """The gossip comm_decide stage: CommState-keyed participation
    sampling (masked to the live rows under churn)."""
    def stage(key, k, g: GraphView):
        return participation_mask(key, k, g.deg.shape[0], plan, g.alive)
    return stage


def stream_primal(feats: jax.Array, labels: jax.Array, *, lam: float,
                  rho: float, lr: float, eta: float | None) -> Callable:
    """The streaming featurize+primal stage shared by online-DKLA/COKE
    (eta=None: one gradient step of size lr) and QC-ODKLA (eta=float: the
    linearized-ADMM closed form, implemented in the same subtractive form
    so the two modes share every other float op). Emits the pre-update
    instantaneous MSE — the online-protocol regret sample."""
    def stage(k, g: GraphView, theta0, theta_hat0, gamma0, nbr_hat):
        N = feats.shape[0]
        deg = g.deg
        preds = jnp.einsum("nbd,nd->nb", feats, theta0)
        inst_mse = jnp.mean((labels - preds) ** 2)
        resid = preds - labels
        g_data = (2.0 * jnp.einsum("nb,nbd->nd", resid, feats)
                  / feats.shape[1])
        grad = (g_data + (2.0 * lam / N) * theta0
                + 2.0 * rho * deg[:, None] * theta0
                + gamma0
                - rho * (deg[:, None] * theta_hat0 + nbr_hat))
        if eta is None:
            theta_new = theta0 - lr * grad
        else:
            theta_new = theta0 - grad / (eta + 2.0 * rho * deg[:, None])
        return theta_new, {"inst_mse": inst_mse}
    return stage


# ---------------------------------------------------------------------------
# The step program and its executor
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class StepProgram:
    """One per-iteration program: the comm chain, the dual stepsize, and
    the three substitutable stages. `exchange(state, k)` resolves the
    iteration's GraphView; `primal(k, g, theta0, theta_hat0, gamma0,
    nbr_hat)` returns (theta_new, extras); `comm_decide(key, k, g)` — if
    set — returns the (N,) participation mask (None = synchronous: every
    agent updates, `chain.apply` runs unmasked and the trace is identical
    to the pre-refactor synchronous steps). `primal_owns_exchange=True`
    declares that the primal stage fetches its own neighbor view of
    theta_hat (the fused megakernel reads the ring-rolled rows inside the
    pallas_call), so `run_step` skips the pre-primal `nbr_sum` and passes
    nbr_hat=None."""

    chain: Any
    rho: Any
    exchange: Callable[[Any, Any], GraphView]
    primal: Callable
    comm_decide: Callable | None = None
    primal_owns_exchange: bool = False


def run_step(program: StepProgram, state):
    """Execute one iteration of `program` on a (theta, theta_hat, gamma,
    step, comms, comm) carry; returns (new_state, extras) with extras the
    primal stage's auxiliary outputs (e.g. the streaming regret sample).

    Each stage runs under `jax.named_scope("coke.<stage>")` (exchange,
    primal, comm_decide, dual, record): compile-time metadata that a
    profiler trace carries in each device op's name stack, so a stage's
    device time can be read off the trace. It changes no computation."""
    chain = program.chain
    k = state.step + 1
    comm_state = chain.ensure_state(state.comm, state.theta.shape[0])
    theta0, theta_hat0, gamma0 = state.theta, state.theta_hat, state.gamma

    with jax.named_scope("coke.exchange"):
        g = program.exchange(state, k)
        if g.joined is not None:
            # a (re)joining agent restarts cold: zero primal/broadcast/dual
            theta0, theta_hat0, gamma0 = _mask_rows(
                g.joined, jax.tree.map(jnp.zeros_like, (theta0, theta_hat0,
                                                        gamma0)),
                (theta0, theta_hat0, gamma0))
        nbr_hat = (None if program.primal_owns_exchange
                   else g.nbr_sum(theta_hat0))

    with jax.named_scope("coke.primal"):
        theta_new, extras = program.primal(k, g, theta0, theta_hat0,
                                           gamma0, nbr_hat)

    with jax.named_scope("coke.comm_decide"):
        if program.comm_decide is not None:
            # gossip: sleepers hold their primal iterate, are structurally
            # silent in the broadcast (zero bits), and their duals freeze
            # (delayed-but-correct — the next wake integrates (21b)
            # against the then-current broadcast values)
            m = program.comm_decide(comm_state.key, k, g)
            theta = _mask_rows(m, theta_new, theta0)
        else:
            m = None
            theta = theta_new
        theta_hat, send, comm_state = chain.apply(theta, theta_hat0, k,
                                                  comm_state, active=m)

    with jax.named_scope("coke.dual"):
        # dual (21b): gamma_i += rho * sum_n (theta_hat_i - theta_hat_n)
        nbr_new = g.nbr_sum(theta_hat)
        gamma = gamma0 + program.rho * (g.deg[:, None] * theta_hat
                                        - nbr_new)
        if m is not None:
            gamma = _mask_rows(m, gamma, gamma0)

    with jax.named_scope("coke.record"):
        comms = state.comms + jnp.sum(send.astype(jnp.int32))

    new_state = type(state)(
        theta=theta, theta_hat=theta_hat, gamma=gamma, step=k,
        comms=comms, comm=comm_state)
    return new_state, extras
