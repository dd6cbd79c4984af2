"""Conformance battery for the fused ADMM megakernel (`coke_megastep`):
bit-parity against the blockwise reference across shapes, the pad-tail/
xi_sq contract pins, fused-vs-simulator fit parity under identity and
Censor+Quantize chains, the degenerate-gossip pin on the fused path, a
jaxpr inspection pinning exactly ONE `pallas_call` per fused iteration,
the top-k participation slowdown regression, and the interpret-mode
resolver contract (`repro.kernels.runtime.resolve_interpret`)."""
import dataclasses

import jax
import jax.extend.core as jex_core
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import (assert_fit_parity, assert_gossip_degenerate,
                      assert_results_match)

from repro.api import (Censor, Chain, FitConfig, KRRConfig, Quantize,
                       build_problem, fit, get_solver)
from repro.api import backends
from repro.api.config import SolveContext
from repro.core.gossip import GossipPlan
from repro.core.step import participation_mask
from repro.kernels import runtime
from repro.kernels.coke_update.coke_update import (coke_fused_update,
                                                  coke_megastep,
                                                  megastep_launch_params)
from repro.kernels.coke_update.ops import coke_update_pytree
from repro.kernels.coke_update.ref import coke_megastep_ref

KRR = KRRConfig(num_agents=4, samples_per_agent=40, num_features=32,
                lam=1e-2, rho=0.1, seed=0)
BASE = FitConfig(krr=KRR, graph="ring", algorithm="coke", censor_v=0.3,
                 censor_mu=0.97, num_iters=40, primal="gradient",
                 inner_steps=1, inner_lr=0.05)


def _operands(n, t, d, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    theta = jax.random.normal(ks[0], (n, d), jnp.float32)
    hat = jax.random.normal(ks[1], (n, d), jnp.float32)
    gamma = 0.1 * jax.random.normal(ks[2], (n, d), jnp.float32)
    phi = jax.random.normal(ks[3], (n, t, d), jnp.float32)
    y = jax.random.normal(ks[4], (n, t), jnp.float32)
    return theta, hat, gamma, phi, y


# ---------------------------------------------------------------------------
# megakernel vs blockwise bit reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,t,d,offsets,bt", [
    (4, 40, 32, (1,), None),      # the fit-level shape
    (2, 33, 513, (1,), 8),        # T and D both off-tile
    (8, 64, 100, (1, 2), None),   # non-multiple-of-128 D, circulant deg 4
    (3, 17, 128, (1,), 8),        # exact lane tile, ragged T
    (5, 128, 256, (2,), 32),      # non-unit ring offset
], ids=["fit", "ragged", "circulant", "lane", "offset2"])
def test_megastep_bitwise_vs_reference(n, t, d, offsets, bt):
    """The pallas megakernel and `ref.coke_megastep_ref` (same block walk,
    jitted so XLA rounds its dots identically) agree BITWISE."""
    theta, hat, gamma, phi, y = _operands(n, t, d)
    out_k, xi_k = coke_megastep(theta, hat, gamma, phi, y, rho=0.3,
                                lam=1e-2, lr=0.05, offsets=offsets,
                                block_t=bt, interpret=True)
    out_r, xi_r = coke_megastep_ref(theta, hat, gamma, phi, y, rho=0.3,
                                    lam=1e-2, lr=0.05, offsets=offsets,
                                    block_t=bt)
    np.testing.assert_array_equal(np.asarray(out_k), np.asarray(out_r))
    np.testing.assert_array_equal(np.asarray(xi_k), np.asarray(xi_r))


@pytest.mark.parametrize("n,t,d,block_t,padded_t", [
    (48, 2048, 16384, 32, 2048),  # the benchmark cell: 56 fits, 32 divides
    (8, 1000, 4096, 200, 1000),   # 248 fits, 200 divides
    (8, 1001, 4096, 248, 1240),   # no multiple of 8 divides T: pad
    (4, 40, 32, 40, 40),          # the whole of T in one block
], ids=["d16k", "divisor", "no-divisor", "one-block"])
def test_megastep_launch_params_roofline(n, t, d, block_t, padded_t):
    """Block sizing respects the VMEM budget, takes the largest
    multiple-of-8 divisor of T under it so that phi needs no pad, pads
    only a T that has none, and the launch carries its own roofline
    verdict (derived from launch.analysis)."""
    lp = megastep_launch_params(n, t, d, 2)
    assert (lp.block_t, lp.padded_t) == (block_t, padded_t)
    assert lp.block_t % 8 == 0 and lp.padded_d % 128 == 0
    assert lp.padded_t % lp.block_t == 0 and lp.padded_t >= t
    streamed = 2 * (lp.block_t * lp.padded_d * 4 + lp.block_t * 4)
    resident = (5 + 2) * lp.padded_d * 4
    assert streamed + resident <= 8 * 1024 * 1024
    assert lp.roofline["dominant"] in ("compute", "memory")
    assert lp.roofline["step_s_lower_bound"] > 0


# ---------------------------------------------------------------------------
# pad-tail / xi_sq contract (satellite: docstring reconciliation pins)
# ---------------------------------------------------------------------------

def test_megastep_pad_tail_contributes_zero():
    """Non-multiple-of-128 D: the lane pad must contribute EXACTLY zero —
    explicitly zero-padding the operands to the tile boundary is bitwise
    the same call, the padded columns of theta_new are exactly 0.0, and
    xi_sq equals the dense ||theta_new - theta_hat||^2."""
    n, t, d, dp = 3, 24, 200, 256
    theta, hat, gamma, phi, y = _operands(n, t, d, seed=1)
    kw = dict(rho=0.3, lam=1e-2, lr=0.05, offsets=(1,), block_t=8,
              interpret=True)
    out, xi = coke_megastep(theta, hat, gamma, phi, y, **kw)

    padr = lambda a: jnp.pad(a, ((0, 0), (0, dp - d)))
    out_p, xi_p = coke_megastep(padr(theta), padr(hat), padr(gamma),
                                jnp.pad(phi, ((0, 0), (0, 0), (0, dp - d))),
                                y, **kw)
    np.testing.assert_array_equal(np.asarray(out_p[:, :d]), np.asarray(out))
    np.testing.assert_array_equal(np.asarray(out_p[:, d:]),
                                  np.zeros((n, dp - d), np.float32))
    np.testing.assert_array_equal(np.asarray(xi_p), np.asarray(xi))
    dense = jnp.sum((out - hat) ** 2, axis=1)
    np.testing.assert_allclose(np.asarray(xi), np.asarray(dense), rtol=1e-6)


def test_fused_update_pad_tail_contributes_zero():
    """Same pin for the consensus-combine kernel at D=513 (one element
    past the 512 block): xi_sq is the squared censor norm over the REAL
    entries only."""
    n, d = 4, 513
    ks = jax.random.split(jax.random.PRNGKey(2), 6)
    ops6 = [jax.random.normal(k, (n, d), jnp.float32) for k in ks]
    gaug, xi = coke_fused_update(*ops6, rho=0.5, deg=2.0, interpret=True)

    padded = [jnp.pad(a, ((0, 0), (0, 1024 - d))) for a in ops6]
    gaug_p, xi_p = coke_fused_update(*padded, rho=0.5, deg=2.0,
                                     interpret=True)
    np.testing.assert_array_equal(np.asarray(gaug_p[:, :d]),
                                  np.asarray(gaug))
    np.testing.assert_array_equal(np.asarray(xi_p), np.asarray(xi))
    theta, hat = ops6[0], ops6[1]
    dense = jnp.sum((hat - theta) ** 2, axis=1)
    np.testing.assert_allclose(np.asarray(xi), np.asarray(dense), rtol=1e-6)


def test_pytree_wrapper_returns_sqrt_of_kernel_xi_sq():
    """The two-level xi contract: kernels emit xi_sq (partial-sum
    friendly), `coke_update_pytree` emits xi_norm = sqrt(xi_sq) — the
    quantity the censor policy thresholds."""
    n = 5
    ks = jax.random.split(jax.random.PRNGKey(4), 12)
    mk = lambda i: {"a": jax.random.normal(ks[2 * i], (n, 3), jnp.float32),
                    "b": jax.random.normal(ks[2 * i + 1], (n, 5),
                                           jnp.float32)}
    trees = [mk(i) for i in range(6)]
    _, xi_norm = coke_update_pytree(*trees, rho=0.5, interpret=True)
    flat = [jnp.concatenate([t["a"], t["b"]], axis=1) for t in trees]
    _, xi_sq = coke_fused_update(*flat, rho=0.5, interpret=True)
    np.testing.assert_array_equal(np.asarray(xi_norm),
                                  np.asarray(jnp.sqrt(xi_sq)))


# ---------------------------------------------------------------------------
# fit-level conformance (megakernel substituted into the StepProgram)
# ---------------------------------------------------------------------------

CENSOR_QUANT = Chain([Censor(0.3, 0.97), Quantize(bits=5, seed=7)])


@pytest.mark.parametrize("alg", ["dkla", "coke"])
@pytest.mark.parametrize("chain", [Chain(()), CENSOR_QUANT],
                         ids=["identity", "censor+quantize"])
def test_fused_megakernel_matches_simulator(alg, chain):
    """fused (megakernel) vs simulator: identical comm decisions and bit
    accounting, theta to 1e-5 — for DKLA and COKE, under the identity
    chain and a Censor+Quantize policy."""
    cfg = BASE.replace(algorithm=alg, comm=chain, censor_v=None,
                       censor_mu=None)
    assert_fit_parity(cfg, ("simulator", "fused"), exact=("comms", "bits"),
                      theta_atol=1e-5)


def test_fused_gossip_degenerate():
    """participation=1.0 gossip on the fused megakernel path is bitwise
    the synchronous run (the all-true mask selects every row)."""
    assert_gossip_degenerate(BASE, ("fused",))


MEGA_CONFIGS = {
    "coke-censor": BASE,
    "dkla": BASE.replace(algorithm="dkla"),
    "gossip": BASE.replace(exec="gossip", participation=0.6),
    "circulant2": BASE.replace(
        krr=dataclasses.replace(KRR, num_agents=6), graph="circulant",
        graph_offsets=(1, 2)),
}


@pytest.mark.parametrize("name", sorted(MEGA_CONFIGS), ids=str)
def test_megakernel_bitwise_vs_unfused_stepprogram(name, monkeypatch):
    """The acceptance pin: the fused megakernel iteration is BIT-IDENTICAL
    to the unfused StepProgram path (same stage assembly, blockwise
    reference instead of the pallas_call) over a whole fit — every history
    key and the final theta, exact."""
    cfg = MEGA_CONFIGS[name].replace(backend="fused")
    res_kernel = fit(cfg)
    monkeypatch.setattr(backends, "_MEGASTEP_USE_KERNEL", False)
    res_unfused = fit(cfg)
    assert_results_match(res_kernel, res_unfused, exact="*",
                         err=f"megakernel vs unfused ({name})")


def _count_pallas_calls(jaxpr) -> int:
    def subs(v):
        if isinstance(v, jex_core.ClosedJaxpr):
            return [v.jaxpr]
        if isinstance(v, jex_core.Jaxpr):
            return [v]
        if isinstance(v, (tuple, list)):
            return [j for x in v for j in subs(x)]
        return []
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            n += 1
        for v in eqn.params.values():
            n += sum(_count_pallas_calls(j) for j in subs(v))
    return n


def _fused_iteration_jaxpr():
    cfg = BASE.replace(backend="fused")
    problem = build_problem(cfg).problem
    ctx = SolveContext.from_config(cfg, num_agents=problem.num_agents)
    carry0, chunk_fn, _ = backends.consensus_runner(
        cfg, get_solver(cfg.algorithm), problem, ctx, None)
    return jax.make_jaxpr(lambda c: chunk_fn(c, 1))(carry0).jaxpr


def test_fused_iteration_has_exactly_one_pallas_call(monkeypatch):
    """The megakernel really is a MEGAkernel: one fused iteration lowers
    to exactly ONE pallas_call (RFF application + primal + ring combine +
    censor partial sums), and zero with the kernel substitution off."""
    assert _count_pallas_calls(_fused_iteration_jaxpr()) == 1
    monkeypatch.setattr(backends, "_MEGASTEP_USE_KERNEL", False)
    assert _count_pallas_calls(_fused_iteration_jaxpr()) == 0


# ---------------------------------------------------------------------------
# participation_mask: top-k slowdown regression (satellite fix)
# ---------------------------------------------------------------------------

def _masks(plan, rounds=200, n=8):
    key = jax.random.PRNGKey(3)
    return np.asarray([participation_mask(key, k, n, plan)
                       for k in range(1, rounds + 1)])


def test_topk_slowdown_threads_into_ranking():
    """Regression: fixed-size (top-k) sampling used to IGNORE straggler
    slowdowns — a 1e6x-slowed agent fired at the base 3/8 rate. Slowdown
    now scales the ranking score, so the straggler sinks while exactly
    `size` agents still fire each round."""
    slow = jnp.ones(8).at[0].set(1e6)
    m = _masks(GossipPlan(participation=jnp.float32(1.0), size=3,
                          slowdown=slow))
    assert (m.sum(axis=1) == 3).all()
    assert m[:, 0].sum() == 0
    others = m[:, 1:].sum(axis=0)
    assert (others > 0).all()          # the load redistributes


def test_topk_slowdown_none_bitwise_matches_unit():
    """slowdown=None is bit-identical to an all-ones slowdown (the score
    is the raw uniform draw either way) — common-random-numbers pin."""
    none = _masks(GossipPlan(participation=jnp.float32(1.0), size=3,
                             slowdown=None), rounds=60)
    unit = _masks(GossipPlan(participation=jnp.float32(1.0), size=3,
                             slowdown=jnp.ones(8)), rounds=60)
    np.testing.assert_array_equal(none, unit)


def test_topk_slowdown_respects_liveness():
    """Dead rows score +inf: never selected even against huge slowdowns,
    and the mask still fires exactly `size` live agents."""
    slow = jnp.full((8,), 1e6).at[0].set(1.0)
    alive = jnp.ones(8, bool).at[0].set(False)
    key = jax.random.PRNGKey(5)
    plan = GossipPlan(participation=jnp.float32(1.0), size=3, slowdown=slow)
    m = np.asarray([participation_mask(key, k, 8, plan, alive)
                    for k in range(1, 40)])
    assert (~m[:, 0]).all()
    assert (m.sum(axis=1) == 3).all()


# ---------------------------------------------------------------------------
# interpret-mode resolution
# ---------------------------------------------------------------------------

def test_resolve_interpret_defaults_to_backend(monkeypatch):
    monkeypatch.delenv(runtime._ENV_VAR, raising=False)
    assert runtime.resolve_interpret(None) is (
        jax.default_backend() == "cpu")
    assert runtime.resolve_interpret(None) is True  # suite runs on CPU


@pytest.mark.parametrize("raw,expect", [
    ("1", True), ("true", True), ("ON", True), (" yes ", True),
    ("0", False), ("false", False), ("Off", False), ("no", False),
])
def test_resolve_interpret_env_override(monkeypatch, raw, expect):
    monkeypatch.setenv(runtime._ENV_VAR, raw)
    assert runtime.resolve_interpret(None) is expect


def test_resolve_interpret_rejects_garbage_env(monkeypatch):
    monkeypatch.setenv(runtime._ENV_VAR, "maybe")
    with pytest.raises(ValueError, match="REPRO_PALLAS_INTERPRET"):
        runtime.resolve_interpret(None)


def test_resolve_interpret_explicit_argument_wins(monkeypatch):
    monkeypatch.setenv(runtime._ENV_VAR, "1")
    assert runtime.resolve_interpret(False) is False
    monkeypatch.setenv(runtime._ENV_VAR, "0")
    assert runtime.resolve_interpret(True) is True
