"""Pallas TPU kernels for the COKE Alg.-2 inner loop.

Two entry points, both bit-pinned against `ref.py`:

`coke_fused_update` — the original fused *consensus combine*: given a
precomputed data gradient, one VMEM pass emits

    g_aug  = g + 2 rho deg theta + gamma - rho (deg theta_hat + left + right)
    xi_sq  = per-block partial sums of (theta_hat - theta)^2

`coke_megastep` — the full-iteration megakernel: one `pallas_call` per
ADMM iteration that fuses the RFF-feature application (phi theta), the
linearized/gradient primal step, the ring neighbor combine, and the
censor-norm partial sums. Per agent, theta / theta_hat / gamma and the
ring-rolled neighbor views stay VMEM-resident across the whole inner
loop over sample blocks (their BlockSpec index is constant in the
sample-grid axis, so Pallas revisits the same block); only the (bt, D)
feature tiles stream from HBM. The output buffer is donated onto theta
via `input_output_aliases`, and block shapes are derived from
`launch/analysis.py`'s `roofline()` helper (see
`megastep_launch_params`).

Grid: (N_agents, T_pad / block_t), sample axis innermost. block_t is
chosen among the multiples of 8 that divide T, so T_pad == T whenever
T % 8 == 0 and phi enters the `pallas_call` as the caller holds it; only
a T with no such divisor (or a D off the 128-lane tile) makes the
wrapper pad phi. The gradient accumulator lives in VMEM scratch; the
final sample step applies the consensus terms and writes theta_new plus
the censor partial sum xi_sq = ||theta_new - theta_hat||^2 (zero
padding of both T and D contributes exactly zero — pinned in tests).

`interpret` defaults to None = resolve via
`repro.kernels.runtime.resolve_interpret` (interpret on CPU, compiled
on TPU/GPU, `$REPRO_PALLAS_INTERPRET` overrides); resolution happens at
trace time.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.runtime import resolve_interpret
from repro.launch import analysis

# ---------------------------------------------------------------------------
# original fused consensus combine (g_aug + censor partial sums)
# ---------------------------------------------------------------------------


def _coke_kernel(theta_ref, hat_ref, gamma_ref, grad_ref, left_ref,
                 right_ref, gaug_ref, xisq_ref, *, rho: float, deg: float):
    th = theta_ref[...].astype(jnp.float32)
    hat = hat_ref[...].astype(jnp.float32)
    g = grad_ref[...].astype(jnp.float32)
    gm = gamma_ref[...].astype(jnp.float32)
    l = left_ref[...].astype(jnp.float32)
    r = right_ref[...].astype(jnp.float32)
    gaug = g + 2.0 * rho * deg * th + gm - rho * (deg * hat + l + r)
    gaug_ref[...] = gaug.astype(gaug_ref.dtype)
    diff = hat - th
    xisq_ref[...] = jnp.sum(diff * diff, keepdims=True)


@functools.partial(jax.jit, static_argnames=("rho", "deg", "block_d",
                                             "interpret"))
def _coke_fused_update(theta, theta_hat, gamma, grad, left, right, *,
                       rho: float, deg: float, block_d: int,
                       interpret: bool):
    N, D = theta.shape
    bd = min(block_d, D)
    pad = (-D) % bd
    Dp = D + pad
    nblocks = Dp // bd
    # rows travel as (N, 1, Dp) with the agent dim squeezed out of the
    # block: a (1, bd) block then spans the whole second-minor dim, which
    # Mosaic's (8, 128)-or-full-dim tiling rule admits (a (1, bd) block
    # of an (N, Dp) array does not)
    rows = lambda a: jnp.pad(a, ((0, 0), (0, pad)))[:, None, :]
    row_spec = pl.BlockSpec((None, 1, bd), lambda i, j: (i, 0, j))

    gaug, xisq = pl.pallas_call(
        functools.partial(_coke_kernel, rho=rho, deg=deg),
        name="coke_fused_update",
        grid=(N, nblocks),
        in_specs=[row_spec] * 6,
        out_specs=[
            row_spec,
            pl.BlockSpec((None, None, 1, 1), lambda i, j: (i, j, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, 1, Dp), jnp.float32),
            jax.ShapeDtypeStruct((N, nblocks, 1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(*map(rows, (theta, theta_hat, gamma, grad, left, right)))
    return gaug[:, 0, :D], jnp.sum(xisq[:, :, 0, 0], axis=1)


def coke_fused_update(theta: jax.Array, theta_hat: jax.Array,
                      gamma: jax.Array, grad: jax.Array, left: jax.Array,
                      right: jax.Array, *, rho: float, deg: float = 2.0,
                      block_d: int = 512, interpret: bool | None = None):
    """All operands (N, D). Returns (g_aug (N, D) fp32, xi_sq (N,) fp32).

    xi_sq is the *squared* censor norm ||theta_hat - theta||^2 per agent
    (partial-sum friendly); `ops.coke_update_pytree` takes the sqrt.
    """
    return _coke_fused_update(theta, theta_hat, gamma, grad, left, right,
                              rho=rho, deg=deg, block_d=block_d,
                              interpret=resolve_interpret(interpret))


# ---------------------------------------------------------------------------
# full-iteration megakernel
# ---------------------------------------------------------------------------

# VMEM working-set budget for block sizing: ~half of a 16 MiB core so the
# pipeline can double-buffer the streamed feature tiles.
MEGASTEP_VMEM_BUDGET = 8 * 1024 * 1024


@dataclasses.dataclass(frozen=True)
class MegastepLaunch:
    """Block shapes + roofline estimate for one `coke_megastep` call."""
    block_t: int
    padded_t: int
    padded_d: int
    cost: dict        # {"flops", "bytes accessed"} per call
    roofline: dict    # launch.analysis.roofline() terms


def megastep_launch_params(n_agents: int, n_samples: int, dim: int,
                           n_nbr: int, block_t: int | None = None,
                           vmem_budget: int = MEGASTEP_VMEM_BUDGET
                           ) -> MegastepLaunch:
    """Derive the sample-block size and padded shapes for the megakernel.

    The feature dim is padded to the 128-lane tile. The sample block is
    capped at the largest sublane multiple (of 8, at most 512 and at
    most T rounded up to 8) whose streamed tiles — double-buffered — fit
    in `vmem_budget` alongside the VMEM-resident per-agent rows (theta,
    theta_hat, gamma, the 2k rolled neighbor views, the donated output,
    and the gradient scratch). Within that cap it is the largest
    multiple of 8 that divides T, so the grid tiles T exactly and the
    wrapper copies no phi: a smaller block costs a few grid steps, a
    padded T a full read and write of phi on every call. Only a T with
    no multiple-of-8 divisor (T % 8 != 0) takes the cap itself and is
    padded up to a multiple of it. An explicit `block_t` wins. The
    resulting cost dict feeds both `pl.CostEstimate` and
    `launch.analysis.roofline` so the launch carries its own
    compute-vs-memory bound.
    """
    Dp = max(128, ((dim + 127) // 128) * 128)
    resident = (5 + n_nbr) * Dp * 4  # theta/hat/gamma/nbrs/out rows + scratch
    if block_t is None:
        bt = 8
        for cand in range(512, 7, -8):
            if 2 * (cand * Dp * 4 + cand * 4) + resident <= vmem_budget:
                bt = cand
                break
        bt = min(bt, ((max(n_samples, 1) + 7) // 8) * 8)
        bt = next((c for c in range(bt, 7, -8) if n_samples % c == 0), bt)
    else:
        bt = block_t
    Tp = ((max(n_samples, 1) + bt - 1) // bt) * bt
    flops = float(n_agents) * (4.0 * Tp * Dp + 12.0 * Dp)
    bytes_accessed = 4.0 * n_agents * (
        Tp * Dp + Tp + (4 + n_nbr) * Dp + 1)
    cost = {"flops": flops, "bytes accessed": bytes_accessed}
    return MegastepLaunch(block_t=bt, padded_t=Tp, padded_d=Dp, cost=cost,
                          roofline=analysis.roofline(cost, {}))


def megastep_scalars(*, rho: float, lam: float, lr: float, n_agents: int,
                     n_samples: int, n_offsets: int):
    """Python-float scalar constants shared by kernel and bit reference."""
    deg = 2.0 * n_offsets
    return {
        "rho": float(rho),
        "deg": deg,
        "lam2": 2.0 * float(lam) / float(n_agents),
        "rho2deg": 2.0 * float(rho) * deg,
        "lr": float(lr),
        "inv_t2": 2.0 / float(n_samples),
    }


def _megastep_kernel(*refs, n_nbr: int, nt: int, rho: float, deg: float,
                     lam2: float, rho2deg: float, lr: float, inv_t2: float):
    (theta_ref, hat_ref, gamma_ref) = refs[:3]
    nbr_refs = refs[3:3 + n_nbr]
    phi_ref, y_ref, out_ref, xisq_ref, g_scr = refs[3 + n_nbr:]
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        g_scr[...] = jnp.zeros_like(g_scr)

    th = theta_ref[...].astype(jnp.float32)          # (1, Dp), VMEM-resident
    phi = phi_ref[...].astype(jnp.float32)           # (bt, Dp) streamed tile
    r = jnp.dot(phi, th.T, preferred_element_type=jnp.float32)    # (bt, 1)
    resid = r - y_ref[...].astype(jnp.float32)                    # (bt, 1)
    g_scr[...] += jnp.dot(resid.T, phi, preferred_element_type=jnp.float32)

    @pl.when(t == nt - 1)
    def _finalize():
        hat = hat_ref[...].astype(jnp.float32)
        gm = gamma_ref[...].astype(jnp.float32)
        acc = deg * hat
        for nbr in nbr_refs:
            acc = acc + nbr[...].astype(jnp.float32)
        g_data = inv_t2 * g_scr[...]
        gaug = g_data + lam2 * th + rho2deg * th + gm - rho * acc
        theta_new = th - lr * gaug
        out_ref[...] = theta_new
        d = theta_new - hat
        xisq_ref[...] = jnp.sum(d * d, keepdims=True)


@functools.partial(jax.jit, static_argnames=("rho", "lam", "lr", "offsets",
                                             "block_t", "interpret"))
def _coke_megastep(theta, theta_hat, gamma, phi, y, *, rho, lam, lr,
                   offsets, block_t, interpret):
    N, T, D = phi.shape
    n_nbr = 2 * len(offsets)
    lp = megastep_launch_params(N, T, D, n_nbr, block_t)
    bt, Tp, Dp = lp.block_t, lp.padded_t, lp.padded_d
    nt = Tp // bt
    sc = megastep_scalars(rho=rho, lam=lam, lr=lr, n_agents=N, n_samples=T,
                          n_offsets=len(offsets))

    # Every operand carries the agent dim as a squeezed leading block dim
    # (`None`), so each block's last two dims either span the whole array
    # dim or are (8, 128)-aligned — the tiling rule Mosaic enforces:
    # per-agent rows are (N, 1, Dp), labels (N, Tp, 1), xi_sq (N, 1, 1).
    # The layout copies run under the `coke.layout` scope, so a profiler
    # trace tells them from the pallas_call itself. phi is the large
    # operand: it is padded (a full copy) only where the block walk
    # cannot tile T and D exactly; the cast is free for float32 phi.
    pad_row = lambda a: jnp.pad(a.astype(jnp.float32),
                                ((0, 0), (0, Dp - D)))[:, None, :]
    with jax.named_scope("coke.layout"):
        theta, theta_hat, gamma = map(pad_row, (theta, theta_hat, gamma))
        phi = phi.astype(jnp.float32)
        if (Tp, Dp) != (T, D):
            phi = jnp.pad(phi, ((0, 0), (0, Tp - T), (0, Dp - D)))
        y = jnp.pad(y.astype(jnp.float32),
                    ((0, 0), (0, Tp - T)))[:, :, None]

    row_spec = pl.BlockSpec((None, 1, Dp), lambda i, t: (i, 0, 0))
    nbr_specs = []
    for o in offsets:
        nbr_specs.append(pl.BlockSpec(
            (None, 1, Dp), lambda i, t, o=o: ((i + o) % N, 0, 0)))
        nbr_specs.append(pl.BlockSpec(
            (None, 1, Dp), lambda i, t, o=o: ((i - o) % N, 0, 0)))

    theta_new, xisq = pl.pallas_call(
        functools.partial(_megastep_kernel, n_nbr=n_nbr, nt=nt, **sc),
        name="coke_megastep",
        grid=(N, nt),
        in_specs=[row_spec, row_spec, row_spec, *nbr_specs,
                  pl.BlockSpec((None, bt, Dp), lambda i, t: (i, t, 0)),
                  pl.BlockSpec((None, bt, 1), lambda i, t: (i, t, 0))],
        out_specs=[
            row_spec,
            pl.BlockSpec((None, 1, 1), lambda i, t: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, 1, Dp), jnp.float32),
            jax.ShapeDtypeStruct((N, 1, 1), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, Dp), jnp.float32)],
        input_output_aliases={0: 0},
        cost_estimate=pl.CostEstimate(
            flops=int(lp.cost["flops"]), transcendentals=0,
            bytes_accessed=int(lp.cost["bytes accessed"])),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(theta, theta_hat, gamma, *([theta_hat] * n_nbr), phi, y)
    with jax.named_scope("coke.layout"):
        return theta_new[:, 0, :D], xisq[:, 0, 0]


def coke_megastep(theta: jax.Array, theta_hat: jax.Array, gamma: jax.Array,
                  phi: jax.Array, y: jax.Array, *, rho: float, lam: float,
                  lr: float, offsets: tuple[int, ...] = (1,),
                  block_t: int | None = None,
                  interpret: bool | None = None):
    """One fused COKE/DKLA gradient-primal iteration for all agents.

    Args: theta/theta_hat/gamma (N, D); phi (N, T, D) RFF features;
    y (N, T) labels; `offsets` the static ring offsets (neighbors at
    +-o for each o). Computes, per agent i with deg = 2*len(offsets):

        g      = (2/T) phi^T (phi theta - y)          # local LS gradient
        g_aug  = g + (2 lam / N) theta + 2 rho deg theta + gamma
                 - rho (deg theta_hat + sum_o theta_hat[i+-o])
        theta' = theta - lr * g_aug

    Returns (theta_new (N, D) fp32, xi_sq (N,) fp32) where xi_sq is the
    *squared* censor norm ||theta_new - theta_hat||^2 — the innovation
    the censor policy thresholds. Bit-identical to
    `ref.coke_megastep_ref` (same block walk, same accumulation order).
    """
    return _coke_megastep(theta, theta_hat, gamma, phi, y, rho=float(rho),
                          lam=float(lam), lr=float(lr),
                          offsets=tuple(offsets), block_t=block_t,
                          interpret=resolve_interpret(interpret))
