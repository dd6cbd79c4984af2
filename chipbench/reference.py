"""Plain references for what the timed paths compute, in straightforward
`jax.numpy`, importing nothing of the program and taking nothing it made.

They run after the window, on the benchmark's own inputs. `dtype` and
`precision` select the arithmetic: float32 at `highest` is the reference;
bfloat16 is the control, the reference computed one precision below what
the configurations state (float32 at the TPU's default dot precision),
which the comparison has to refuse.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

REFERENCE = (jnp.float32, jax.lax.Precision.HIGHEST)
CONTROL = (jnp.bfloat16, jax.lax.Precision.DEFAULT)
AGENT_BLOCK = 8   # agents per block of the exact primal's Gram and factor
DEG = 2.0         # an agent's degree on the ring


def _ring_sum(x):
    return jnp.roll(x, 1, axis=0) + jnp.roll(x, -1, axis=0)


def _coke(phi, y, primal, deg, *, rho, v, mu, iters, dtype, precision):
    """The COKE recursion of Alg. 2 of arXiv:2001.10133 on a ring around a
    primal step `primal(theta, theta_hat, gamma) -> theta'`: per iteration
    k = 1..iters and agent i

        send_i = ||theta_hat_i - theta'_i|| >= v mu^k
        theta_hat_i = theta'_i where sent
        gamma_i += rho (deg theta_hat_i - theta_hat_{i+-1})

    `phi`, `y` and `deg` are already in `dtype`. Returns (theta (N, D) float32,
    train MSE per iteration, cumulative sends per iteration)."""
    N, T, D = phi.shape
    c = lambda a: jnp.asarray(a, dtype)

    def body(carry, k):
        theta, hat, gamma, comms = carry
        theta = primal(theta, hat, gamma)
        h = (c(v) * c(mu) ** k).astype(dtype)
        xi = hat - theta
        send = jnp.sqrt(jnp.sum(xi * xi, axis=-1)) >= h
        hat = jnp.where(send[:, None], theta, hat)
        gamma = gamma + c(rho) * (deg * hat - _ring_sum(hat))
        comms = comms + jnp.sum(send.astype(jnp.int32))
        pred = jnp.einsum("ntd,nd->nt", phi, theta, precision=precision)
        mse = jnp.mean(((y - pred).astype(jnp.float32)) ** 2)
        return (theta, hat, gamma, comms), (mse, comms)

    z = jnp.zeros((N, D), dtype)
    (theta, _, _, _), (mse, comms) = jax.lax.scan(
        body, (z, z, z, jnp.zeros((), jnp.int32)),
        jnp.arange(1, iters + 1, dtype=jnp.int32))
    return theta.astype(jnp.float32), mse, comms


@functools.partial(jax.jit, static_argnames=(
    "lam", "rho", "v", "mu", "lr", "iters", "dtype", "precision"))
def coke_gradient_fit(phi, y, *, lam, rho, v, mu, lr, iters, dtype,
                      precision):
    """COKE (`_coke`) with the one-step gradient primal: per agent i

        g      = (2/T) phi_i^T (phi_i theta_i - y_i)
        theta' = theta_i - lr (g + (2 lam/N) theta_i + 2 rho deg theta_i
                 + gamma_i - rho (deg theta_hat_i + theta_hat_{i+-1}))"""
    N, T, D = phi.shape
    phi = phi.astype(dtype)
    y = y.astype(dtype)
    c = lambda a: jnp.asarray(a, dtype)
    deg = c(DEG)

    def primal(theta, hat, gamma):
        r = jnp.einsum("ntd,nd->nt", phi, theta, precision=precision) - y
        g = c(2.0 / T) * jnp.einsum("nt,ntd->nd", r, phi,
                                    precision=precision)
        gaug = (g + c(2.0 * lam / N) * theta + c(2.0 * rho) * deg * theta
                + gamma - c(rho) * (deg * hat + _ring_sum(hat)))
        return theta - c(lr) * gaug

    return _coke(phi, y, primal, deg, rho=rho, v=v, mu=mu, iters=iters,
                 dtype=dtype, precision=precision)


@functools.partial(jax.jit, static_argnames=(
    "lam", "rho", "v", "mu", "iters", "dtype", "precision"))
def coke_exact_fit(phi, y, *, lam, rho, v, mu, iters, dtype, precision):
    """COKE (`_coke`) with the exact primal, the closed-form (21a) solve
    per agent i, with c_i = 2 lam/N + 2 rho deg:

        A_i   = (2/T) Phi_i^T Phi_i + c_i I
        rhs_i = (2/T) Phi_i^T y_i - gamma_i + rho (deg theta_hat_i
                + theta_hat_{i+-1})
        theta' = A_i^{-1} rhs_i
               = (1/c_i) [rhs_i - Phi_i^T (K_i + (c_i T/2) I)^{-1} Phi_i rhs_i]

    by Woodbury's identity, K_i = Phi_i Phi_i^T: a Cholesky factor of each
    T x T matrix, made once per fit in blocks of agents, and never a D x D
    matrix. Everything that reads phi runs in `dtype`; the factor and its
    triangular solves run in float32 on the `dtype` matrix, since neither
    LAPACK nor XLA factors bfloat16. Under jit on a phi sharded over D the
    contractions over D become the partitioner's partial sums."""
    N, T, D = phi.shape
    phi = phi.astype(dtype)
    y = y.astype(dtype)
    c = lambda a: jnp.asarray(a, dtype)
    deg = c(DEG)
    ci = 2.0 * lam / N + 2.0 * rho * DEG
    blk = math.gcd(N, AGENT_BLOCK)

    def factor(p):   # (blk, T, D) -> (blk, T, T)
        K = jnp.einsum("ntd,nsd->nts", p, p, precision=precision)
        K = K + c(ci * T / 2.0) * jnp.eye(T, dtype=dtype)
        return jnp.linalg.cholesky(K.astype(jnp.float32))

    chol = jax.lax.map(factor, phi.reshape(N // blk, blk, T, D)).reshape(
        N, T, T)
    solve = jax.vmap(lambda L, u: jax.scipy.linalg.cho_solve((L, True), u))
    b = c(2.0 / T) * jnp.einsum("nt,ntd->nd", y, phi, precision=precision)

    def primal(theta, hat, gamma):
        rhs = b - gamma + c(rho) * (deg * hat + _ring_sum(hat))
        u = jnp.einsum("ntd,nd->nt", phi, rhs, precision=precision)
        z = solve(chol, u.astype(jnp.float32)).astype(dtype)
        back = jnp.einsum("nt,ntd->nd", z, phi, precision=precision)
        return (rhs - back) / c(ci)

    return _coke(phi, y, primal, deg, rho=rho, v=v, mu=mu, iters=iters,
                 dtype=dtype, precision=precision)
