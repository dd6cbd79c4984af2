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

import jax
import jax.numpy as jnp

REFERENCE = (jnp.float32, jax.lax.Precision.HIGHEST)
CONTROL = (jnp.bfloat16, jax.lax.Precision.DEFAULT)


def _ring_sum(x):
    return jnp.roll(x, 1, axis=0) + jnp.roll(x, -1, axis=0)


@functools.partial(jax.jit, static_argnames=(
    "lam", "rho", "v", "mu", "lr", "iters", "dtype", "precision"))
def coke_gradient_fit(phi, y, *, lam, rho, v, mu, lr, iters, dtype,
                      precision):
    """COKE (Alg. 2 of arXiv:2001.10133) on a ring with the one-step
    gradient primal: per iteration k = 1..iters and agent i (deg 2)

        g      = (2/T) phi_i^T (phi_i theta_i - y_i)
        theta' = theta_i - lr (g + (2 lam/N) theta_i + 2 rho deg theta_i
                 + gamma_i - rho (deg theta_hat_i + theta_hat_{i+-1}))
        send_i = ||theta_hat_i - theta'_i|| >= v mu^k
        theta_hat_i = theta'_i where sent
        gamma_i += rho (deg theta_hat_i - theta_hat_{i+-1})

    Returns (theta (N, D), train MSE per iteration, cumulative sends per
    iteration)."""
    N, T, D = phi.shape
    phi = phi.astype(dtype)
    y = y.astype(dtype)
    c = lambda a: jnp.asarray(a, dtype)
    deg = c(2.0)

    def body(carry, k):
        theta, hat, gamma, comms = carry
        r = jnp.einsum("ntd,nd->nt", phi, theta, precision=precision) - y
        g = c(2.0 / T) * jnp.einsum("nt,ntd->nd", r, phi,
                                    precision=precision)
        gaug = (g + c(2.0 * lam / N) * theta + c(2.0 * rho) * deg * theta
                + gamma - c(rho) * (deg * hat + _ring_sum(hat)))
        theta = theta - c(lr) * gaug
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
