"""The benchmark's own inputs, made from `--seed` alone: the paper's
synthetic regression data, the random-feature draw and the training
features. The program receives
these through its public API and makes none of them, so no change to the
program can move what it is measured on.
"""
from __future__ import annotations

import functools

import numpy as np


def jax_seed(seed: int) -> int:
    """A 31-bit seed for `jax.random.PRNGKey`, drawn from any whole
    number: seeds past 2**31 are welcome on the command line."""
    return int(np.random.default_rng(seed).integers(0, 2**31 - 1))


def paper_synthetic(num_agents: int, samples: int, seed: int,
                    input_dim: int = 5, num_components: int = 50,
                    bandwidth: float = 5.0,
                    noise_std: float = float(np.sqrt(0.1))):
    """Section 5.1 of arXiv:2001.10133: x ~ N(0, I_5), y = sum_m b_m
    exp(-||c_m - x||^2 / (2 sigma^2)) + e with b_m ~ U[0, 1],
    c_m ~ N(0, I_5), e ~ N(0, 0.1), sigma = 5; inputs and labels then
    normalized to [0, 1] (Sec. 5). Only the training split is drawn:
    (N, T, d) inputs and (N, T) labels, float32."""
    rng = np.random.default_rng(seed)
    b = rng.uniform(0.0, 1.0, num_components)
    c = rng.normal(size=(num_components, input_dim))
    x = rng.normal(size=(num_agents, samples, input_dim))
    y = np.empty((num_agents, samples))
    for n in range(num_agents):   # one agent at a time bounds host memory
        sq = ((x[n, :, None, :] - c[None]) ** 2).sum(-1)
        y[n] = np.exp(-sq / (2.0 * bandwidth ** 2)) @ b
    y += rng.normal(scale=noise_std, size=(num_agents, samples))
    lo, hi = x.min(axis=(0, 1)), x.max(axis=(0, 1))
    x = (x - lo) / np.maximum(hi - lo, 1e-9)
    y = (y - y.min()) / max(y.max() - y.min(), 1e-9)
    return x.astype(np.float32), y.astype(np.float32)


@functools.lru_cache(maxsize=None)
def _rff_fn(input_dim: int, dim: int, bandwidth: float):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def draw(key):
        k_omega, k_bias = jax.random.split(key)
        omega = jax.random.normal(k_omega, (input_dim, dim),
                                  jnp.float32) / bandwidth
        bias = jax.random.uniform(k_bias, (dim,), jnp.float32, 0.0,
                                  2.0 * np.pi)
        return omega, bias
    return draw


def rff_draw(seed: int, input_dim: int, dim: int, bandwidth: float):
    """(omega (d, D), bias (D,)) of the Gaussian kernel's random Fourier
    features (Rahimi & Recht, Eq. (13) of the paper), on the device."""
    import jax
    key = jax.random.fold_in(jax.random.PRNGKey(jax_seed(seed)), 1)
    return _rff_fn(input_dim, dim, float(bandwidth))(key)


@functools.lru_cache(maxsize=None)
def _features_fn(sharding=None):
    import jax
    import jax.numpy as jnp

    def feats(x, omega, bias):
        dim = omega.shape[1]
        proj = jnp.einsum("ntd,dl->ntl", x, omega,
                          precision=jax.lax.Precision.HIGHEST)
        return (np.sqrt(2.0 / dim).astype(np.float32)
                * jnp.cos(proj + bias))
    if sharding is None:
        return jax.jit(feats)
    return jax.jit(feats, out_shardings=sharding)


def features(x, omega, bias, sharding=None):
    """phi(x) = sqrt(2/D) cos(x omega + b) for (N, T, d) inputs, at full
    float32, in one jitted call on the device. With a `sharding`, phi is
    made straight into it: each device computes its own slice and no
    device ever holds the whole."""
    return _features_fn(sharding)(x, omega, bias)
