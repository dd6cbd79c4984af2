"""Pallas TPU kernel: fused random-Fourier featurizer.

phi = sqrt(2/L) * cos(X @ Omega + b)

One VMEM pass fuses the MXU matmul with the VPU cosine + scale — the
XLA-naive version round-trips the (T, L) projection through HBM between the
matmul and the transcendental. Every agent featurizes every sample in every
experiment, so this is the paper workload's compute hot spot.

Tiling: grid (T/bt, L/bl); X tile (bt, d) with d kept whole (assigned
datasets have d <= 96; the wrapper pads d to a lane multiple), Omega tile
(d, bl), bias tile (1, bl), out tile (bt, bl). bt/bl default to MXU-aligned
128.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.runtime import resolve_interpret


def _rff_kernel(x_ref, omega_ref, bias_ref, out_ref, *, scale: float):
    proj = jnp.dot(x_ref[...], omega_ref[...],
                   preferred_element_type=jnp.float32)
    out_ref[...] = (scale * jnp.cos(proj + bias_ref[...])
                    ).astype(out_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("block_t", "block_l", "interpret"))
def _rff_pallas(x: jax.Array, omega: jax.Array, bias: jax.Array,
                block_t: int, block_l: int, interpret: bool) -> jax.Array:
    T, d = x.shape
    L = omega.shape[1]
    scale = float((2.0 / L) ** 0.5)

    bt = min(block_t, T)
    bl = min(block_l, L)
    pad_t, pad_l = (-T) % bt, (-L) % bl
    pad_d = (-d) % 8  # sublane alignment for the contracted dim
    xp = jnp.pad(x, ((0, pad_t), (0, pad_d)))
    op = jnp.pad(omega, ((0, pad_d), (0, pad_l)))
    # the bias travels as a (1, L) row: a 1-D (bl,) block gets a layout
    # XLA and Mosaic disagree on, a (1, bl) block spans the full row dim
    bp = jnp.pad(bias, (0, pad_l))[None, :]
    Tp, dp = xp.shape
    Lp = op.shape[1]

    out = pl.pallas_call(
        functools.partial(_rff_kernel, scale=scale),
        grid=(Tp // bt, Lp // bl),
        in_specs=[
            pl.BlockSpec((bt, dp), lambda i, j: (i, 0)),
            pl.BlockSpec((dp, bl), lambda i, j: (0, j)),
            pl.BlockSpec((1, bl), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bt, bl), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Tp, Lp), x.dtype),
        interpret=interpret,
    )(xp, op, bp)
    return out[:T, :L]


def rff_pallas(x: jax.Array, omega: jax.Array, bias: jax.Array,
               block_t: int = 128, block_l: int = 128,
               interpret: bool | None = None) -> jax.Array:
    """x: (T, d); omega: (d, L); bias: (L,) -> (T, L) features.

    Matches repro.core.rff.featurize with mapping='cos_bias' (incl. the
    1/sqrt(L) normalization). interpret=None resolves via
    repro.kernels.runtime.resolve_interpret (compiled off-CPU)."""
    return _rff_pallas(x, omega, bias, block_t, block_l,
                       resolve_interpret(interpret))
