"""The work each measured operation requires, counted from its unpadded
shapes. Roofline shares and `mfu` metrics divide the time these need at
the chip's peaks by the time measured, so they count only what the
mathematics needs: padding, recomputation and second reads of an array do
not count. All sizes are float32 (4 bytes).
"""
from __future__ import annotations

F32 = 4


def admm_iteration(n_agents: int, n_samples: int, dim: int,
                   n_offsets: int = 1) -> tuple[float, float]:
    """One COKE iteration over all agents: (flops, bytes).

    Flops: phi theta and phi^T r, 2 T D each per agent. Bytes: phi read
    once, the labels, and the per-agent rows the update touches (theta,
    theta_hat, gamma, the 2k neighbour rows read; theta written)."""
    N, T, D = n_agents, n_samples, dim
    flops = 4.0 * N * T * D
    rows = 3 + 2 * n_offsets + 1
    nbytes = F32 * (N * T * D + N * T + rows * N * D)
    return flops, nbytes


def megastep_call(n_agents: int, n_samples: int, dim: int,
                  n_offsets: int = 1) -> tuple[float, float]:
    """One `coke_megastep` kernel call: the same work as one iteration."""
    return admm_iteration(n_agents, n_samples, dim, n_offsets)
