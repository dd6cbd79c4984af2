"""Compile each Pallas kernel of the main path for a described TPU v5e.

Nothing runs: the TPU compiler installed with JAX compiles for a chip that
is described, not attached, and refuses what the chip's compiler would
refuse (block shapes off the (8, 128) tiling, layouts Mosaic cannot
take, too much VMEM). Interpret mode on CPU sees none of that. Each test
compiles one kernel at a size the chip smoke run uses and checks that
the compiled program holds the Mosaic kernel (`tpu_custom_call`).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and the test workers all
import this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.coke_update.coke_update import (coke_fused_update,
                                                  coke_megastep)
from repro.kernels.rff.rff import rff_pallas


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        # the compiler otherwise writes its logs under /tmp
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent
        # cache but cannot be read back without one: keep the cache off
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield topo
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def f32(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                               sharding=one_chip)


def _kernel_calls(compiled) -> int:
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


def test_megastep_compiles_for_v5e(f32):
    N, T, D = 16, 2048, 16384
    step = jax.jit(lambda th, hat, gm, phi, y: coke_megastep(
        th, hat, gm, phi, y, rho=1e-2, lam=5e-5, lr=0.1, interpret=False))
    compiled = step.lower(f32(N, D), f32(N, D), f32(N, D), f32(N, T, D),
                          f32(N, T)).compile()
    assert _kernel_calls(compiled) == 1


def test_fused_update_compiles_for_v5e(f32):
    N, D = 16, 16384
    update = jax.jit(lambda *ops: coke_fused_update(*ops, rho=1e-2,
                                                    interpret=False))
    compiled = update.lower(*[f32(N, D)] * 6).compile()
    assert _kernel_calls(compiled) == 1


def test_rff_compiles_for_v5e(f32):
    T, d, L = 4096, 90, 16384
    feat = jax.jit(lambda x, omega, bias: rff_pallas(x, omega, bias,
                                                    interpret=False))
    compiled = feat.lower(f32(T, d), f32(d, L), f32(L)).compile()
    assert _kernel_calls(compiled) == 1
