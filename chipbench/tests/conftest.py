"""Tiny versions of the benchmark's cells for CPU tests: the cells'
configurations and traffic at a few hundred features, so a whole run
(set-up, window, reference, comparison) takes seconds."""
import dataclasses
import os
import sys

from chipbench import spec

sys.path.insert(0, os.path.join(spec.ROOT, "src"))

TINY = {"num_agents": 4, "samples_per_agent": 64, "num_features": 256}


def tiny_cell(name: str):
    """A cell of BENCHMARK.json at a tiny size: 20-iteration fits."""
    cell = spec.resolve(name)
    return dataclasses.replace(cell, config=dict(cell.config, **TINY),
                               traffic=dict(cell.traffic, num_iters=20))


def tiny_sharded_cell():
    """d16k.fit's tiny cell as a feature-sharded fit over four devices
    with the exact primal (CG) on the spmd backend."""
    cell = tiny_cell("d16k.fit")
    config = dict(cell.config, mesh={"data": 1, "model": 4},
                  fit=dict(cell.config["fit"], backend="spmd", primal="cg"))
    return dataclasses.replace(cell, chips=4, config=config)


class Args:
    def __init__(self, seed=2**31 + 17, seconds=0.5, trace=0):
        self.seed, self.seconds, self.trace = seed, seconds, trace


def drive(cell, **args):
    """A run without the harness's look for a chip, on the CPU."""
    import time

    import jax

    from chipbench import harness
    from chipbench.peaks import peaks_for
    devices = jax.devices()[:cell.chips]
    a = Args(**args)
    run = harness.drive(cell, a, time.monotonic(), devices,
                        on="cpu (test)", peaks=peaks_for("TPU v5 lite"))
    return run, harness.result_line(run, devices, bool(a.trace))
