"""Readings that set the limits of `correct`, at a cell's own size.

    python3 chipbench/control.py --workload d16k.fit --seeds 1 2 3 \
        [--program]

For each seed it builds the cell's inputs and prints the compared numbers
of the control: the plain reference computed one precision below what the
configuration states (bfloat16), against the reference at float32
`highest`. With --program it also prints the program's numbers for one
fit through the timed entry point (`repro.api.fit` on the same compiled
program the window runs). The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from chipbench import harness, reference, spec  # noqa: E402


def fit_readings(cell, seed: int, devices, program: bool) -> dict:
    import numpy as np

    from chipbench import fit_cell
    config, iters = cell.config, int(cell.traffic["num_iters"])
    cfg, problem, (phi, labels), mesh = fit_cell.build(config, seed,
                                                       devices)
    out = {}
    if program:
        from repro.api import fit
        res = harness.ready(fit(cfg.replace(num_iters=iters),
                                problem=problem, mesh=mesh))
        ans = (np.asarray(res.theta), np.asarray(res.history["train_mse"]),
               np.asarray(res.history["comms"]))
        del res
    del problem
    ref = fit_cell.reference_fit(config, phi, labels, iters,
                                 reference.REFERENCE)
    if program:
        out["program"] = fit_cell.gaps(ans, ref)
    ctl = fit_cell.reference_fit(config, phi, labels, iters,
                                 reference.CONTROL)
    out["control"] = fit_cell.gaps(ctl, ref)
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--program", action="store_true")
    args = p.parse_args(argv)
    sys.path.insert(0, os.path.join(spec.ROOT, "src"))
    cell = spec.resolve(args.workload)
    harness.runner(cell)
    devices = harness.device_check(cell.chips)
    harness.enable_cache()
    on = f"{devices[0].device_kind} x{len(devices)}"
    for seed in args.seeds:
        t = time.perf_counter()
        out = fit_readings(cell, seed, devices, args.program)
        print(json.dumps({"workload": cell.name, "seed": seed, **out,
                          "seconds": time.perf_counter() - t, "on": on}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
