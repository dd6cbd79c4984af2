"""Record the small TPU trace that `test_chipbench_stages.py` reads: two
3-iteration fused fits at a small shape, traced as the harness traces a
window (the `chipbench.window` span around `chipbench.fit` spans). Run it
on a TPU from the repository's root:

    python -m chipbench.tests.record_v5e_trace \
        chipbench/tests/data/v5e_fit.xplane.pb
"""
import os
import shutil
import sys

from chipbench import harness, spec, trace


def main(out: str) -> None:
    sys.path.insert(0, os.path.join(spec.ROOT, "src"))
    from repro.api import FitConfig, KRRConfig, build_problem, fit

    import jax
    harness.device_check(1)
    # source files by name alone in the ops' metadata, not by path, and
    # every program compiled here: one read from a persistent compile
    # cache keeps the metadata of the process that compiled it
    jax.config.update("jax_hlo_source_file_canonicalization_regex", ".*/")
    jax.config.update("jax_enable_compilation_cache", False)
    cfg = FitConfig(
        algorithm="coke", backend="fused", primal="gradient", graph="ring",
        num_iters=3, inner_lr=0.1,
        krr=KRRConfig(num_agents=4, samples_per_agent=64, num_features=256,
                      lam=5e-5, rho=1e-2, censor_v=0.1, censor_mu=0.95,
                      seed=0))
    problem = build_problem(cfg).problem
    harness.ready(fit(cfg, problem=problem))      # compiles
    tracer = harness.Tracer("v5e-record", True)
    tracer.start()
    with harness.span("window", True):
        for _ in range(2):
            with harness.span("fit", True):
                harness.ready(fit(cfg, problem=problem))
    tracer.stop()
    shutil.copy(trace.find_xplane(tracer.dir), out)
    print(f"{out}: {os.path.getsize(out)} bytes")


if __name__ == "__main__":
    main(sys.argv[1])
