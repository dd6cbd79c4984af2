"""The names a profiler trace of `fit()` carries: the `coke.*` stage scopes
in the compiled chunk's name stacks, and the `repro.fit*` host spans."""
import glob
import importlib
import re

import jax
import pytest

from repro.api import FitConfig, KRRConfig, build_problem, fit, get_solver
from repro.api import backends
from repro.api.config import SolveContext

fit_module = importlib.import_module("repro.api.fit")

KRR = KRRConfig(num_agents=4, samples_per_agent=40, num_features=32,
                lam=1e-2, rho=0.1, seed=0)
BASE = FitConfig(krr=KRR, graph="ring", algorithm="coke", censor_v=0.3,
                 censor_mu=0.97, num_iters=4, primal="gradient",
                 inner_steps=1, inner_lr=0.05)

# the fused chunk has no op in coke.exchange: the megakernel reads the
# ring-rolled neighbor rows itself (primal_owns_exchange)
SCOPES = {
    "fused": {"coke.primal", "coke.layout", "coke.comm_decide",
              "coke.dual", "coke.record", "coke.history"},
    "simulator": {"coke.exchange", "coke.primal", "coke.comm_decide",
                  "coke.dual", "coke.record", "coke.history"},
}


@pytest.mark.parametrize("backend", sorted(SCOPES))
def test_chunk_names_its_stages(backend):
    """The lowered chunk (the fused one through the megakernel, in
    interpret mode here) carries every stage scope of its path in its
    debug locations."""
    cfg = BASE.replace(backend=backend)
    problem = build_problem(cfg).problem
    ctx = SolveContext.from_config(cfg, num_agents=problem.num_agents)
    solver = get_solver(cfg.algorithm)
    runner = (backends.consensus_runner if backend == "fused"
              else fit_module._simulator_runner)
    carry0, chunk_fn, _ = runner(cfg, solver, problem, ctx, None)
    text = jax.jit(lambda c: chunk_fn(c, 2)).lower(carry0).as_text(
        debug_info=True)
    assert set(re.findall(r"coke\.[a-z_]+", text)) == SCOPES[backend]


@pytest.mark.parametrize("chunk_size,chunks", [(None, 1), (2, 2)])
def test_fit_spans_its_host_work(tmp_path, chunk_size, chunks):
    """A profiled fit() writes repro.fit around the call, repro.fit.prepare
    before the first dispatch and one repro.fit.chunk per chunk, nested
    in it, into the host plane."""
    from jax.profiler import ProfileData

    cfg = BASE.replace(backend="simulator", chunk_size=chunk_size)
    problem = build_problem(cfg).problem
    jax.block_until_ready(fit(cfg, problem=problem).theta)   # compiles
    jax.profiler.start_trace(str(tmp_path))
    try:
        jax.block_until_ready(fit(cfg, problem=problem).theta)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans: dict = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("repro."):
                        spans.setdefault(e.name, []).append(
                            (e.start_ns, e.end_ns))
    assert {k: len(v) for k, v in spans.items()} == {
        "repro.fit": 1, "repro.fit.prepare": 1, "repro.fit.chunk": chunks}
    (lo, hi), = spans["repro.fit"]
    (p0, p1), = spans["repro.fit.prepare"]
    assert lo <= p0 <= p1 <= min(s for s, _ in spans["repro.fit.chunk"])
    assert all(lo <= s <= e <= hi for s, e in spans["repro.fit.chunk"])
