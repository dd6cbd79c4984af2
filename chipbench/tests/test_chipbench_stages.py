"""The program's stages and host spans in a trace: the reduction on
hand-made records with known answers, the four readers, and the loaders
on a trace recorded on a TPU v5e (`data/v5e_fit.xplane.pb`, made by
`record_v5e_trace.py`)."""
import os
import types

import pytest

from chipbench import spec, stages, trace

MS = 1e6  # ns
DATA = os.path.join(os.path.dirname(__file__), "data", "v5e_fit.xplane.pb")
READERS = ("fit_layout_ms", "fit_history_ms", "fit_consensus_ms",
           "fit_call_idle_ms")


@pytest.mark.parametrize("stack,stage", [
    # as the TPU trace gives them: "<name stack>:<op type>"
    ("jit(_megastep_chunk)/while/body/closed_call/coke.history/"
     "ntd,nd->nt/dot_general:", "coke.history"),
    # the wrapper's copies sit inside the primal stage: innermost wins
    ("jit(_megastep_chunk)/while/body/closed_call/coke.primal/"
     "jit(_coke_megastep)/coke.layout/jit(_pad)/pad:", "coke.layout"),
    ("jit(_megastep_chunk)/while/body/closed_call/coke.primal/"
     "jit(_coke_megastep)/coke_megastep/pallas_call:", "coke.primal"),
    ("jit(_megastep_chunk)/while/body/coke.dual/add", "coke.dual"),
    ("jit(_megastep_chunk)/while/body/copy:", "unscoped"),
    ("jit(_threefry_seed)/concatenate:", "unscoped"),
    ("", "unscoped"),
])
def test_stage_is_the_innermost_coke_scope(stack, stage):
    assert stages.stage_of(stack) == stage


def record(devices=("TPU:0",)):
    # window [0, 100] ms, two fit calls. Per device: ops [0,2] (clipped
    # from [-5,2]), [4,5], [10,48] in five stages, [60,84] in four; idle
    # [2,4] (inside the first prepare span), [5,10] (in the first
    # repro.fit, past its prepare), [48,60] and [84,100] (most under the
    # second chipbench.fit).
    ops = [["coke.layout", -5 * MS, 7 * MS],
           ["unscoped", 4 * MS, 1 * MS],
           ["coke.layout", 10 * MS, 20 * MS],
           ["coke.primal", 30 * MS, 10 * MS],
           ["coke.history", 40 * MS, 5 * MS],
           ["coke.dual", 45 * MS, 1 * MS],
           ["unscoped", 46 * MS, 2 * MS],
           ["coke.layout", 60 * MS, 20 * MS],
           ["coke.comm_decide", 80 * MS, 2 * MS],
           ["coke.record", 82 * MS, 1 * MS],
           ["coke.exchange", 83 * MS, 1 * MS]]
    host = [["chipbench.window", 0.0, 100 * MS],
            ["chipbench.fit", 0.0, 50 * MS],
            ["repro.fit", 1 * MS, 29 * MS],
            ["repro.fit.prepare", 1 * MS, 7 * MS],
            ["repro.fit.chunk", 8 * MS, 1 * MS],
            ["chipbench.fit", 50 * MS, 50 * MS],
            ["repro.fit", 52 * MS, 10 * MS],
            ["repro.fit.prepare", 52 * MS, 7 * MS],
            ["repro.fit.chunk", 59 * MS, 1 * MS]]
    return {"window": [0.0, 100 * MS],
            "devices": {d: [list(o) for o in ops] for d in devices},
            "host": host}


@pytest.mark.parametrize("devices", [("TPU:0",), ("TPU:0", "TPU:1")],
                         ids=["one", "two"])
def test_split_by_stage_and_span(devices):
    """Self time by stage, busy time and idle by the innermost host span,
    per device (a second identical device changes nothing)."""
    sp = stages.split(record(devices), iterations=2)
    assert sp.devices == len(devices) and sp.fit_calls == 2
    assert sp.stage_ns == {
        "coke.layout": pytest.approx(42 * MS),
        "coke.primal": pytest.approx(10 * MS),
        "coke.history": pytest.approx(5 * MS),
        "coke.dual": pytest.approx(1 * MS),
        "coke.comm_decide": pytest.approx(2 * MS),
        "coke.record": pytest.approx(1 * MS),
        "coke.exchange": pytest.approx(1 * MS),
        "unscoped": pytest.approx(3 * MS)}
    assert sp.busy_ns == pytest.approx(sum(sp.stage_ns.values()))
    # [2,4] -> the prepare span (shortest of three covering it); [5,10]
    # -> repro.fit (covers all of it, shorter than chipbench.fit);
    # [48,60] and [84,100] -> chipbench.fit, which covers the most
    assert sp.idle_ns == {"repro.fit.prepare": pytest.approx(2 * MS),
                          "repro.fit": pytest.approx(5 * MS),
                          "chipbench.fit": pytest.approx(28 * MS)}
    assert sp.scoped


def test_per_iteration_and_per_call():
    sp = stages.split(record(), iterations=2)
    assert sp.stage_ms("coke.layout") == pytest.approx(21.0)
    assert sp.stage_ms("coke.history") == pytest.approx(2.5)
    assert sp.stage_ms(*stages.CONSENSUS) == pytest.approx(2.5)
    assert sp.stage_ms(stages.UNSCOPED) == pytest.approx(1.5)
    assert sp.stage_ms("coke.nothing") == 0.0
    # 7 ms of idle under repro.fit* spans over two calls
    assert sp.fit_idle_ms() == pytest.approx(3.5)


def _run(traced=True):
    return types.SimpleNamespace(
        trace={} if traced else None, fit={"iterations": 2},
        cell=types.SimpleNamespace(name="d16k.fit"), on="test")


def _readers():
    return {name: spec.load_reader(name) for name in READERS}


def _serve(monkeypatch, rec):
    monkeypatch.setattr(trace, "find_xplane", lambda d: "trace.xplane.pb")
    monkeypatch.setattr(stages, "load", lambda path: rec)


def test_readers(monkeypatch, capsys):
    _serve(monkeypatch, record())
    got = {name: read(_run()) for name, read in _readers().items()}
    assert got == {"fit_layout_ms": pytest.approx(21.0),
                   "fit_history_ms": pytest.approx(2.5),
                   "fit_consensus_ms": pytest.approx(2.5),
                   "fit_call_idle_ms": pytest.approx(3.5)}
    # each logs the whole split per iteration
    err = capsys.readouterr().err
    assert err.count("device busy 32.5 = coke.layout 21.0") == 4
    assert "unscoped 1.5" in err and "coke.primal 5.0" in err
    assert "idle by host span: chipbench.fit 14.0" in err


def test_readers_find_nothing_untraced(monkeypatch):
    _serve(monkeypatch, record())
    assert all(read(_run(traced=False)) is None
               for read in _readers().values())


def test_readers_find_nothing_in_an_unnamed_program(monkeypatch):
    """A program without stage scopes or fit spans (the code before they
    were added) reads None, and raises nothing."""
    rec = record()
    for ops in rec["devices"].values():
        for op in ops:
            op[0] = stages.UNSCOPED
    rec["host"] = [h for h in rec["host"] if h[0].startswith("chipbench.")]
    _serve(monkeypatch, rec)
    assert all(read(_run()) is None for read in _readers().values())


def test_readers_find_nothing_without_a_device(monkeypatch):
    """A trace with host spans but no device plane (the CPU) reads
    None."""
    rec = dict(record(), devices={})
    _serve(monkeypatch, rec)
    assert all(read(_run()) is None for read in _readers().values())


def test_readers_find_nothing_without_a_trace_file(monkeypatch):
    def missing(d):
        raise FileNotFoundError(d)
    monkeypatch.setattr(trace, "find_xplane", missing)
    assert all(read(_run()) is None for read in _readers().values())


# ---------------------------------------------------------------------------
# the loaders on a trace recorded on a TPU v5e
# ---------------------------------------------------------------------------

FITS, ITERS = 2, 3   # record_v5e_trace.py: two 3-iteration fused fits


def _is_kernel(name):
    return "coke_megastep" in name


def test_trace_load_reads_the_tpu_plane():
    """`trace.load` keeps the `XLA Ops` line of the TPU plane without its
    control flow: one megakernel event per iteration, inside the
    window."""
    from jax.profiler import ProfileData

    rec = trace.load(DATA)
    assert set(rec["devices"]) == {"TPU:0"}
    names = [op[0] for op in rec["devices"]["TPU:0"]]
    assert not any(n.startswith(trace.CONTROL_FLOW) for n in names)
    raw = [e.name for p in ProfileData.from_file(DATA).planes
           if p.name == "/device:TPU:0" for line in p.lines
           if line.name == trace.OPS_LINE for e in line.events]
    assert any(n.startswith("%while") for n in raw)   # left out above
    assert len(raw) > len(names)
    assert trace.op_count(rec, _is_kernel) == {"TPU:0": FITS * ITERS}
    assert sum(h[0] == "chipbench.fit" for h in rec["host"]) == FITS
    lo, hi = rec["window"]
    assert all(lo <= s and s + d <= hi for n, s, d in
               rec["devices"]["TPU:0"] if _is_kernel(n))


def test_the_name_stack_stat_is_in_the_tpu_plane():
    with open(DATA, "rb") as f:
        space = memoryview(f.read())
    plane = next(p for k, p in stages._fields(space) if k == 1
                 and stages._text(dict(stages._fields(p))[2])
                 == "/device:TPU:0")
    stat_names = {stages._text(dict(stages._fields(
        dict(stages._fields(v))[2])).get(2, b""))
        for k, v in stages._fields(plane) if k == 5}
    assert stages.NAME_STACK_STAT in stat_names


def test_stages_load_matches_trace_load():
    """`stages.load` gives the same device intervals as `trace.load`, each
    named "<stage> <op>" by its stage and the op `trace.load` names, and
    the same host spans, the program's nested in the harness's."""
    rec, st = trace.load(DATA), stages.load(DATA)
    assert st["window"] == rec["window"]
    assert st["host"] == rec["host"]
    ops, staged = rec["devices"]["TPU:0"], st["devices"]["TPU:0"]
    assert [o[1:] for o in staged] == [o[1:] for o in ops]
    by_name = {}
    for (name, _, _), (named, _, _) in zip(ops, staged):
        stage = stages.stage_name(named)
        assert named == f"{stage} {name}"
        by_name.setdefault(name, set()).add(stage)
    assert {s for n, ss in by_name.items() if _is_kernel(n)
            for s in ss} == {"coke.primal"}
    assert {s for ss in by_name.values() for s in ss} >= {
        "coke.layout", "coke.primal", "coke.comm_decide", "coke.dual",
        "coke.record", "coke.history", stages.UNSCOPED}
    count = {}
    for name, _, _ in st["host"]:
        count[name] = count.get(name, 0) + 1
    assert count == {"chipbench.window": 1, "chipbench.fit": FITS,
                     "repro.fit": FITS, "repro.fit.prepare": FITS,
                     "repro.fit.chunk": FITS}
    fits = [h for h in st["host"] if h[0] == "chipbench.fit"]
    for name, s, d in st["host"]:
        if name.startswith("repro."):
            assert any(fs <= s and s + d <= fs + fd for _, fs, fd in fits)


def test_split_of_the_recorded_trace():
    sp = stages.split(stages.load(DATA), iterations=FITS * ITERS)
    assert sp.devices == 1 and sp.fit_calls == FITS and sp.scoped
    assert sum(sp.stage_ns.values()) == pytest.approx(sp.busy_ns, rel=0.02)
    assert sp.stage_ms("coke.primal") > 0 and sp.stage_ms("coke.layout") > 0
    assert set(sp.idle_ns) <= {"chipbench.fit", "repro.fit",
                               "repro.fit.prepare", "repro.fit.chunk"}


def test_breakdown_names_stages_and_program_spans(monkeypatch):
    """A traced run's `breakdown` names each device op by its stage and
    op, and each idle gap by the innermost span open in it, the
    program's `repro.*` spans among them."""
    from chipbench import harness
    monkeypatch.setattr(trace, "find_xplane", lambda d: DATA)
    run = harness.Run(cell=types.SimpleNamespace(name="d16k.fit",
                                                 end_to_end=(),
                                                 per_layer=()),
                      seed=0, seconds=1.0, on="test", peaks=None,
                      trace=trace.load(DATA))
    device = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    b = harness.result_line(run, [device], traced=True)["breakdown"]
    ops = dict(b["device_ops"])
    assert len(ops) == 10
    assert "coke.primal %coke_megastep.8" in ops
    assert all(stages.stage_name(op).startswith(("coke.", stages.UNSCOPED))
               and op.split(" ", 1)[1].startswith("%") for op in ops)
    gaps = dict(b["idle_gaps"])
    assert "repro.fit.prepare" in gaps
    assert set(gaps) <= {"chipbench.fit", "repro.fit", "repro.fit.prepare",
                         "repro.fit.chunk"}
