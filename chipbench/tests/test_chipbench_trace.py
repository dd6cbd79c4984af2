"""The trace reduction on hand-made records with known answers, and the
loader on a trace recorded here (the CPU, so without device planes)."""
import json
import os

import pytest

from chipbench import trace

MS = 1e6  # ns


def record():
    # window [0, 100] ms; device A: ops [10,30], [20,40] (overlap),
    # [35,50], [80,90]; device B: [0,100] busy throughout.
    return {
        "window": [0.0, 100 * MS],
        "devices": {
            "TPU:0": [["fusion.1", 10 * MS, 20 * MS],
                      ["_megastep_kernel", 20 * MS, 20 * MS],
                      ["all-reduce.3", 35 * MS, 15 * MS],
                      ["fusion.1", 80 * MS, 10 * MS]],
            "TPU:1": [["copy", -5 * MS, 110 * MS]],
        },
        "host": [["chipbench.window", 0.0, 100 * MS],
                 ["chipbench.fit", 0.0, 60 * MS],
                 ["chipbench.fit", 60 * MS, 40 * MS]],
    }


def test_union_and_subtract():
    assert trace.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert trace.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert trace.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]


def test_busy_and_idle():
    r = record()
    busy = trace.busy_ns(r)
    assert busy["TPU:0"] == pytest.approx(50 * MS)   # [10,50] + [80,90]
    assert busy["TPU:1"] == pytest.approx(100 * MS)  # clipped to window
    idle = trace.idle_share(r)
    assert idle == {"TPU:0": pytest.approx(0.5), "TPU:1": pytest.approx(0)}


def test_kernel_time_and_count():
    r = record()
    pred = lambda n: "megastep" in n  # noqa: E731
    assert trace.op_ns(r, pred)["TPU:0"] == pytest.approx(20 * MS)
    assert trace.op_count(r, pred) == {"TPU:0": 1, "TPU:1": 0}


def test_top_ops_and_idle_gaps():
    r = record()
    top = dict(trace.top_ops(r))
    assert top["copy"] == pytest.approx(0.1)
    assert top["fusion.1"] == pytest.approx(0.03)
    gaps = dict(trace.idle_gaps(r))
    # TPU:0 idle [0,10] and [50,60] under the first fit, [60,80] and
    # [90,100] under the second
    assert gaps == {"chipbench.fit": pytest.approx(0.05)}


def test_load_reads_a_recorded_trace(tmp_path, monkeypatch):
    """A real profiler trace (recorded here on the CPU, so without device
    planes) loads into the record: the window and the spans inside it."""
    import jax
    import jax.numpy as jnp

    from chipbench import harness
    monkeypatch.setattr(harness, "OUT", str(tmp_path))
    tracer = harness.Tracer("trace-test", True)
    tracer.start()
    with harness.span("window", True):
        for _ in range(3):
            with harness.span("fit", True):
                jnp.sin(jnp.ones((64, 64))).block_until_ready()
    record = tracer.stop()
    assert record["devices"] == {}            # the CPU has no TPU plane
    lo, hi = record["window"]
    assert hi > lo
    fits = [h for h in record["host"] if h[0] == "chipbench.fit"]
    assert len(fits) == 3
    assert all(lo <= s and s + d <= hi for _, s, d in fits)
    assert os.path.exists(os.path.join(tracer.dir, "record.json"))
    assert isinstance(json.load(open(os.path.join(tracer.dir,
                                                  "record.json"))), dict)
    jax.clear_caches()

