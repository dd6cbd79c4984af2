"""The megakernel's share of its roofline: the least time its calls in
the traced window need at the peaks (chipbench.work.megastep_call), over
the device time of its events in the trace."""
from chipbench import trace
from chipbench.peaks import roofline_s

# how the trace names the coke_megastep pallas_call on the TPU
MATCH = ("_megastep_kernel", "coke_megastep")


def is_megastep(name: str) -> bool:
    return any(m in name for m in MATCH)


def read(run):
    if run.fit is None or run.trace is None:
        return None
    calls = sum(trace.op_count(run.trace, is_megastep).values())
    busy = sum(trace.op_ns(run.trace, is_megastep).values()) / 1e9
    if not calls or busy <= 0:
        return None
    flops, nbytes = run.fit["megastep_call"]
    return 100.0 * calls * roofline_s(flops, nbytes, run.peaks) / busy
