"""Device ms per iteration in the consensus stages outside the primal
(`coke.exchange`, `coke.comm_decide`, `coke.dual`, `coke.record`: the
neighbor exchange, the censor's decisions, the dual update and the
sends' count), over the traced window's iterations."""
from chipbench import stages


def read(run):
    sp = stages.read(run)
    if sp is None or not sp.scoped:
        return None
    return sp.stage_ms(*stages.CONSENSUS)
