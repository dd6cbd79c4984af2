"""Device ms per iteration in the per-iteration history (the
`coke.history` scope: train MSE, consensus gap, sends, bits), over the
traced window's iterations."""
from chipbench import stages


def read(run):
    sp = stages.read(run)
    if sp is None or not sp.scoped:
        return None
    return sp.stage_ms("coke.history")
