"""Device ms per iteration in the megakernel wrapper's layout copies (the
`coke.layout` scope: the pads of phi, y and the rows, the final slice),
over the traced window's iterations."""
from chipbench import stages


def read(run):
    sp = stages.read(run)
    if sp is None or not sp.scoped:
        return None
    return sp.stage_ms("coke.layout")
