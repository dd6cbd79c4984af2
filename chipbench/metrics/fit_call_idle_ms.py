"""Device idle ms per `fit()` call while the program's own host work runs:
the traced window's idle gaps whose innermost open host span is a
`repro.fit*` span (trace.py's rule), over the fit calls in the window."""
from chipbench import stages


def read(run):
    sp = stages.read(run)
    if sp is None or not sp.devices or not sp.fit_calls:
        return None
    return sp.fit_idle_ms()
