"""Share of the peaks of the cell's chips together that one ADMM
iteration's required work (phi read once, 4 N T D flops;
chipbench.work.admm_iteration) would take, over the traced run's time per
iteration (host clock). It counts the same work whatever primal or kernel
implements the iteration, and however many chips share it."""
from chipbench.peaks import roofline_s


def read(run):
    if run.fit is None or run.trace is None or not run.fit["iterations"]:
        return None
    need = roofline_s(*run.fit["work"], run.peaks) / run.cell.chips
    return 100.0 * need / (run.window_s / run.fit["iterations"])
