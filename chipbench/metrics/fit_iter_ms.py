"""Milliseconds per ADMM iteration: the whole window over all the
iterations of its fits, host clock, every fit ended in
block_until_ready."""


def read(run):
    if run.fit is None or not run.fit["iterations"]:
        return None
    return run.window_s / run.fit["iterations"] * 1e3
