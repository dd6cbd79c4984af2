"""Set-up: process start to the window's start (loading, data, compile or
compile-cache reads, warm-up)."""


def read(run):
    return run.setup_s
