"""Set-up: process start to the opening of the measured window, loading,
weights, warm-up and compilation included (host clock)."""


def read(run):
    return run.setup_s
