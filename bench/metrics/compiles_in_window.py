"""Batcher: programs compiled or fetched from the compile cache inside the
measured window (a count; 0 when warm-up covered every shape).  Moves
ttft_p50_ms."""


def read(run):
    return run.compiles_in_window
