"""Time per run of the destination the planner selected, each run ending
in ``block_until_ready``, over every run in the window: each block of runs
is timed as a whole (host clock)."""


def read(run):
    runs = sum(b["runs"] for b in run.app_blocks)
    if not runs:
        return None
    return 1e3 * sum(b["s"] for b in run.app_blocks) / runs
