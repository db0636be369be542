"""Planner -> TimedRunner: planner wall time per measured candidate,
compilation included (host clock).  Moves plan_s."""


def read(run):
    n = sum(p["candidates"] for p in run.plans)
    if not n:
        return None
    return sum(p["s"] for p in run.plans) / n
