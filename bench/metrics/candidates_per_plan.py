"""Planner: candidate patterns measured per plan (each verification's
distinct measured choices, summed; a count).  Moves plan_s."""


def read(run):
    if not run.plans:
        return None
    return sum(p["candidates"] for p in run.plans) / len(run.plans)
