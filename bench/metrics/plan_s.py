"""Planner wall time per completed ``plan_offload`` call, over every call
in the window (host clock)."""


def read(run):
    if not run.plans:
        return None
    return sum(p["s"] for p in run.plans) / len(run.plans)
