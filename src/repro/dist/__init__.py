"""Parallelism-plan subsystem: the framework-side "environment-adaptive"
configuration layer (paper §II.C applied to the mesh, DESIGN.md §2).

Public API (stable — later PRs build on this):

  * :mod:`repro.dist.plan`      — :class:`Plan` execution-plan dataclass with
    the categorical ``GENE_SPACE`` the GA searches (``from_genes`` /
    ``to_genes`` / ``gene_cardinalities``); ``Gene(field, choices,
    structural)`` entries flag the model-only pipeline genes
    (``pipeline_schedule`` / ``virtual_stages``), and
    ``Plan.structural_key()`` is the compiled-artifact identity
    ``repro.core.search_cache`` dedupes compiles by.
  * :mod:`repro.dist.sharding`  — :class:`Rules` (logical-axis -> mesh-axis
    mapping with largest-divisible-prefix / duplicate-axis fallback),
    :class:`NullRules`, ``tree_shardings`` and ``batch_axes``.
  * :mod:`repro.dist.schedules` — pipeline-parallel schedules as static tick
    plans: :class:`Schedule` / :class:`TickPlan`, built-ins ``gpipe``,
    ``one_f_one_b``, ``interleaved`` (``SCHEDULES`` / ``get_schedule`` /
    ``register_schedule``).
  * :mod:`repro.dist.pipeline`  — ``pipeline_apply`` / ``sequential_apply``
    (stage parallelism over the "pod" axis under any registered schedule).
  * :mod:`repro.dist.bridge`    — planner <-> mesh bridge: compile a
    dp / tp candidate under a real mesh via ``CompiledCostRunner``.
"""
from repro.dist.plan import Plan
from repro.dist.schedules import (SCHEDULES, Schedule, TickPlan,
                                  get_schedule, register_schedule)
from repro.dist.sharding import NullRules, Rules, batch_axes, tree_shardings

__all__ = ["Plan", "Rules", "NullRules", "tree_shardings", "batch_axes",
           "Schedule", "TickPlan", "SCHEDULES", "get_schedule",
           "register_schedule"]
