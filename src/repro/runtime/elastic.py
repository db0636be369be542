"""Elastic scaling: resume any checkpoint onto a different mesh.

Checkpoints store full (global) arrays, so resharding is a pure placement
decision at restore time.  ``reshard_restore`` rebuilds the sharding pytree
for the *new* mesh from the model's logical axes and restores onto it —
scale from 512 chips to 256 (or to this CPU host) without conversion.

:class:`ResizeEvent` / :func:`detect_resize` are the signal side: an edge
detector over the live device count that the online fleet controller
(:class:`repro.runtime.control.FleetController.on_resize`) consumes to
trigger a placement replan when a slice is lost or regained.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional

import jax

from repro.checkpoint.checkpointer import Checkpointer
from repro.dist.plan import Plan
from repro.dist.sharding import Rules, tree_shardings


@dataclass(frozen=True)
class ResizeEvent:
    """One observed change in usable capacity (devices, chips, slots)."""
    tick: int
    n_before: int
    n_after: int

    @property
    def grew(self) -> bool:
        return self.n_after > self.n_before


def detect_resize(prev_n: Optional[int], n: int,
                  tick: int = 0) -> Optional[ResizeEvent]:
    """Edge-detect a capacity change: None while the count is stable (or
    on the first observation), a :class:`ResizeEvent` on any transition —
    the elastic-restart signal the fleet controller replans on."""
    if prev_n is None or prev_n == n:
        return None
    return ResizeEvent(tick=tick, n_before=prev_n, n_after=n)


def shardings_for(cfg, mesh, plan: Plan, tree_sds, axes_tree):
    rules = Rules(mesh, plan)
    return tree_shardings(rules, axes_tree, tree_sds)


def reshard_restore(ckpt: Checkpointer, *, step: Optional[int],
                    new_mesh, plan: Plan, cfg, make_abstract,
                    axes_tree) -> Any:
    """Restore checkpoint `step` re-sharded for `new_mesh`.

    make_abstract() -> pytree of ShapeDtypeStruct matching the saved tree.
    """
    sds = make_abstract()
    shardings = shardings_for(cfg, new_mesh, plan, sds, axes_tree)
    tree, extra = ckpt.restore(step, shardings=shardings)
    return tree, extra


def available_mesh(preferred_shape=None, axes=("data", "model")):
    """Best mesh for the devices that are actually alive (elastic restart
    after losing a slice): largest power-of-two data axis x rest."""
    import numpy as np
    from jax.sharding import AxisType, Mesh
    n = len(jax.devices())
    auto = (AxisType.Auto,) * len(axes)
    if preferred_shape is not None:
        need = 1
        for s in preferred_shape:
            need *= s
        if need <= n:
            devs = np.asarray(jax.devices()[:need]).reshape(preferred_shape)
            return Mesh(devs, axes, axis_types=auto)
    # fall back: 1-D data mesh over whatever is left
    return Mesh(np.asarray(jax.devices()).reshape(n, 1), axes,
                axis_types=auto)
