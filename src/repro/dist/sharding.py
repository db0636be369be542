"""Logical-axis sharding rules: map model-side axis names to mesh axes.

Every layer in ``repro.models`` annotates its params and activations with
*logical* axes (``"embed"``, ``"heads"``, ``"batch"`` ...).  :class:`Rules`
turns a logical-axes tuple into a :class:`~jax.sharding.PartitionSpec` for a
concrete mesh, with two safety fallbacks the GA relies on (an invalid plan
must lower, not crash):

  * divisibility — a dimension is sharded over the largest prefix of its
    assigned mesh axes whose total size divides it (fully replicated only
    when not even the first axis divides);
  * duplicate axes — a mesh axis already used earlier in the same spec is
    skipped (e.g. with ``Plan.decode_kv_seq_shard`` the ``kv_seq`` axis
    claims "model" and ``kv_heads`` falls back to replicated).

``NullRules`` is the single-process no-op used when there is no mesh.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import NamedSharding, PartitionSpec

# logical axis -> mesh axes.  A tuple value shards one dimension over
# several mesh axes (and stays a tuple inside the PartitionSpec); a string
# value is a single mesh axis.  "batch"/"embed" ride the data-class axes
# (embed sharding over "data" is the FSDP-style parameter shard); the
# model-class axes carry heads / ff / experts / vocab (tensor parallel).
BASE_RULES = {
    "batch": ("pod", "data"),
    "embed": ("data",),
    "heads": "model",
    "kv_heads": "model",
    "ff": "model",
    "lru": "model",
    "vocab": "model",
    "experts": "model",
}


def batch_axes(mesh) -> Tuple[str, ...]:
    """The mesh axes that carry the batch dimension, in batch order."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


class Rules:
    """Sharding rules for one (mesh, plan) pair."""

    def __init__(self, mesh, plan=None):
        self.mesh = mesh
        self.plan = plan
        self.rules = dict(BASE_RULES)
        if plan is not None and getattr(plan, "decode_kv_seq_shard", False):
            self.rules["kv_seq"] = "model"

    # ------------------------------------------------------------------
    def _assign(self, logical: Optional[str], dim: Optional[int],
                used: set):
        """Mesh-axis entry for one dimension (None = replicated)."""
        if logical is None:
            return None
        rule = self.rules.get(logical)
        if rule is None:
            return None
        as_tuple = isinstance(rule, tuple)
        candidates = rule if as_tuple else (rule,)
        axes = tuple(a for a in candidates
                     if a in self.mesh.axis_names and a not in used)
        if not axes:
            return None
        if dim is not None:
            # shard over the largest prefix of the remaining axes whose
            # total size divides the dimension — "batch % (pod*data) != 0"
            # must degrade to sharding over "pod", not all the way to
            # replicated
            size, take = 1, 0
            for a in axes:
                if dim % (size * self.mesh.shape[a]) != 0:
                    break
                size *= self.mesh.shape[a]
                take += 1
            axes = axes[:take]
            if not axes:
                return None                  # replicate: nothing divides
        used.update(axes)
        if as_tuple:
            return axes
        return axes[0]

    def spec(self, axes: Optional[Sequence[Optional[str]]],
             dims: Optional[Sequence[int]] = None) -> PartitionSpec:
        """PartitionSpec for a logical-axes tuple (trailing Nones trimmed).

        ``dims`` (the concrete shape) enables the divisibility fallback;
        without it the rules are applied unconditionally.
        """
        entries = []
        used: set = set()
        for i, logical in enumerate(tuple(axes or ())):
            dim = None if dims is None else dims[i]
            entries.append(self._assign(logical, dim, used))
        while entries and entries[-1] is None:
            entries.pop()
        return PartitionSpec(*entries)

    def sharding(self, axes, shape=None) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec(axes, dims=shape))

    def constrain(self, x, axes):
        """``with_sharding_constraint`` x to its logical axes."""
        return jax.lax.with_sharding_constraint(
            x, self.sharding(axes, getattr(x, "shape", None)))


class NullRules:
    """No-mesh rules: every operation is the identity / fully replicated."""

    mesh = None
    plan = None

    def spec(self, axes, dims=None) -> PartitionSpec:
        return PartitionSpec()

    def sharding(self, axes, shape=None):
        return None

    def constrain(self, x, axes):
        return x


def _is_axes_leaf(x) -> bool:
    return isinstance(x, tuple) and all(
        e is None or isinstance(e, str) for e in x)


def tree_shardings(rules: Rules, axes_tree, tree_sds):
    """Pytree of NamedShardings from a logical-axes tree + matching
    ShapeDtypeStruct (or array) tree.

    ``axes_tree`` mirrors the value tree with tuples of logical axis names
    as leaves (the ``*_axes`` helpers in ``repro.models``); ``()`` marks a
    scalar leaf.
    """
    return jax.tree.map(
        lambda ax, sds: rules.sharding(ax, getattr(sds, "shape", None)),
        axes_tree, tree_sds, is_leaf=_is_axes_leaf)
