"""Pallas TPU kernels: compiled on the chip, interpreted on the CPU."""
from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """The platform picks the kernel path: the Pallas interpreter on the
    CPU backend, the Mosaic compiler everywhere else.  An explicit bool
    overrides it (tests compile for a described chip from a CPU host)."""
    if interpret is None:
        return jax.default_backend() == "cpu"
    return interpret
