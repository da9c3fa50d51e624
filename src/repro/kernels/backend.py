"""Backend-aware Pallas execution mode.

Every kernel wrapper defaults its ``interpret`` flag to
``default_interpret()``: compiled Pallas on TPU, interpreter mode on every
other backend (the CPU test suite).
"""
from __future__ import annotations

from typing import Optional

import jax


def default_interpret() -> bool:
    """True when Pallas kernels should run in interpreter mode."""
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """Per-call override (tests) or the backend-aware default."""
    return default_interpret() if interpret is None else bool(interpret)
