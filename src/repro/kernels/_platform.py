"""Where the Pallas kernels run: the one place that decides it.

Every kernel entry point takes ``interpret: Optional[bool] = None`` and
passes it through :func:`resolve_interpret`.  ``None`` follows the
platform: Mosaic-compiled on a TPU, the Pallas interpreter elsewhere (the
CPU test suite).  An explicit bool forces one or the other.
"""
from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """None -> native (False) on a TPU backend, interpreter (True) on any
    other; an explicit bool passes through."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret
