"""Where the Pallas kernels run: compiled on a TPU, interpreted elsewhere.

Interpret mode is a property of the platform, not a user option.  Every
kernel wrapper takes ``interpret=None`` and resolves it here, so a
process on a TPU backend never times the Pallas interpreter and a
process on CPU (the test suite) never tries to compile Mosaic.  An
explicit ``True``/``False`` is kept for tests and the static analyzer,
which need to steer one kernel call against the platform default.
"""
from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """``interpret`` if given, else ``True`` unless the default backend
    is a TPU."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)
