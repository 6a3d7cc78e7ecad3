"""Pallas kernels for the compute hot spots the paper optimizes (fused
dequant-matmul over HQQ-packed experts) and the attention reads of the
serving planes.  ``ops.py`` holds the dispatching wrappers and ``ref.py``
the pure-jnp oracles."""
from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """Whether a Pallas kernel runs in the interpreter.

    ``None`` decides from the backend, in this one place: the
    interpreter only where the default backend is the CPU (the tier-1
    tests); on a TPU every kernel goes through the Mosaic compiler, never
    silently through the interpreter.  An explicit bool wins — the
    compile-only tests pass ``False`` to lower a kernel for a described
    (unattached) TPU from a CPU process."""
    if interpret is None:
        return jax.default_backend() == "cpu"
    return bool(interpret)
