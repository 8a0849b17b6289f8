"""Whether a Pallas call runs interpreted or compiled, decided by the backend.

The platform decides it at trace time: on CPU the kernel bodies run in
Pallas interpret mode (the same program, evaluated with XLA CPU ops), on
TPU they compile through Mosaic.  Any other backend has neither and
raises.  A caller that needs a particular mode (a test compiling for a
described TPU from a CPU process) passes ``interpret`` explicitly.
"""
from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    if interpret is not None:
        return interpret
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise NotImplementedError(
        f"Pallas kernels compile for TPU or run interpreted on CPU; the "
        f"{backend!r} backend has neither")
