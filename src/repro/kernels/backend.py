"""Where a Pallas kernel runs: compiled on a TPU, interpreted elsewhere.

The one place the `interpret` choice is made.  Callers above the kernels
pass nothing; a test that compiles for a described TPU from a CPU host
passes `interpret=False` itself.
"""
from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """`interpret` as given, or, when None, True exactly off a TPU: the
    kernels compile to Mosaic on a TPU and run in the Pallas interpreter
    on every other backend."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def refuse_tpu_lowering(kernel: str, reason: str) -> None:
    """Fail before lowering a kernel configuration that does not compile
    for a TPU, saying why (the primitive Pallas cannot lower), instead
    of letting the caller swap in another path unannounced."""
    raise NotImplementedError(
        f"{kernel} does not lower for TPU: {reason}; use_kernel=False "
        "takes the jnp path"
    )
