"""Where a Pallas kernel runs: compiled on a TPU, interpreted elsewhere.

The one place the platform choice is made (`on_tpu`), for the
`interpret` default and, with the mesh the program is traced under
(`on_one_tpu`), for the model's kernel paths.  Callers above the kernels
pass nothing; a test that compiles for a described TPU from a CPU host
passes `interpret=False` itself, or patches `on_tpu`.
"""
from __future__ import annotations

import math
from typing import Optional

import jax


def on_tpu() -> bool:
    """Whether the program is built for a TPU, where the kernels compile
    to Mosaic."""
    return jax.default_backend() == "tpu"


def on_one_tpu() -> bool:
    """Whether the program being traced runs on one TPU device, so that
    the model may take the paths that only a TPU runs (`models/mamba.py`'s
    fused scan): built for a TPU, and not spread by the partitioner over
    a mesh of more than one device (the mesh in context, `jax.set_mesh`
    or `jax.sharding.use_abstract_mesh`, less the axes a `shard_map` made
    manual).  A Mosaic kernel cannot be partitioned automatically, so the
    model keeps its jnp paths on such a mesh; `launch/steps.py` traces
    its SPMD steps under their mesh for this."""
    mesh = jax.sharding.get_abstract_mesh()
    spread = math.prod(
        n for a, n in mesh.shape.items() if a not in mesh.manual_axes
    )
    return on_tpu() and spread == 1


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """`interpret` as given, or, when None, True exactly off a TPU: the
    kernels compile to Mosaic on a TPU and run in the Pallas interpreter
    on every other backend."""
    if interpret is None:
        return not on_tpu()
    return interpret


def refuse_tpu_lowering(kernel: str, reason: str) -> None:
    """Fail before lowering a kernel configuration that does not compile
    for a TPU, saying why (the primitive Pallas cannot lower), instead
    of letting the caller swap in another path unannounced."""
    raise NotImplementedError(
        f"{kernel} does not lower for TPU: {reason}; use_kernel=False "
        "takes the jnp path"
    )
