"""Faults planted in the program under test, to show that the comparison
catches them (`readings.py` on the chip, the CPU tests at a small size).

Each is a context manager that breaks the timed path underneath the
harness and restores it on exit:

- `unchanged`: a round that returns its state unchanged;
- `half_batch`: half of the agents left out, the mean taken over the
  rest.
"""
from __future__ import annotations

import contextlib
from typing import Dict, Iterator

import jax


@contextlib.contextmanager
def _patch_build(build) -> Iterator[None]:
    from repro.launch import train

    sound = train.build_fused_round
    train.build_fused_round = lambda *a: build(sound, *a)
    try:
        yield
    finally:
        train.build_fused_round = sound


def unchanged():
    return _patch_build(lambda sound, *a: jax.jit(
        lambda *carry: (carry[0], carry[1]) + carry[3:]))


def _half(tree):
    return jax.tree.map(lambda u: u[: u.shape[0] // 2], tree)


def half_batch():
    def build(sound, *a):
        rnd = sound(*a)
        return jax.jit(lambda x, y, data, *s: rnd(x, y, _half(data), *s))

    return _patch_build(build)


FAULTS: Dict[str, object] = {"unchanged": unchanged, "half_batch": half_batch}
