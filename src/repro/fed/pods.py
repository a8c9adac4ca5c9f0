"""Pod-level wire payloads + pod/shard alignment for the two-level tree.

The hierarchical aggregation path (million-agent ROADMAP item) inserts a
pod tier between agents and the server: active agents aggregate into
their pod's partial weighted sum (`core.engine.pod_weighted_sums`), and
each LIVE pod ships ONE partial payload to the server instead of the
server fanning in every agent.  This module owns the wire side of that
tier, reusing the PR-3 transport stack end to end:

  * `encode_pod_partials` packs the live pods' partial-sum rows as a
    `transport.PackedTree` with DENSE leaf specs — the dense encoding
    round-trips bitwise (decode(encode(c)) == c, the transport
    conformance contract), so shipping partials through the packed path
    moves no values;
  * `pod_payload_bytes` prices one pod's per-round traffic (partial up,
    broadcast down) with the same priced == measured contract the
    per-agent payloads carry (`sim.elastic.schedule_bytes` consumes it
    for the pod edge);
  * `pod_aligned_shard_count` picks an agent-shard count that keeps
    whole pods inside single shards, so `AsyncFederatedRunner`'s
    skip-absent-shards dispatch doubles as "skip quiet pods": a pod
    with no active agents never costs a device program.
"""
from __future__ import annotations

from typing import List, Tuple

import jax
import numpy as np

from ..core.types import Pytree
from .transport import (
    HEADER_BYTES,
    LeafSpec,
    PackedTree,
    encode_leaf,
    probe_leaf_bytes,
)


def pod_aligned_shard_count(num_pods: int, max_shards: int) -> int:
    """Largest shard count <= max_shards that divides `num_pods`, so
    every shard holds a whole number of pods.  With pod-aligned shards,
    a fully-quiet pod lands inside a shard whose other pods may still
    be live — but a run of quiet pods spanning a whole shard makes that
    shard's `active.any()` false and the async runner skips it without
    any pod-specific dispatch logic."""
    if num_pods < 1 or max_shards < 1:
        raise ValueError(
            f"need num_pods >= 1 and max_shards >= 1, got "
            f"{num_pods}, {max_shards}"
        )
    for d in range(min(num_pods, max_shards), 0, -1):
        if num_pods % d == 0:
            return d
    return 1


def encode_pod_partials(partials: Pytree) -> PackedTree:
    """Pack per-pod partial aggregates (leaves with a leading pod axis —
    typically only the LIVE pods' rows, gathered by the caller) into a
    `PackedTree` of DENSE payloads.  Dense specs (ratio 1.0, 32 bits)
    make the encode/decode round trip bitwise, so the pod tier can ride
    the exact wire machinery the compressed strategies use without
    perturbing the aggregate (tests/test_sparse_elastic.py pins the
    round trip)."""
    leaves, treedef = jax.tree.flatten(partials)
    payloads, specs, shapes = [], [], []
    for u in leaves:
        num_rows = u.shape[0]
        base = LeafSpec.build(u.shape[1:], u.dtype, 1.0, 32)
        spec = base.stacked(num_rows)
        flat = u.reshape(num_rows * base.rows, base.cols)
        payload, _ = encode_leaf(flat, None, None, None, spec)
        payloads.append(payload)
        specs.append(spec)
        shapes.append(u.shape)
    return PackedTree(payloads, specs, treedef, shapes)


def pod_payload_bytes(x: Pytree, y: Pytree, *, measured: bool = True) -> int:
    """Per-round wire bytes of ONE live pod on the pod <-> server edge:
    the pod's partial aggregate up plus the server broadcast down — two
    dense (x, y) model copies in packed framing (headers included).
    `measured=True` probes the encoder's actual emitted buffers
    (`transport.probe_leaf_bytes`), `measured=False` takes the spec
    arithmetic; the PR-3 conformance contract keeps the two equal."""
    total = 0
    for u in jax.tree.leaves((x, y)):
        spec = LeafSpec.build(u.shape, u.dtype, 1.0, 32)
        total += (
            probe_leaf_bytes(spec) if measured else spec.wire_bytes()
        ) + HEADER_BYTES
    return 2 * total


def decode_pod_partials(tree: PackedTree) -> Pytree:
    """Inverse of `encode_pod_partials` (bitwise, dense specs)."""
    return tree.decode()
