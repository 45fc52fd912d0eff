"""Sharding helpers shared by the clustering engine and the model runtime."""

from __future__ import annotations

import math
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P


def make_mesh(
    shape: Sequence[int], axes: Sequence[str], devices=None
) -> Mesh:
    """``jax.make_mesh`` with Auto axis types: the device order follows the
    chips' physical topology, so mesh neighbours are interconnect
    neighbours, not whatever order ``jax.devices()`` lists."""
    shape, axes = tuple(shape), tuple(axes)
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
        devices=devices,
    )


def make_flat_mesh(n_devices: int | None = None, axis: str = "data") -> Mesh:
    """1-D mesh over all (or first n) local devices — the clustering layout."""
    devs = jax.devices()[: n_devices or len(jax.devices())]
    return make_mesh((len(devs),), (axis,), devices=devs)


def make_pod_mesh(
    n_pods: int,
    pod_size: int | None = None,
    axes: tuple[str, str] = ("pod", "data"),
) -> Mesh:
    """2-D (n_pods, pod_size) mesh — the two-tier collective layout.

    Rows stay sharded over BOTH axes (``data_spec(axes, ...)`` flattens
    them), but collectives that reduce per axis — the tiered 'component'
    reduce — resolve the innermost ``data`` axis (intra-pod links) before
    anything crosses the ``pod`` axis. ``pod_size=None`` divides the local
    device count by ``n_pods``; non-power-of-two shapes like (2, 3) are
    fine — only the product must not exceed the devices available.
    """
    if pod_size is None:
        if len(jax.devices()) % n_pods:
            raise ValueError(
                f"{len(jax.devices())} devices do not split into {n_pods} pods"
            )
        pod_size = len(jax.devices()) // n_pods
    devs = jax.devices()[: n_pods * pod_size]
    if len(devs) < n_pods * pod_size:
        raise ValueError(
            f"need {n_pods * pod_size} devices for a ({n_pods}, {pod_size})"
            f" pod mesh, have {len(devs)}"
        )
    return make_mesh((n_pods, pod_size), axes, devices=devs)


def tier_sizes(mesh: Mesh, axes: tuple[str, ...]) -> tuple[int, ...]:
    """Per-tier shard counts, outermost first: (n_pods, pod_size) on a pod
    mesh, (P,) on a flat one. This tuple IS the tier topology — AOT caches
    key on it so executables never survive a mesh reshape, and the analytic
    shuffle accounting splits bytes across it."""
    return tuple(int(mesh.shape[a]) for a in axes)


def data_spec(axes: tuple[str, ...], ndim: int) -> P:
    """Shard dim 0 over (possibly multiple) mesh axes, replicate the rest."""
    return P(axes, *(None,) * (ndim - 1))


def ring_permutation(size: int) -> list[tuple[int, int]]:
    """ppermute pairs of a one-step rotation along a mesh axis: shard i's
    block moves to shard i+1 (mod size), so ``size`` successive rotations
    visit every block on every shard — the exchange schedule of the sharded
    candidate sweep (engine.ring_sweep, DESIGN.md §16)."""
    return [(i, (i + 1) % size) for i in range(size)]


def ring_block_rows(s: int, n_shards: int) -> int:
    """Rows of one ring block: the padded sample splits evenly, so every
    visiting block (and therefore every ppermute hop) is the same
    ceil-to-multiple slice — the unit of the sharded sweep's per-device
    residency model O(s/P·d) (DESIGN.md §16)."""
    return (s + ((-s) % n_shards)) // n_shards


def replicated(ndim: int) -> P:
    del ndim
    return P()


def shard_rows(mesh: Mesh, axes: tuple[str, ...], x: jax.Array) -> jax.Array:
    """Place an array with rows sharded over `axes` (host -> devices)."""
    return jax.device_put(x, NamedSharding(mesh, data_spec(axes, x.ndim)))


def mesh_axis_size(mesh: Mesh, axes: tuple[str, ...]) -> int:
    return int(math.prod(mesh.shape[a] for a in axes))


def check_stream_shardable(stream, mesh: Mesh, axes: tuple[str, ...]) -> None:
    """Streaming entry points shard each fixed-size chunk on arrival; the
    chunk row count must divide over the data shards."""
    n_shards = mesh_axis_size(mesh, axes)
    if stream.chunk % n_shards:
        raise ValueError(
            f"stream chunk {stream.chunk} must divide over {n_shards} shards"
        )


def pad_rows_to_multiple(
    x: np.ndarray | jax.Array, multiple: int
) -> tuple[Any, Any]:
    """Pad rows to a multiple of the shard count; returns (padded, weights).

    Weights are 1.0 for real rows and 0.0 for padding — every distributed job
    threads them so padding never contributes to statistics.
    """
    n = x.shape[0]
    pad = (-n) % multiple
    w = jnp.ones((n,), jnp.float32)
    if pad:
        x = jnp.concatenate([jnp.asarray(x), jnp.zeros((pad,) + x.shape[1:], x.dtype)])
        w = jnp.concatenate([w, jnp.zeros((pad,), jnp.float32)])
    return jnp.asarray(x), w
