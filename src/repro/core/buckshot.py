"""Buckshot clustering for big text (paper §4, Fig. 2).

  Phase 1 (cluster subroutine): sample s = sqrt(k n) docs, run single-link HAC
    on the sample down to k clusters, take their centroids as initial centers.
  Phase 2: K-Means-style assignment of the whole collection with only 2-3
    iterations.

Phase 1 is MATRIX-FREE by default (``hac="boruvka"``): O(log s) rounds of the
fused sim+best-edge kernel, so the (s, s) sample similarity matrix never
exists and phase-1 peak memory is O(s*d) — the paper's 1GB-collection regime
(n = 1M, k = 500 -> s ~ 22k, a ~2 GB f32 matrix) fits one device.
``hac="prim"`` keeps the dense Prim path as the exact oracle. The initial
centers come from ONE label_stats pass over the sample (the fused labels+stats
build — HAC hands over labels, so there is no assign step to fuse with).
Phase 2 reuses the PKMeans step (core/kmeans.py), exactly as the paper reuses
its §2 implementation 'for a fair comparison with BKC'.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from repro.common import l2_normalize
from repro.core import sampling
from repro.core.hac import single_link_labels, single_link_labels_boruvka
from repro.core.kmeans import KMeansResult, kmeans_fit
from repro.kernels import ops


class BuckshotResult(NamedTuple):
    kmeans: KMeansResult
    sample_idx: jax.Array  # (s,) indices of the HAC sample
    sample_labels: jax.Array  # (s,) HAC cluster of each sampled doc
    init_centers: jax.Array  # (k, d) centers handed to phase 2


@functools.partial(jax.jit, static_argnames=("k", "impl", "hac"))
def phase1_from_sample(
    xs: jax.Array,
    k: int,
    *,
    impl: str = "auto",
    hac: str = "boruvka",
) -> tuple[jax.Array, jax.Array]:
    """Phase 1 on already-collected sample rows (s, d): HAC labels + centers.

    The shared core behind the resident (gathered rows) and streaming
    (reservoir rows) entry points — the sample is O(s·d) either way.
    """
    xs = l2_normalize(xs)
    if hac == "prim":
        labels = single_link_labels(xs @ xs.T, k)
    elif hac == "boruvka":
        labels = single_link_labels_boruvka(xs, k, impl=impl)
    else:
        raise ValueError(f"unknown hac implementation: {hac!r}")

    # HAC hands us labels directly (no assign step), so the center build is
    # ONE fused label_stats pass over the sample (d-tiled accumulator grid).
    sums, counts = ops.label_stats(xs, labels, k, impl=impl)
    init_centers = jnp.where(counts[:, None] > 0, l2_normalize(sums), 0.0)
    return labels, init_centers


@functools.partial(jax.jit, static_argnames=("k", "impl", "hac"))
def buckshot_phase1(
    x: jax.Array,
    sample_idx: jax.Array,
    k: int,
    *,
    impl: str = "auto",
    hac: str = "boruvka",
) -> tuple[jax.Array, jax.Array]:
    """Phase 1 alone: sample HAC labels + initial centers.

    hac = "boruvka" (default): matrix-free single-link via Borůvka rounds of
      the fused sim+best-edge kernel — O(s*d) memory, O(log s) rounds.
    hac = "prim": dense (s, s) similarity + Prim MST — the exact oracle path.

    Returns (labels (s,), init_centers (k, d)).
    """
    return phase1_from_sample(x[sample_idx], k, impl=impl, hac=hac)


@functools.partial(
    jax.jit,
    static_argnames=("k", "kmeans_iters", "impl", "fused", "hac", "bounded"),
)
def buckshot_fit(
    x: jax.Array,
    sample_idx: jax.Array,
    k: int,
    *,
    kmeans_iters: int = 3,
    impl: str = "auto",
    fused: bool = True,
    hac: str = "boruvka",
    bounded: bool = False,
) -> BuckshotResult:
    """Run Buckshot given the sampled document indices (s static via shape).

    bounded=True runs phase 2 through the bound-pruned assignment (the few
    Buckshot iterations still benefit: iteration 1 seeds the bounds carry,
    iterations 2-3 prune against it)."""
    labels, init_centers = buckshot_phase1(x, sample_idx, k, impl=impl, hac=hac)
    km = kmeans_fit(
        x, init_centers, k, max_iters=kmeans_iters, tol=0.0, impl=impl,
        fused=fused, bounded=bounded,
    )
    return BuckshotResult(
        kmeans=km,
        sample_idx=sample_idx,
        sample_labels=labels,
        init_centers=init_centers,
    )


def buckshot(
    x: jax.Array,
    k: int,
    key: jax.Array,
    *,
    sample_size: int | None = None,
    kmeans_iters: int = 3,
    impl: str = "auto",
    fused: bool = True,
    hac: str = "boruvka",
    bounded: bool | None = None,
) -> BuckshotResult:
    """Paper defaults: s = sqrt(k n), 2-3 assignment iterations."""
    n = x.shape[0]
    s = sample_size or sampling.buckshot_sample_size(n, k)
    sample_idx = sampling.sample_indices(key, n, s)
    return buckshot_fit(
        x, sample_idx, k, kmeans_iters=kmeans_iters, impl=impl, fused=fused,
        hac=hac, bounded=ops.bounds_enabled(bounded),
    )


# ------------------------------------------------------------------ streaming


def buckshot_stream(
    stream,
    k: int,
    key: jax.Array,
    *,
    sample_size: int | None = None,
    kmeans_iters: int = 3,
    tol: float = 0.0,
    impl: str = "auto",
    hac: str = "boruvka",
    checkpoint=None,
    guard=None,
    bounded: bool | None = None,
) -> BuckshotResult:
    """Out-of-core Buckshot: the s = √(kn) sample comes from a one-pass
    running top-s reservoir over the chunk stream (exact uniform sample —
    core/sampling.reservoir_sample_stream), phase 1 runs matrix-free on the
    O(s·d) sample, and phase 2 streams the whole collection through the
    carried-accumulator K-Means passes. Every pass rides the shared
    streaming executor (text/stream.run_pass), so chunk regeneration
    overlaps the device fold. Peak residency O(chunk·d + s·d + k·d) — the
    dense (n, d) matrix never exists anywhere. The distributed twin is
    distrib/cluster.buckshot_distributed_stream.

    ``checkpoint`` covers every data pass: the reservoir pass stores its
    sample as a result (a job killed in phase 2 skips the sample pass), and
    the phase-2 K-Means passes checkpoint under the ``buckshot/`` namespace.
    """
    from repro.core.kmeans import kmeans_fit_stream

    s = sample_size or sampling.buckshot_sample_size(stream.n, k)
    rows, sample_idx = sampling.reservoir_sample_stream(
        stream, s, key, checkpoint=checkpoint, guard=guard
    )
    with TraceAnnotation("buckshot.phase1"):
        labels, init_centers = phase1_from_sample(rows, k, impl=impl, hac=hac)
    km = kmeans_fit_stream(
        stream, init_centers, k, max_iters=kmeans_iters, tol=tol, impl=impl,
        checkpoint=checkpoint.scoped("buckshot") if checkpoint is not None else None,
        guard=guard, bounded=bounded,
    )
    if checkpoint is not None:
        checkpoint.delete_result("reservoir")  # the run is over
    return BuckshotResult(
        kmeans=km,
        sample_idx=jnp.asarray(sample_idx),
        sample_labels=labels,
        init_centers=init_centers,
    )
