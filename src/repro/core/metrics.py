"""Clustering quality metrics: RSS (paper's metric), cosine objective, purity, NMI.

The paper clusters by cosine similarity but reports RSS. For unit-norm documents
RSS decomposes as ``RSS = n - sum_k n_k * ||mean_k||^2`` (means over members,
NOT renormalized), which we exploit so RSS costs one stats pass, no residuals.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.common import bincount, segment_sum
from repro.kernels import ops


@functools.partial(jax.jit, static_argnames=("k",))
def rss(x: jax.Array, idx: jax.Array, k: int) -> jax.Array:
    """Residual sum of squares vs member-mean centroids (general, any norm)."""
    sums, counts = ops.label_stats(x, idx, k)
    means = sums / jnp.maximum(counts, 1.0)[:, None]
    sq_norm_x = jnp.sum(x.astype(jnp.float32) ** 2)
    sq_norm_m = jnp.sum(counts * jnp.sum(means * means, axis=1))
    return sq_norm_x - sq_norm_m


@jax.jit
def cosine_objective(best_sim: jax.Array) -> jax.Array:
    """Sum of (1 - cos(x, assigned center)); lower is better."""
    return jnp.sum(1.0 - best_sim)


@functools.partial(jax.jit, static_argnames=("k_pred", "k_true"))
def contingency(
    pred: jax.Array, true: jax.Array, k_pred: int, k_true: int
) -> jax.Array:
    """(k_pred, k_true) label co-occurrence counts."""
    flat = pred.astype(jnp.int32) * k_true + true.astype(jnp.int32)
    counts = bincount(flat, k_pred * k_true)
    return counts.reshape(k_pred, k_true).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("k_pred", "k_true"))
def purity(pred: jax.Array, true: jax.Array, k_pred: int, k_true: int) -> jax.Array:
    c = contingency(pred, true, k_pred, k_true)
    return jnp.sum(jnp.max(c, axis=1)) / jnp.sum(c)


@functools.partial(jax.jit, static_argnames=("k_pred", "k_true"))
def nmi(pred: jax.Array, true: jax.Array, k_pred: int, k_true: int) -> jax.Array:
    """Normalized mutual information (sqrt normalization)."""
    c = contingency(pred, true, k_pred, k_true)
    n = jnp.sum(c)
    p = c / n
    pi = jnp.sum(p, axis=1)  # pred marginal
    pj = jnp.sum(p, axis=0)  # true marginal

    def _safe_xlogx(v):
        return jnp.where(v > 0, v * jnp.log(jnp.maximum(v, 1e-30)), 0.0)

    mi = jnp.sum(
        jnp.where(
            p > 0,
            p * (jnp.log(jnp.maximum(p, 1e-30)) - jnp.log(jnp.maximum(pi[:, None] * pj[None, :], 1e-30))),
            0.0,
        )
    )
    h_pred = -jnp.sum(_safe_xlogx(pi))
    h_true = -jnp.sum(_safe_xlogx(pj))
    return mi / jnp.maximum(jnp.sqrt(h_pred * h_true), 1e-30)


@functools.partial(jax.jit, static_argnames=("k",))
def rss_from_assignment_stats(
    sums: jax.Array, counts: jax.Array, sq_norm_x: jax.Array, k: int
) -> jax.Array:
    """RSS from already-reduced cluster stats (used by the distributed path)."""
    del k
    means = sums / jnp.maximum(counts, 1.0)[:, None]
    return sq_norm_x - jnp.sum(counts * jnp.sum(means * means, axis=1))
