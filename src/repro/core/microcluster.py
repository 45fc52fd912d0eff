"""Micro-clusters for BKC-for-documents (paper §3.1).

A micro-cluster is the (2d+3)-vector (n_i, CF1_i, CF2_i, Center_i, min_i):
  n_i    — member count
  CF1_i  — linear sum of member vectors (CF vector LS)
  CF2_i  — sum of squared norms of members (CF vector SS)
  Center_i — the ORIGINAL randomly selected document serving as center
  min_i  — the lowest cosine similarity observed between a member and Center_i
           during the assignment pass ('longest distance' -> 'lowest similarity')

Stored struct-of-arrays so everything is one psum-able pytree.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.common import segment_min
from repro.kernels import ops


class MicroClusters(NamedTuple):
    n: jax.Array  # (K,) f32 member counts
    cf1: jax.Array  # (K, d) f32 linear sums
    cf2: jax.Array  # (K,) f32 sum of squared norms
    centers: jax.Array  # (K, d) original sampled center documents (unit norm)
    min_sim: jax.Array  # (K,) f32 lowest member->center cosine similarity
    valid: jax.Array  # (K,) bool, False for empty micro-clusters


@functools.partial(jax.jit, static_argnames=("big_k", "impl", "fused", "bounded"))
def build_microclusters(
    x: jax.Array,
    centers: jax.Array,
    big_k: int,
    *,
    impl: str = "auto",
    fused: bool = True,
    bounded: bool = False,
) -> tuple[MicroClusters, jax.Array, jax.Array]:
    """BKC steps 2-3: assign every doc to its most similar center, build MCs.

    fused=True gets assignment + CF1 + counts + CF2 + min_sim from ONE
    assign_stats pass (no separate label_stats / segment_sum / segment_min
    passes over x); fused=False keeps the legacy multi-pass path for
    benchmarks. bounded=True routes the single pass through the bound-pruned
    op (sentinel bounds — no carry to prune with, but the Pallas path gets
    the two-level center index, which is where BigK ≫ k pays).

    Returns (micro_clusters, idx, best_sim).
    """
    if bounded and fused:
        index = (
            ops.build_center_index(centers)
            if ops._resolve(impl) != "xla"
            else None
        )
        st = ops.assign_stats_bounded(
            x, centers, ops.bounds_identity(x.shape[0]),
            jnp.zeros((big_k,), jnp.float32), index=index, impl=impl,
        )
        idx, best_sim = st.idx, st.best_sim
        sums, counts, cf2, min_sim = st.sums, st.counts, st.sumsq, st.min_sim
    elif fused:
        st = ops.assign_stats(x, centers, impl=impl)
        idx, best_sim = st.idx, st.best_sim
        sums, counts, cf2, min_sim = st.sums, st.counts, st.sumsq, st.min_sim
    else:
        idx, best_sim = ops.assign_argmax(x, centers, impl=impl)
        sums, counts = ops.label_stats(x, idx, big_k, impl=impl)
        sq = jnp.sum(x.astype(jnp.float32) ** 2, axis=1)
        cf2 = jax.ops.segment_sum(sq, idx, num_segments=big_k)
        min_sim = segment_min(best_sim, idx, big_k)
    valid = counts > 0
    min_sim = jnp.where(valid, min_sim, 1.0)  # empty MC: neutral
    return (
        MicroClusters(
            n=counts, cf1=sums, cf2=cf2, centers=centers, min_sim=min_sim, valid=valid
        ),
        idx,
        best_sim,
    )


def merge_stats(a: MicroClusters, b: MicroClusters) -> MicroClusters:
    """CF additivity (used by the distributed combiner): elementwise merge of
    partial micro-cluster statistics computed on different shards."""
    return MicroClusters(
        n=a.n + b.n,
        cf1=a.cf1 + b.cf1,
        cf2=a.cf2 + b.cf2,
        centers=a.centers,  # centers are replicated, not partial
        min_sim=jnp.minimum(a.min_sim, b.min_sim),
        valid=jnp.logical_or(a.valid, b.valid),
    )


@jax.jit
def pair_similarity(mc: MicroClusters) -> tuple[jax.Array, jax.Array]:
    """Paper §3.1: sim(Si,Sj) = cos(Center_i, Center_j) - min_i - min_j,
    clamped at 0; plus the escape-clause mask
    (sim == 0) & (cos >= min(min_i, min_j)).

    Returns (pair_sim (K,K), escape (K,K) bool). Diagonal excluded; invalid
    (empty) micro-clusters are isolated.
    """
    cos = mc.centers @ mc.centers.T  # centers are unit-norm documents
    pair = cos - mc.min_sim[:, None] - mc.min_sim[None, :]
    pair = jnp.maximum(pair, 0.0)
    escape = jnp.logical_and(
        pair == 0.0, cos >= jnp.minimum(mc.min_sim[:, None], mc.min_sim[None, :])
    )
    k = pair.shape[0]
    eye = jnp.eye(k, dtype=bool)
    both_valid = jnp.logical_and(mc.valid[:, None], mc.valid[None, :])
    keep = jnp.logical_and(~eye, both_valid)
    return jnp.where(keep, pair, 0.0), jnp.logical_and(escape, keep)
