"""BigKClustering for documents (paper §3, Fig. 1).

Pipeline (two full passes over the data + tiny K x K group phase):
  1. randomly select BigK centers from the dataset
  2. assign all docs to most-similar center (pass 1)     [MR job 1: map]
  3. build BigK micro-clusters                           [MR job 1: reduce]
  4. connection similarity s0 = mean of min_i
  5. joinToGroups: equivalence-relation components, adapt s until #groups == k
                                                         [MR job 2: single reducer]
  6. group centroids become the k final centers
  7. assign all docs to final centers (pass 2)           [MR job 3]

TPU adaptation of step 5 (DESIGN.md §2): the paper's sequential 'adapt s and
re-scan' loop becomes a BISECTION on s over min-label-propagation connected
components. #groups(s) is monotone non-decreasing in s, so bisection finds an
exact-k threshold whenever one exists; otherwise we take the smallest s with
#groups >= k and absorb the smallest surplus groups into their most similar
anchor group (single shot, deterministic).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.common import l2_normalize
from repro.core import metrics
from repro.core.connected_components import compact_labels, label_components, num_components
from repro.core.microcluster import MicroClusters, build_microclusters, pair_similarity
from repro.kernels import ops


class BKCResult(NamedTuple):
    centers: jax.Array  # (k, d)
    assignment: jax.Array  # (n,)
    best_sim: jax.Array  # (n,)
    rss: jax.Array
    objective: jax.Array
    group_of_mc: jax.Array  # (BigK,) final group id per micro-cluster
    threshold: jax.Array  # connection similarity actually used


def _adjacency(pair: jax.Array, escape: jax.Array, s: jax.Array, use_escape) -> jax.Array:
    """Equivalence relation at threshold s (paper's joinToGroups conditions)."""
    edge = jnp.logical_and(pair > 0.0, pair >= s)
    return jnp.where(use_escape, jnp.logical_or(edge, escape), edge)


@functools.partial(jax.jit, static_argnames=("k", "iters"))
def _bisect_threshold(
    pair: jax.Array, escape: jax.Array, k: int, use_escape, iters: int = 40
) -> tuple[jax.Array, jax.Array]:
    """Find s with #groups(s) == k if possible, else smallest s: #groups >= k.

    Returns (s, n_groups_at_s). Monotonicity: raising s removes edges, so
    #groups is non-decreasing in s.
    """
    lo = jnp.float32(0.0)  # all positive-sim edges on -> fewest groups
    hi = jnp.max(pair) + 1e-3  # no threshold edges -> most groups

    def groups_at(s):
        return num_components(label_components(_adjacency(pair, escape, s, use_escape)))

    def body(_, state):
        lo, hi = state
        mid = 0.5 * (lo + hi)
        g = groups_at(mid)
        # too few groups -> raise threshold; enough -> lower it to find boundary
        lo = jnp.where(g < k, mid, lo)
        hi = jnp.where(g < k, hi, mid)
        return lo, hi

    lo, hi = jax.lax.fori_loop(0, iters, body, (lo, hi))
    return hi, groups_at(hi)  # hi always satisfies #groups >= k (or is max s)


@functools.partial(jax.jit, static_argnames=("k",))
def join_to_groups(mc: MicroClusters, k: int) -> tuple[jax.Array, jax.Array]:
    """Paper Fig. 1 joinToGroups: group micro-clusters into exactly k groups.

    Returns (group_id per micro-cluster in [0, k), threshold used). Invalid
    (empty) micro-clusters get group k-1 (harmless: zero CF mass).
    """
    pair, escape = pair_similarity(mc)

    # Escape-clause edges are s-independent; if they over-connect the graph so
    # that even max-s yields < k groups, retry without them (then #groups can
    # reach BigK >= k).
    s_esc, g_esc = _bisect_threshold(pair, escape, k, jnp.bool_(True))
    use_escape = g_esc >= k
    s_val = jnp.where(use_escape, s_esc, 0.0)
    s_noesc, _ = _bisect_threshold(pair, escape, k, jnp.bool_(False))
    s = jnp.where(use_escape, s_val, s_noesc)

    labels = label_components(_adjacency(pair, escape, s, use_escape))
    dense = compact_labels(labels)  # [0, G)
    big_k = pair.shape[0]

    # Group mass and centroid directions (from CF1 sums).
    g_n = jax.ops.segment_sum(mc.n, dense, num_segments=big_k)
    g_cf1 = jax.ops.segment_sum(mc.cf1, dense, num_segments=big_k)
    g_dir = l2_normalize(g_cf1)

    # Keep the k heaviest groups as anchors; absorb the rest into the most
    # similar anchor by centroid cosine. If G == k this is the identity.
    order = jnp.argsort(-g_n)  # group ids sorted by size desc
    anchor_rank = jnp.full((big_k,), big_k, dtype=jnp.int32)
    anchor_rank = anchor_rank.at[order[:k]].set(jnp.arange(k, dtype=jnp.int32))
    is_anchor = anchor_rank < k

    sim_to_anchor = g_dir @ g_dir[order[:k]].T  # (G..., k)
    nearest_anchor = jnp.argmax(sim_to_anchor, axis=1).astype(jnp.int32)
    group_to_final = jnp.where(is_anchor, anchor_rank, nearest_anchor)

    final = group_to_final[dense]
    final = jnp.where(mc.valid, final, k - 1)
    return final, s


@functools.partial(
    jax.jit, static_argnames=("big_k", "k", "impl", "fused", "bounded")
)
def bkc_fit(
    x: jax.Array,
    init_centers: jax.Array,
    big_k: int,
    k: int,
    *,
    impl: str = "auto",
    fused: bool = True,
    bounded: bool = False,
) -> BKCResult:
    """Run BKC-for-documents given the BigK sampled center documents.

    bounded=True routes both data passes through the bound-pruned op with
    sentinel bounds (single passes carry nothing to prune with; the payoff is
    the two-level center index on the Pallas path, where BigK is large)."""
    mc, _, _ = build_microclusters(
        x, init_centers, big_k, impl=impl, fused=fused, bounded=bounded
    )
    centers, group, s = _group_centers(mc, k)

    # Step 7: final assignment pass (one K-Means-style iteration); the fused
    # path reuses the same single read of x for assignment AND the RSS stats.
    if bounded and fused:
        index = (
            ops.build_center_index(centers)
            if ops._resolve(impl) != "xla"
            else None
        )
        st = ops.assign_stats_bounded(
            x, centers, ops.bounds_identity(x.shape[0]),
            jnp.zeros((k,), jnp.float32), index=index, impl=impl,
        )
        idx, best_sim = st.idx, st.best_sim
        rss = metrics.rss_from_assignment_stats(
            st.sums, st.counts, jnp.sum(st.sumsq), k
        )
    elif fused:
        st = ops.assign_stats(x, centers, impl=impl)
        idx, best_sim = st.idx, st.best_sim
        rss = metrics.rss_from_assignment_stats(
            st.sums, st.counts, jnp.sum(st.sumsq), k
        )
    else:
        idx, best_sim = ops.assign_argmax(x, centers, impl=impl)
        rss = metrics.rss(x, idx, k)
    return BKCResult(
        centers=centers,
        assignment=idx,
        best_sim=best_sim,
        rss=rss,
        objective=metrics.cosine_objective(best_sim),
        group_of_mc=group,
        threshold=s,
    )


def bkc(
    x: jax.Array,
    big_k: int,
    k: int,
    key: jax.Array,
    *,
    impl: str = "auto",
    fused: bool = True,
    bounded: bool | None = None,
) -> BKCResult:
    """Convenience entry point: sample BigK center documents, then fit."""
    idx = jax.random.choice(key, x.shape[0], shape=(big_k,), replace=False)
    centers = l2_normalize(x[idx])
    return bkc_fit(
        x, centers, big_k, k, impl=impl, fused=fused,
        bounded=ops.bounds_enabled(bounded),
    )


# ------------------------------------------------------------------ streaming


@functools.partial(jax.jit, static_argnames=("k",))
def _group_centers(
    mc: MicroClusters, k: int
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """joinToGroups + step 6 on the replicated (BigK)-sized state."""
    group, s = join_to_groups(mc, k)
    sums = jax.ops.segment_sum(mc.cf1, group, num_segments=k)
    counts = jax.ops.segment_sum(mc.n, group, num_segments=k)
    centers = jnp.where(counts[:, None] > 0, l2_normalize(sums), 0.0)
    return centers, group, s


def bkc_fit_stream(
    stream,
    init_centers: jax.Array,
    big_k: int,
    k: int,
    *,
    impl: str = "auto",
    checkpoint=None,
    guard=None,
    bounded: bool | None = None,
) -> BKCResult:
    """Out-of-core BKC: passes 1 and 3 stream chunks through the fused kernel
    with carried accumulators (the shared executor prefetches chunk i+1 while
    chunk i folds — text/stream.run_pass); the K×K group phase runs on the
    replicated O(BigK·d) micro-cluster statistics as before. Peak residency
    is O(chunk·d + BigK·d) for any collection size.

    ``checkpoint``/``guard`` thread down to both data passes (pass ids
    ``bkc/mc`` and ``bkc/final``); pass-1's micro-cluster stats are stored as
    a pass result so a restart killed in pass 3 skips pass 1 entirely.
    ``bounded`` (None → REPRO_ASSIGN_BOUNDS) routes both passes through the
    bound-pruned op with sentinel bounds.
    """
    from repro.core.kmeans import _stream_pass

    bounded = ops.bounds_enabled(bounded)
    use_index = bounded and ops._resolve(impl) != "xla"

    # pass 1: micro-cluster statistics folded over the stream (CF additivity
    # is the chunk monoid — the same merge_stats the distributed combiner uses)
    mc_stats = None
    if checkpoint is not None:
        from repro.resilience import array_token

        mc_meta = {"centers": array_token(init_centers)}
        mc_stats = checkpoint.load_result("bkc/mc", meta=mc_meta)
    if mc_stats is not None:
        sums, counts, min_sim, sumsq = mc_stats
    else:
        index = (
            ops.build_center_index(jnp.asarray(init_centers))
            if use_index else None
        )
        out = _stream_pass(
            stream, init_centers, big_k, impl,
            pass_id="bkc/mc", checkpoint=checkpoint, guard=guard,
            bounded=bounded, index=index,
        )
        sums, counts, min_sim, sumsq = out.stats
        if checkpoint is not None:
            checkpoint.save_result(
                "bkc/mc", (sums, counts, min_sim, sumsq), meta=mc_meta
            )
    valid = counts > 0
    mc = MicroClusters(
        n=counts,
        cf1=sums,
        cf2=sumsq,
        centers=init_centers,
        min_sim=jnp.where(valid, min_sim, 1.0),
        valid=valid,
    )
    centers, group, s = _group_centers(mc, k)

    # pass 3: final assignment — same streaming pass against the k centers
    index = ops.build_center_index(centers) if use_index else None
    out = _stream_pass(
        stream, centers, k, impl, collect=True,
        pass_id="bkc/final", checkpoint=checkpoint, guard=guard,
        bounded=bounded, index=index,
    )
    sums, counts, _, sumsq = out.stats
    idx, best_sim, obj = out.idx, out.best_sim, out.objective
    if checkpoint is not None:
        checkpoint.delete_result("bkc/mc")  # the run is over
    rss = metrics.rss_from_assignment_stats(sums, counts, jnp.sum(sumsq), k)
    return BKCResult(
        centers=centers,
        assignment=idx,
        best_sim=best_sim,
        rss=rss,
        objective=obj,
        group_of_mc=group,
        threshold=s,
    )


def bkc_stream(
    stream,
    big_k: int,
    k: int,
    key: jax.Array,
    *,
    impl: str = "auto",
    checkpoint=None,
    guard=None,
    bounded: bool | None = None,
) -> BKCResult:
    """Streaming convenience entry: the BigK random center documents come
    from the one-pass reservoir (exact uniform sample), then the fit."""
    from repro.core.sampling import reservoir_sample_stream

    rows, _ = reservoir_sample_stream(
        stream, big_k, key, checkpoint=checkpoint, guard=guard
    )
    result = bkc_fit_stream(
        stream, l2_normalize(rows), big_k, k, impl=impl,
        checkpoint=checkpoint, guard=guard, bounded=bounded,
    )
    if checkpoint is not None:
        checkpoint.delete_result("reservoir")  # the run is over
    return result
