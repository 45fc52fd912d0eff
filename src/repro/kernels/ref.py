"""Pure-jnp oracles for every Pallas kernel in this package.

Each function here is the semantic ground truth. Kernel implementations in
``assign_argmax.py`` / ``assign_stats.py`` / ``best_edge.py`` /
``sim_best_edge.py`` / ``component_reduce.py`` are validated against
these in interpret mode across shape/dtype sweeps (tests/test_kernels.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# Sentinel for "no member seen": min-reducible across shards (jax.lax.pmin)
# and convertible by callers (microclusters map empty -> 1.0). finfo.max, not
# inf, so arithmetic on unconsumed lanes stays finite.
BIG = float(jnp.finfo(jnp.float32).max)

# Sentinel for "no row seen" in segmented argmin folds: min-reducible across
# shards (jax.lax.pmin) the way BIG is for similarities.
BIG_I = int(jnp.iinfo(jnp.int32).max)

# Safety margin for bound-based pruning (assign_stats_bounded): a row skips
# the center sweep only when its deflated lower bound BEATS its deflated upper
# bound by more than this. Real-arithmetic Elkan/Hamerly pruning is exact; the
# margin absorbs f32 rounding of the dots and the drift norms (worst case
# ~d·ulp ≈ 1e-4 relative at d=2048) so pruned labels stay bit-identical to
# the brute-force argmax, ties included (an exact tie has lo == hi, which the
# strict margin never prunes).
PRUNE_MARGIN = 1e-4

# Contraction precision of every f32 dot on the assignment, statistics and
# edge paths, in the Pallas kernels and in these oracles alike. A TPU runs an
# f32 dot at DEFAULT precision as one bf16 pass (~3 significant digits);
# HIGHEST keeps it f32, so the kernel, the XLA path and a float64 reference
# agree on the chip as they do on the CPU, where it changes nothing.
PRECISION = jax.lax.Precision.HIGHEST


def dot_precision(dtype) -> jax.lax.Precision | None:
    """``PRECISION`` for f32 operands. A bf16 product is exact in the f32
    accumulator at DEFAULT, and Mosaic accepts HIGHEST for f32 only."""
    return PRECISION if jnp.dtype(dtype) == jnp.float32 else None


def assign_argmax(x: jax.Array, centers: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Nearest-center assignment by dot-product similarity.

    Args:
      x: (n, d) document vectors (caller normalizes for cosine semantics).
      centers: (k, d) center vectors.

    Returns:
      best_idx: (n,) int32 argmax_k <x, c_k>   (ties -> lowest index)
      best_sim: (n,) f32    max_k <x, c_k>
    """
    sims = jnp.einsum(
        "nd,kd->nk", x, centers,
        preferred_element_type=jnp.float32, precision=PRECISION,
    )
    best_idx = jnp.argmax(sims, axis=1).astype(jnp.int32)
    best_sim = jnp.max(sims, axis=1).astype(jnp.float32)
    return best_idx, best_sim


def cluster_stats(
    x: jax.Array, idx: jax.Array, k: int
) -> tuple[jax.Array, jax.Array]:
    """Combiner: per-cluster sums and counts (the MapReduce 'combine' step).

    Historical oracle: the dedicated cluster_stats kernel is retired (the
    weighted, d-tiled ``label_stats`` subsumes it); this one-hot formulation
    survives as the ground truth label_stats is validated against.

    Args:
      x: (n, d) document vectors.
      idx: (n,) int32 cluster assignment in [0, k).
      k: number of clusters.

    Returns:
      sums: (k, d) f32 per-cluster vector sums.
      counts: (k,) f32 per-cluster document counts.
    """
    one_hot = jax.nn.one_hot(idx, k, dtype=jnp.float32)  # (n, k)
    sums = jnp.einsum(
        "nk,nd->kd", one_hot, x,
        preferred_element_type=jnp.float32, precision=PRECISION,
    )
    counts = jnp.sum(one_hot, axis=0)
    return sums, counts


def assign_stats(
    x: jax.Array, centers: jax.Array, w: jax.Array | None = None
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """Fused map+combine oracle: assignment AND cluster statistics, one pass.

    Semantic ground truth for the fused Pallas kernel (assign_stats.py): the
    paper's map step (nearest center) and combiner (local aggregation before
    the shuffle) as a single logical pass over the documents.

    Args:
      x: (n, d) document vectors.
      centers: (k, d) center vectors.
      w: optional (n,) row weights (0.0 rows are padding: excluded from every
        statistic; counts accumulate w).

    Returns:
      idx:      (n,) int32 argmax_k <x, c_k>  (ties -> lowest index)
      best_sim: (n,) f32    max_k <x, c_k>
      sums:     (k, d) f32  per-cluster weighted vector sums
      counts:   (k,) f32    per-cluster weight totals
      min_sim:  (k,) f32    lowest member best_sim per cluster (BIG if empty)
      sumsq:    (k,) f32    per-cluster weighted sum of squared row norms
    """
    k = centers.shape[0]
    idx, best_sim = assign_argmax(x, centers)
    one_hot = jax.nn.one_hot(idx, k, dtype=jnp.float32)  # (n, k)
    if w is not None:
        one_hot = one_hot * w.astype(jnp.float32)[:, None]
    sums = jnp.einsum(
        "nk,nd->kd", one_hot, x,
        preferred_element_type=jnp.float32, precision=PRECISION,
    )
    counts = jnp.sum(one_hot, axis=0)
    rowsq = jnp.sum(x.astype(jnp.float32) ** 2, axis=1)  # (n,)
    sumsq = jnp.einsum("nk,n->k", one_hot, rowsq, precision=PRECISION)
    member = jnp.where(one_hot > 0, best_sim[:, None], BIG)  # (n, k)
    min_sim = jnp.min(member, axis=0) if x.shape[0] else jnp.full((k,), BIG)
    min_sim = jnp.where(counts > 0, min_sim, BIG)
    return idx, best_sim, sums, counts, min_sim, sumsq


def assign_stats_scatter(
    x: jax.Array, centers: jax.Array, w: jax.Array | None = None
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """Production XLA path for the fused op: combiner via scatter-add.

    Same contract as ``assign_stats`` (the oracle), but the statistics use
    segment reductions — O(n*d) adds instead of the oracle's O(n*k*d) one-hot
    matmul, which halves the flops of a fused K-Means iteration on backends
    without an MXU. Results match the oracle up to f32 summation order.
    """
    k = centers.shape[0]
    idx, best_sim = assign_argmax(x, centers)
    xf = x.astype(jnp.float32)
    # einsum, not sum(x*x): XLA CPU lowers the contraction ~3x faster
    rowsq = jnp.einsum("nd,nd->n", xf, xf, precision=PRECISION)
    if w is not None:
        wf = w.astype(jnp.float32)
        xf = xf * wf[:, None]
        rowsq = rowsq * wf
        counts = jax.ops.segment_sum(wf, idx, num_segments=k)
        sim_m = jnp.where(wf > 0, best_sim, BIG)
    else:
        counts = jax.ops.segment_sum(
            jnp.ones_like(best_sim), idx, num_segments=k
        )
        sim_m = best_sim
    sums = jax.ops.segment_sum(xf, idx, num_segments=k)
    sumsq = jax.ops.segment_sum(rowsq, idx, num_segments=k)
    min_sim = jax.ops.segment_min(sim_m, idx, num_segments=k)
    min_sim = jnp.where(counts > 0, min_sim, BIG)
    return idx, best_sim, sums, counts, min_sim, sumsq


def deflate_bounds(
    prev_idx: jax.Array,
    lo: jax.Array,
    hi: jax.Array,
    rownorm: jax.Array,
    drift: jax.Array,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Deflate carried similarity bounds by per-center drift (Cauchy-Schwarz).

    Bounds semantics (cosine/max-dot assignment — the mirror image of the
    classical distance-space Elkan bounds):
      lo: lower bound on sim(x, c_{prev_idx}) under the CURRENT centers.
      hi: upper bound on max_{j != prev_idx} sim(x, c_j).
    Both were exact similarities under the centers of the pass that produced
    them; |sim(x, c') - sim(x, c)| <= ‖x‖·‖c' - c‖ deflates them to the
    current centers:
      lo' = lo - ‖x‖·drift[prev_idx]
      hi' = hi + ‖x‖·max_{j != prev_idx} drift[j]

    Args:
      prev_idx: (n,) int32 prior assignment; negative/oob = unknown sentinel.
      lo, hi: (n,) f32 carried bounds (sentinel rows carry -BIG / +BIG).
      rownorm: (n,) f32 row L2 norms.
      drift: (k,) f32 per-center movement ‖c_new - c_old‖.

    Returns:
      ok: (n,) bool — prev_idx is a real assignment.
      pidx: (n,) int32 prev_idx clipped into [0, k).
      lo_adj, hi_adj: (n,) f32 deflated bounds (garbage where ~ok).
    """
    k = drift.shape[0]
    ok = jnp.logical_and(prev_idx >= 0, prev_idx < k)
    pidx = jnp.clip(prev_idx, 0, k - 1).astype(jnp.int32)
    argd = jnp.argmax(drift)
    maxd = jnp.max(drift)
    # largest drift among centers OTHER than the row's own (top-2 trick)
    sec = jnp.maximum(
        jnp.max(jnp.where(jnp.arange(k) == argd, -1.0, drift)), 0.0
    )
    d_other = jnp.where(pidx == argd, sec, maxd)
    lo_adj = lo - rownorm * drift[pidx]
    hi_adj = hi + rownorm * d_other
    return ok, pidx, lo_adj, hi_adj


def _bounded_assign(
    x: jax.Array,
    centers: jax.Array,
    prev_idx: jax.Array,
    lo: jax.Array,
    hi: jax.Array,
    drift: jax.Array,
    margin: float,
):
    """Shared assignment half of the bounded oracle/scatter paths.

    Returns (idx, best_sim, lo_out, hi_out, pruned, rowsq) — the full (n, k)
    sweep IS computed (XLA's static shapes leave no data-dependent savings;
    real compute skipping lives in the Pallas path), but pruned rows take
    their carried index so pruning bugs surface in label-parity tests.
    """
    k = centers.shape[0]
    neg = jnp.finfo(jnp.float32).min
    xf = x.astype(jnp.float32)
    rowsq = jnp.einsum("nd,nd->n", xf, xf, precision=PRECISION)
    rownorm = jnp.sqrt(rowsq)
    ok, pidx, lo_adj, hi_adj = deflate_bounds(prev_idx, lo, hi, rownorm, drift)
    pruned = jnp.logical_and(ok, lo_adj > hi_adj + margin)

    sims = jnp.einsum(
        "nd,kd->nk", x, centers,
        preferred_element_type=jnp.float32, precision=PRECISION,
    )
    brute_idx = jnp.argmax(sims, axis=1).astype(jnp.int32)
    brute_best = jnp.max(sims, axis=1).astype(jnp.float32)
    # second-best VALUE (duplicates count separately): mask one instance of
    # the winner column, take the max of the rest
    masked = jnp.where(
        jnp.arange(k)[None, :] == brute_idx[:, None], neg, sims
    )
    second = jnp.max(masked, axis=1).astype(jnp.float32)

    idx = jnp.where(pruned, pidx, brute_idx)
    best_sim = jnp.where(
        pruned, jnp.take_along_axis(sims, pidx[:, None], axis=1)[:, 0],
        brute_best,
    )
    # refreshed bounds, valid against THESE centers: lo is the exact winner
    # similarity; hi is the exact second-best where the sweep ran, and the
    # deflated carry (still a valid upper bound) where it was pruned.
    lo_out = best_sim
    hi_out = jnp.where(pruned, hi_adj, second)
    return idx, best_sim, lo_out, hi_out, pruned, rowsq


def assign_stats_bounded(
    x: jax.Array,
    centers: jax.Array,
    prev_idx: jax.Array,
    lo: jax.Array,
    hi: jax.Array,
    drift: jax.Array,
    w: jax.Array | None = None,
    *,
    margin: float = PRUNE_MARGIN,
):
    """Bound-pruned fused oracle: ``assign_stats`` + Elkan/Hamerly carry.

    Semantic ground truth for ``assign_stats_bounded_pallas``. Labels are
    bit-identical to ``assign_stats`` on every row: pruning only fires when
    the deflated bounds PROVE the winner unchanged (see ``deflate_bounds``;
    the margin covers f32 rounding), so the bounds state is a pure
    performance hint — stats and labels never depend on it.

    Args (beyond ``assign_stats``):
      prev_idx, lo, hi: (n,) carried bounds from the previous pass against
        the previous centers (-1 / -BIG / +BIG = unknown sentinel).
      drift: (k,) f32 per-center movement since that pass.
      margin: f32 safety margin; rows prune only when lo' > hi' + margin.

    Returns:
      (idx, best_sim, sums, counts, min_sim, sumsq, idx, lo_out, hi_out,
       pruned) — the first six exactly as ``assign_stats``; the refreshed
      bounds (idx, lo_out, hi_out) are valid against ``centers``; pruned is
      the (n,) bool row mask that skipped the sweep.
    """
    k = centers.shape[0]
    idx, best_sim, lo_out, hi_out, pruned, rowsq = _bounded_assign(
        x, centers, prev_idx, lo, hi, drift, margin
    )
    one_hot = jax.nn.one_hot(idx, k, dtype=jnp.float32)  # (n, k)
    if w is not None:
        one_hot = one_hot * w.astype(jnp.float32)[:, None]
    sums = jnp.einsum(
        "nk,nd->kd", one_hot, x,
        preferred_element_type=jnp.float32, precision=PRECISION,
    )
    counts = jnp.sum(one_hot, axis=0)
    sumsq = jnp.einsum("nk,n->k", one_hot, rowsq, precision=PRECISION)
    member = jnp.where(one_hot > 0, best_sim[:, None], BIG)  # (n, k)
    min_sim = jnp.min(member, axis=0) if x.shape[0] else jnp.full((k,), BIG)
    min_sim = jnp.where(counts > 0, min_sim, BIG)
    return idx, best_sim, sums, counts, min_sim, sumsq, idx, lo_out, hi_out, pruned


def assign_stats_bounded_scatter(
    x: jax.Array,
    centers: jax.Array,
    prev_idx: jax.Array,
    lo: jax.Array,
    hi: jax.Array,
    drift: jax.Array,
    w: jax.Array | None = None,
    *,
    margin: float = PRUNE_MARGIN,
):
    """Production XLA path for the bounded fused op: stats via scatter-add.

    Same contract as ``assign_stats_bounded`` (labels and bounds identical
    bit-for-bit — both use ``_bounded_assign``); the statistics use segment
    reductions like ``assign_stats_scatter``. XLA cannot skip compute for
    pruned rows (static shapes), so this path pays O(n·k + n·d) bookkeeping
    on top of the brute sweep — the pruning payoff is Pallas-only.
    """
    k = centers.shape[0]
    idx, best_sim, lo_out, hi_out, pruned, rowsq = _bounded_assign(
        x, centers, prev_idx, lo, hi, drift, margin
    )
    xf = x.astype(jnp.float32)
    if w is not None:
        wf = w.astype(jnp.float32)
        xf = xf * wf[:, None]
        rsq = rowsq * wf
        counts = jax.ops.segment_sum(wf, idx, num_segments=k)
        sim_m = jnp.where(wf > 0, best_sim, BIG)
    else:
        counts = jax.ops.segment_sum(
            jnp.ones_like(best_sim), idx, num_segments=k
        )
        rsq = rowsq
        sim_m = best_sim
    sums = jax.ops.segment_sum(xf, idx, num_segments=k)
    sumsq = jax.ops.segment_sum(rsq, idx, num_segments=k)
    min_sim = jax.ops.segment_min(sim_m, idx, num_segments=k)
    min_sim = jnp.where(counts > 0, min_sim, BIG)
    return idx, best_sim, sums, counts, min_sim, sumsq, idx, lo_out, hi_out, pruned


def label_stats(
    x: jax.Array, idx: jax.Array, k: int, w: jax.Array | None = None
) -> tuple[jax.Array, jax.Array]:
    """Weighted combiner oracle: per-label sums and weight totals.

    The labels-are-given sibling of ``assign_stats`` (HAC hands Buckshot
    phase 1 its labels directly, so there is no argmax to fuse with — only the
    accumulator machinery). Out-of-range labels (e.g. -1 padding) fall into no
    bin; weight-0 rows contribute nothing.

    Args:
      x: (n, d) document vectors.
      idx: (n,) int32 labels; rows with idx outside [0, k) are dropped.
      k: number of bins.
      w: optional (n,) row weights.

    Returns:
      sums: (k, d) f32 per-label weighted vector sums.
      counts: (k,) f32 per-label weight totals.
    """
    one_hot = jax.nn.one_hot(idx, k, dtype=jnp.float32)  # (n, k); oob -> 0 row
    if w is not None:
        one_hot = one_hot * w.astype(jnp.float32)[:, None]
    sums = jnp.einsum(
        "nk,nd->kd", one_hot, x,
        preferred_element_type=jnp.float32, precision=PRECISION,
    )
    counts = jnp.sum(one_hot, axis=0)
    return sums, counts


def label_stats_scatter(
    x: jax.Array, idx: jax.Array, k: int, w: jax.Array | None = None
) -> tuple[jax.Array, jax.Array]:
    """Production XLA path for label_stats: segment reductions, O(n*d) adds.

    Same contract as ``label_stats``; out-of-range labels are dropped by the
    segment ops. Matches the oracle up to f32 summation order.
    """
    xf = x.astype(jnp.float32)
    if w is not None:
        wf = w.astype(jnp.float32)
        xf = xf * wf[:, None]
    else:
        wf = jnp.ones((x.shape[0],), jnp.float32)
    sums = jax.ops.segment_sum(xf, idx, num_segments=k)
    counts = jax.ops.segment_sum(wf, idx, num_segments=k)
    return sums, counts


def best_edge(
    sim: jax.Array, labels_row: jax.Array, labels_col: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """Single-link/Boruvka step: per-row best cross-component edge.

    Args:
      sim: (r, c) similarity block; rows are this shard's points.
      labels_row: (r,) component label of each row point. NEGATIVE row labels
        mark padding: those rows propose nothing (-1, f32.min) — they are
        masked out of the map itself, not sliced off after a gather.
      labels_col: (c,) component label of each column point. NEGATIVE column
        labels mark padding too: the sharded ring sweep visits PADDED row
        blocks as its column set, and a zero pad column (sim 0.0) must never
        outscore a real cross edge whose similarity is negative.

    Returns:
      best_j: (r,) int32 column index of the most similar point in a DIFFERENT
        component (ties -> lowest index; -1 if none).
      best_s: (r,) f32 similarity of that edge (-inf if none).
    """
    neg = jnp.finfo(jnp.float32).min
    cross = jnp.logical_and(
        jnp.logical_and(
            labels_row[:, None] != labels_col[None, :],
            labels_row[:, None] >= 0,
        ),
        labels_col[None, :] >= 0,
    )
    masked = jnp.where(cross, sim.astype(jnp.float32), neg)
    best_j = jnp.argmax(masked, axis=1).astype(jnp.int32)
    best_s = jnp.max(masked, axis=1)
    best_j = jnp.where(best_s == neg, -1, best_j)
    return best_j, best_s


def sim_best_edge(
    xs_rows: jax.Array,
    xs_all: jax.Array,
    labels_row: jax.Array,
    labels_col: jax.Array,
) -> tuple[jax.Array, jax.Array]:
    """Matrix-free best-edge oracle: similarity build + masked row-max fused.

    Semantically ``best_edge(xs_rows @ xs_all.T, ...)`` — the oracle DOES
    materialize the (r, c) similarity block (it is the ground truth at test
    sizes); the Pallas kernel and the chunked XLA path compute the same thing
    without ever holding more than one tile / row block of it.

    Args:
      xs_rows: (r, d) row vectors (callers pass unit-norm rows for cosine).
      xs_all: (c, d) column vectors.
      labels_row: (r,) component label of each row point.
      labels_col: (c,) component label of each column point.

    Returns:
      best_j: (r,) int32 most similar column in a DIFFERENT component
        (ties -> lowest index; -1 if none).
      best_s: (r,) f32 similarity of that edge (f32.min if none).
    """
    sim = jax.lax.dot_general(
        xs_rows,
        xs_all,
        (((1,), (1,)), ((), ())),  # contract on d — same form as the kernel
        preferred_element_type=jnp.float32, precision=PRECISION,
    )
    return best_edge(sim, labels_row, labels_col)


def component_best_edge(
    row_w: jax.Array,
    row_j: jax.Array,
    rows: jax.Array,
    comp: jax.Array,
    c: int,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Segmented pre-reduce: per-COMPONENT lexicographic best candidate.

    The combiner between the per-row Borůvka edge search and the shuffle:
    of each component's rows, keep only the winning candidate — ordered by
    (weight desc, row asc); the column needs no tie-break because each row
    already carries its unique best column. Only O(#components) values
    survive the merge, so only O(#components) should cross shards.

    Args:
      row_w: (r,) f32 best cross-component weight per row (f32.min if none).
      row_j: (r,) int32 best column per row (-1 if none).
      rows: (r,) int32 GLOBAL row id of each local row.
      comp: (r,) int32 dense component id in [0, c); out-of-range ids (e.g.
        pad rows tagged c) fall into no segment.
      c: number of component segments (static).

    Returns:
      best_w: (c,) f32 winning weight (f32.min if the segment is empty).
      best_row: (c,) int32 winning global row id (BIG_I if empty).
      best_j: (c,) int32 winning column (-1 if empty or the winner has none).
    """
    order = jnp.lexsort((rows, -row_w, comp))  # comp asc, w desc, row asc
    comp_s = comp[order]
    first = jnp.concatenate(
        [jnp.ones((1,), bool), comp_s[1:] != comp_s[:-1]]
    )
    in_range = jnp.logical_and(comp_s >= 0, comp_s < c)
    slot = jnp.where(jnp.logical_and(first, in_range), comp_s, c)
    neg = jnp.finfo(jnp.float32).min
    best_w = jnp.full((c,), neg, jnp.float32).at[slot].set(
        row_w[order].astype(jnp.float32), mode="drop"
    )
    best_row = jnp.full((c,), BIG_I, jnp.int32).at[slot].set(
        rows[order].astype(jnp.int32), mode="drop"
    )
    best_j = jnp.full((c,), -1, jnp.int32).at[slot].set(
        row_j[order].astype(jnp.int32), mode="drop"
    )
    return best_w, best_row, best_j
