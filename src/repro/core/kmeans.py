"""Spherical K-Means over the MapReduce pattern (PKMeans, Zhao et al. [26]).

One iteration == one MapReduce job == ONE fused pass over the documents:
  map+combine -> nearest center + per-shard cluster stats (ops.assign_stats,
                 a single kernel: x is read from HBM once per iteration)
  reduce      -> global new centers                (psum in the distributed path)

``fused=False`` keeps the legacy two-pass path (assign_argmax then
label_stats) for benchmarking the fusion win; production paths default to
fused.

This module is the single-device reference; distrib/engine.py lifts the exact
same step onto the mesh. Documents are expected L2-normalized (cosine semantics,
paper §3.1); centers are renormalized after every update (spherical K-Means).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.common import l2_normalize
from repro.core import metrics
from repro.kernels import ops


class KMeansResult(NamedTuple):
    centers: jax.Array  # (k, d) unit-norm centers used for assignment
    assignment: jax.Array  # (n,) int32
    best_sim: jax.Array  # (n,) f32 cos(doc, center)
    rss: jax.Array  # scalar Euclidean RSS vs member means
    objective: jax.Array  # scalar cosine objective
    iterations: jax.Array  # int32 iterations actually run


def init_random_centers(key: jax.Array, x: jax.Array, k: int) -> jax.Array:
    """Paper's init: k documents drawn at random from the collection."""
    idx = jax.random.choice(key, x.shape[0], shape=(k,), replace=False)
    return l2_normalize(x[idx])


@jax.jit
def _split_empty_centers_info(
    centers: jax.Array,
    sums: jax.Array,
    counts: jax.Array,
    sumsq: jax.Array,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Reseed empty clusters by splitting the highest-RSS cluster.

    Without this, ``counts == 0`` keeps the stale center forever (the
    ``jnp.where`` in the update): the cluster can only recover if a document
    happens to drift back. The reseed policy points every empty center at the
    worst-fit region instead: the donor is the non-empty cluster with the
    largest RSS contribution (sumsq_c - |sums_c|^2 / n_c, from the stats the
    fused kernel already carries), and empty center j becomes the donor's
    center nudged along basis vector j mod d — deterministic, and distinct
    per empty slot so the split centers immediately partition the donor's
    members. No-op when no cluster is empty.

    Returns (new_centers, donor id, (k,) bool reseeded-slot mask) — the extra
    outputs drive the bounded path's carry invalidation."""
    k, d = centers.shape
    rss_c = sumsq - jnp.sum(sums * sums, axis=1) / jnp.maximum(counts, 1.0)
    donor = jnp.argmax(jnp.where(counts > 0, rss_c, -jnp.inf))
    nudge = 1e-3 * jax.nn.one_hot(jnp.arange(k) % d, d, dtype=centers.dtype)
    split = l2_normalize(centers[donor][None, :] + nudge)
    reseeded = counts <= 0
    return jnp.where(reseeded[:, None], split, centers), donor, reseeded


def _split_empty_centers(
    centers: jax.Array,
    sums: jax.Array,
    counts: jax.Array,
    sumsq: jax.Array,
) -> jax.Array:
    return _split_empty_centers_info(centers, sums, counts, sumsq)[0]


@functools.partial(jax.jit, static_argnames=("k", "impl", "fused", "reseed"))
def kmeans_step(
    x: jax.Array,
    centers: jax.Array,
    k: int,
    *,
    impl: str = "auto",
    fused: bool = True,
    reseed: str | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """One full map/combine/reduce iteration on one device.

    fused=True issues exactly ONE assign+stats kernel call (one HBM read of
    x); fused=False is the legacy two-pass path, kept for benchmarks.

    reseed="split" recovers empty clusters by splitting the highest-RSS
    cluster (``_split_empty_centers``); the default (None) keeps the stale
    center — the seed behavior, preserved for parity with the paper runs.
    Requires the fused path (the donor choice needs the carried sumsq).

    Returns (new_centers, idx, best_sim, sums, counts).
    """
    if reseed not in (None, "split"):
        raise ValueError(f"unknown reseed policy {reseed!r}: expected 'split'")
    if reseed and not fused:
        raise ValueError("reseed='split' needs fused=True (donor uses sumsq)")
    if fused:
        st = ops.assign_stats(x, centers, impl=impl)
        idx, best_sim, sums, counts = st.idx, st.best_sim, st.sums, st.counts
    else:
        idx, best_sim = ops.assign_argmax(x, centers, impl=impl)
        sums, counts = ops.label_stats(x, idx, k, impl=impl)
    means = sums / jnp.maximum(counts, 1.0)[:, None]
    new_centers = jnp.where(counts[:, None] > 0, l2_normalize(means), centers)
    if reseed == "split":
        new_centers = _split_empty_centers(new_centers, sums, counts, st.sumsq)
    return new_centers, idx, best_sim, sums, counts


@functools.partial(jax.jit, static_argnames=("k", "impl", "reseed"))
def kmeans_step_bounded(
    x: jax.Array,
    centers: jax.Array,
    prev_centers: jax.Array,
    bounds: "ops.Bounds",
    k: int,
    *,
    impl: str = "auto",
    reseed: str | None = None,
    index: "ops.CenterIndex | None" = None,
) -> tuple[jax.Array, "ops.AssignStatsBounded"]:
    """Bound-pruned sibling of ``kmeans_step``: one fused iteration that
    deflates the carried per-row bounds by the per-center drift
    ``‖centers - prev_centers‖`` and lets provably-settled rows skip the
    center sweep (ops.assign_stats_bounded). Labels, stats, and therefore the
    new centers are bit-identical to the brute-force step for ANY carried
    bounds state.

    reseed="split" additionally forces the refreshed bounds of every row
    assigned to the DONOR or to a RESEEDED slot back to the unknown sentinel:
    those rows' carried similarities reference centers the reseed just
    rewrote, and the sentinel is deterministic where trusting drift-deflation
    against a split center would be fragile.

    Returns (new_centers, AssignStatsBounded) — ``st.bounds`` is the carry
    for the next step, valid against ``centers``.
    """
    if reseed not in (None, "split"):
        raise ValueError(f"unknown reseed policy {reseed!r}: expected 'split'")
    drift = jnp.sqrt(jnp.sum((centers - prev_centers) ** 2, axis=1))
    st = ops.assign_stats_bounded(
        x, centers, bounds, drift, index=index, impl=impl
    )
    means = st.sums / jnp.maximum(st.counts, 1.0)[:, None]
    new_centers = jnp.where(
        st.counts[:, None] > 0, l2_normalize(means), centers
    )
    if reseed == "split":
        new_centers, donor, reseeded = _split_empty_centers_info(
            new_centers, st.sums, st.counts, st.sumsq
        )
        any_reseed = jnp.any(reseeded)
        stale = jnp.logical_or(
            reseeded[st.idx], jnp.logical_and(any_reseed, st.idx == donor)
        )
        st = st._replace(bounds=ops.bounds_invalidate(st.bounds, stale))
    return new_centers, st


@functools.partial(
    jax.jit, static_argnames=("k", "max_iters", "impl", "fused", "bounded")
)
def kmeans_fit(
    x: jax.Array,
    init_centers: jax.Array,
    k: int,
    *,
    max_iters: int = 8,
    tol: float = 1e-4,
    impl: str = "auto",
    fused: bool = True,
    bounded: bool = False,
) -> KMeansResult:
    """Iterate to convergence (max center movement < tol) or max_iters.

    bounded=True threads the Elkan/Hamerly bounds carry through the
    while_loop (kmeans_step_bounded) — same centers and labels bit-for-bit,
    with the per-row sweep pruned once drift settles.
    """
    if bounded:
        use_index = ops._resolve(impl) != "xla"

        def bcond(state):
            moved = jnp.max(jnp.sum((state[0] - state[1]) ** 2, axis=1))
            return jnp.logical_and(state[2] < max_iters, moved > tol * tol)

        def bbody(state):
            centers, prev, it, bounds = state
            index = ops.build_center_index(centers) if use_index else None
            new_centers, st = kmeans_step_bounded(
                x, centers, prev, bounds, k, impl=impl, index=index
            )
            return new_centers, centers, it + 1, st.bounds

        far = init_centers + 10.0  # force first iteration
        centers, prev, iters, bounds = jax.lax.while_loop(
            bcond,
            bbody,
            (init_centers, far, jnp.int32(0), ops.bounds_identity(x.shape[0])),
        )
        # final assignment AND the RSS stats, still bound-pruned
        drift = jnp.sqrt(jnp.sum((centers - prev) ** 2, axis=1))
        index = ops.build_center_index(centers) if use_index else None
        st = ops.assign_stats_bounded(
            x, centers, bounds, drift, index=index, impl=impl
        )
        return KMeansResult(
            centers=centers,
            assignment=st.idx,
            best_sim=st.best_sim,
            rss=metrics.rss_from_assignment_stats(
                st.sums, st.counts, jnp.sum(st.sumsq), k
            ),
            objective=metrics.cosine_objective(st.best_sim),
            iterations=iters,
        )

    def cond(state):
        centers, prev, it = state
        moved = jnp.max(jnp.sum((centers - prev) ** 2, axis=1))
        return jnp.logical_and(it < max_iters, moved > tol * tol)

    def body(state):
        centers, _, it = state
        new_centers, _, _, _, _ = kmeans_step(
            x, centers, k, impl=impl, fused=fused
        )
        return new_centers, centers, it + 1

    far = init_centers + 10.0  # force first iteration
    centers, _, iters = jax.lax.while_loop(
        cond, body, (init_centers, far, jnp.int32(0))
    )
    if fused:
        # final assignment AND the RSS stats from the same single pass
        st = ops.assign_stats(x, centers, impl=impl)
        idx, best_sim = st.idx, st.best_sim
        rss = metrics.rss_from_assignment_stats(
            st.sums, st.counts, jnp.sum(st.sumsq), k
        )
    else:
        idx, best_sim = ops.assign_argmax(x, centers, impl=impl)
        rss = metrics.rss(x, idx, k)
    return KMeansResult(
        centers=centers,
        assignment=idx,
        best_sim=best_sim,
        rss=rss,
        objective=metrics.cosine_objective(best_sim),
        iterations=iters,
    )


def kmeans(
    x: jax.Array,
    k: int,
    key: jax.Array,
    *,
    max_iters: int = 8,
    tol: float = 1e-4,
    init_centers: jax.Array | None = None,
    impl: str = "auto",
    fused: bool = True,
    bounded: bool | None = None,
) -> KMeansResult:
    """Convenience entry point with the paper's random-document init.

    ``bounded=None`` defers to REPRO_ASSIGN_BOUNDS (ops.bounds_enabled)."""
    if init_centers is None:
        init_centers = init_random_centers(key, x, k)
    return kmeans_fit(
        x, init_centers, k, max_iters=max_iters, tol=tol, impl=impl,
        fused=fused, bounded=ops.bounds_enabled(bounded),
    )


@functools.partial(jax.jit, static_argnames=("impl",))
def assign_batch(
    x: jax.Array,
    centers: jax.Array,
    w: jax.Array | None = None,
    *,
    index: "ops.CenterIndex | None" = None,
    impl: str = "auto",
) -> tuple[jax.Array, jax.Array]:
    """One serving micro-batch: nearest-center assignment through the
    bound-pruned kernel. Batch rows are new every call, so there is no
    cross-batch bounds carry — the sentinel identity goes in, and pruning
    comes from the two-level center ``index`` (slab skipping on the Pallas
    path). Labels are bit-identical to the brute-force sweep either way.

    Returns ``(idx, best_sim)`` for the batch; weight-0 (padding) rows get
    whatever the sweep computes and must be sliced off by the caller.
    """
    st = ops.assign_stats_bounded(
        x,
        centers,
        ops.bounds_identity(x.shape[0]),
        jnp.zeros((centers.shape[0],), jnp.float32),
        w,
        index=index,
        impl=impl,
    )
    return st.idx, st.best_sim


# ------------------------------------------------------------------ streaming


@functools.partial(jax.jit, static_argnames=("impl",))
def _stream_fold_chunk(carry, x, w, centers, *, impl: str = "auto"):
    """Fold one chunk: ONE fused kernel call, monoid-merge into the carry.

    Also returns the chunk's (idx, best_sim, weighted objective term) — they
    fall out of the same single read of the chunk, so the final pass collects
    assignments at zero extra cost.
    """
    st = ops.assign_stats(x, centers, w, impl=impl)
    obj = jnp.sum(w * (1.0 - st.best_sim))  # pad rows carry w == 0
    return ops.merge_stats(carry, st), (st.idx, st.best_sim, obj)


@functools.partial(jax.jit, static_argnames=("impl",))
def _stream_fold_chunk_bounded(
    carry, x, w, centers, bounds, drift, *, index=None, impl: str = "auto"
):
    """Bounded sibling of ``_stream_fold_chunk``: same monoid fold, plus the
    refreshed per-row bounds and the chunk's (pruned, real) row counts for the
    analytic prune_rate."""
    st = ops.assign_stats_bounded(
        x, centers, bounds, drift, w, index=index, impl=impl
    )
    obj = jnp.sum(w * (1.0 - st.best_sim))  # pad rows carry w == 0
    real = w > 0
    pruned = jnp.sum(jnp.logical_and(st.pruned, real).astype(jnp.float32))
    rows = jnp.sum(real.astype(jnp.float32))
    return ops.merge_stats(carry, st), (
        st.idx, st.best_sim, obj, st.bounds, pruned, rows,
    )


class StreamPassOut(NamedTuple):
    """What one streaming assignment pass returns (see ``_stream_pass``)."""

    stats: tuple  # (sums, counts, min_sim, sumsq) folded accumulators
    idx: "np.ndarray | None"  # (n,) collected labels (None unless collect)
    best_sim: "np.ndarray | None"  # (n,) collected similarities
    objective: jax.Array  # weighted cosine objective
    bounds: "list | None"  # per-chunk host (idx, lo, hi) blocks (bounded only)
    pruned: float  # real rows that skipped the sweep (bounded only)
    rows: float  # real rows seen (bounded only)


def _stream_pass(
    stream,
    centers,
    k: int,
    impl: str,
    collect: bool = False,
    *,
    pass_id: str = "kmeans/pass",
    checkpoint=None,
    guard=None,
    bounded: bool = False,
    bounds_blocks=None,
    drift=None,
    index=None,
):
    """One full pass driven by the shared streaming executor
    (text/stream.run_pass): the prefetcher's background thread regenerates
    chunk i+1 while the device folds chunk i into the carried f32
    accumulators — O(chunk + k·d) resident. Returns a ``StreamPassOut``;
    idx/best_sim are None unless ``collect``.

    bounded=True carries per-row Elkan/Hamerly bounds: each chunk's prior
    bounds come from ``bounds_blocks`` (the previous pass's per-chunk host
    blocks, aligned by chunk index — the unknown sentinel when absent),
    deflated by the (k,) ``drift`` vector, and the refreshed blocks ride the
    fold carry, so a checkpointed snapshot captures them and a killed pass
    resumes with its pruning state intact. ``run_pass`` and its prefetcher
    stay oblivious — bounds are fold-carry state, never producer state.

    The collected idx/sim blocks live INSIDE the run_pass carry (not a
    closure): a checkpointed snapshot then captures them with the stats, so
    a pass killed mid-collection resumes with the already-collected prefix
    intact — bit-identical to the uninterrupted run."""
    from repro.resilience import array_token
    from repro.text.stream import run_pass, to_device  # lazy: keeps layering acyclic

    if bounded:
        drift_dev = (
            jnp.zeros((k,), jnp.float32) if drift is None else jnp.asarray(drift)
        )

        def fold(state, ch, ci):
            carry, obj, idxs, sims, blocks, pruned, rows = state
            x, w = to_device(ch)
            if bounds_blocks is not None and ci < len(bounds_blocks):
                bi, bl, bh = bounds_blocks[ci]
                b = ops.Bounds(
                    jnp.asarray(bi), jnp.asarray(bl), jnp.asarray(bh)
                )
            else:
                b = ops.bounds_identity(x.shape[0])
            carry, (idx, sim, o, nb, p, r) = _stream_fold_chunk_bounded(
                carry, x, w, centers, b, drift_dev, index=index, impl=impl,
            )
            with TraceAnnotation("stream.sync"):
                blocks = blocks + [
                    (np.asarray(nb.idx), np.asarray(nb.lo), np.asarray(nb.hi))
                ]
                if collect:
                    idxs = idxs + [np.asarray(idx)]
                    sims = sims + [np.asarray(sim)]
            return carry, obj + o, idxs, sims, blocks, pruned + p, rows + r

        carry, obj, idxs, sims, blocks, pruned, rows = run_pass(
            stream,
            fold,
            (
                ops.stats_identity(k, stream.dim), jnp.float32(0.0),
                [], [], [], jnp.float32(0.0), jnp.float32(0.0),
            ),
            pass_id=pass_id,
            checkpoint=checkpoint,
            guard=guard,
            meta={"centers": array_token(centers)}
            if checkpoint is not None
            else None,
        )
        with TraceAnnotation("stream.sync"):
            pruned, rows = float(pruned), float(rows)
        return StreamPassOut(
            stats=carry,
            idx=np.concatenate(idxs)[: stream.n] if collect else None,
            best_sim=np.concatenate(sims)[: stream.n] if collect else None,
            objective=obj,
            bounds=blocks,
            pruned=pruned,
            rows=rows,
        )

    def fold(state, ch, ci):
        carry, obj, idxs, sims = state
        x, w = to_device(ch)
        carry, (idx, sim, o) = _stream_fold_chunk(carry, x, w, centers, impl=impl)
        if collect:
            with TraceAnnotation("stream.sync"):
                idxs = idxs + [np.asarray(idx)]
                sims = sims + [np.asarray(sim)]
        return carry, obj + o, idxs, sims

    carry, obj, idxs, sims = run_pass(
        stream,
        fold,
        (ops.stats_identity(k, stream.dim), jnp.float32(0.0), [], []),
        pass_id=pass_id,
        checkpoint=checkpoint,
        guard=guard,
        meta={"centers": array_token(centers)} if checkpoint is not None else None,
    )
    return StreamPassOut(
        stats=carry,
        idx=np.concatenate(idxs)[: stream.n] if collect else None,
        best_sim=np.concatenate(sims)[: stream.n] if collect else None,
        objective=obj,
        bounds=None,
        pruned=0.0,
        rows=0.0,
    )


def kmeans_fit_stream(
    stream,
    init_centers: jax.Array,
    k: int,
    *,
    max_iters: int = 8,
    tol: float = 1e-4,
    impl: str = "auto",
    checkpoint=None,
    guard=None,
    bounded: bool | None = None,
    profile: dict | None = None,
) -> KMeansResult:
    """Out-of-core ``kmeans_fit``: the host drives iterations, each iteration
    is one streaming pass through the fused assign+stats kernel with carried
    accumulators — peak residency O(chunk·d + k·d), any n.

    Same convergence rule as the resident path (stop when max center movement
    ≤ tol); assignment/best_sim come back as host arrays trimmed to real rows.

    With a ``checkpoint`` (resilience.Checkpointer), each iteration's outcome
    is persisted as a pass RESULT and each in-flight pass snapshots its carry:
    a killed job restarted with the same stream/init replays completed
    iterations from stored results (no data pass) and resumes the killed pass
    mid-stream — the final model is bit-identical to an uninterrupted run.
    ``guard='finite'`` raises GuardError naming the pass/chunk that first
    produced a non-finite accumulator.

    ``bounded`` (None → REPRO_ASSIGN_BOUNDS) carries per-chunk Elkan/Hamerly
    bounds between iterations — per-row streaming state, O(chunk) extra
    residency, same labels and centers bit-for-bit. Iterations replayed from
    checkpoint results reset the carry to the unknown sentinel (only the
    prune rate suffers; exactness never depends on the bounds state).
    ``profile`` (a dict) receives a per-iteration ``prune_rate`` list.
    """
    from repro.resilience import array_token

    bounded = ops.bounds_enabled(bounded)
    use_index = bounded and ops._resolve(impl) != "xla"
    centers = init_centers
    prev_centers = None  # None -> unknown drift -> sentinel bounds
    bblocks = None
    iters = 0

    def _drift():
        if prev_centers is None:
            return None
        return jnp.sqrt(jnp.sum((centers - prev_centers) ** 2, axis=1))

    for i in range(max_iters):
        pid = f"kmeans/iter{i}"
        done = checkpoint.load_result(pid) if checkpoint is not None else None
        if done is not None and done["token"] == array_token(centers):
            centers, moved = jnp.asarray(done["centers"]), done["moved"]
            prev_centers, bblocks = None, None  # no pass ran: bounds unknown
            iters += 1
            if moved <= tol * tol:
                break
            continue
        index = ops.build_center_index(jnp.asarray(centers)) if use_index else None
        out = _stream_pass(
            stream, centers, k, impl,
            pass_id=pid, checkpoint=checkpoint, guard=guard,
            bounded=bounded, bounds_blocks=bblocks, drift=_drift(), index=index,
        )
        with TraceAnnotation("kmeans.update"):
            sums, counts = out.stats[0], out.stats[1]
            means = sums / jnp.maximum(counts, 1.0)[:, None]
            new_centers = jnp.where(
                counts[:, None] > 0, l2_normalize(means), centers
            )
            moved = jnp.max(jnp.sum((new_centers - centers) ** 2, axis=1))
            with TraceAnnotation("stream.sync"):
                moved = float(moved)
        if checkpoint is not None:
            checkpoint.save_result(
                pid,
                {
                    "token": array_token(centers),  # keyed by the INPUT centers
                    "centers": np.asarray(new_centers),
                    "moved": moved,
                },
            )
        if profile is not None and bounded:
            profile.setdefault("prune_rate", []).append(
                out.pruned / max(out.rows, 1.0)
            )
        prev_centers, bblocks = centers, out.bounds
        centers = new_centers
        iters += 1
        if moved <= tol * tol:
            break
    # final assignment AND the RSS stats from the same streaming pass
    index = ops.build_center_index(jnp.asarray(centers)) if use_index else None
    out = _stream_pass(
        stream, centers, k, impl, collect=True,
        pass_id="kmeans/final", checkpoint=checkpoint, guard=guard,
        bounded=bounded, bounds_blocks=bblocks, drift=_drift(), index=index,
    )
    (sums, counts, _, sumsq), idx, best_sim, obj = (
        out.stats, out.idx, out.best_sim, out.objective,
    )
    if profile is not None and bounded:
        profile.setdefault("prune_rate", []).append(
            out.pruned / max(out.rows, 1.0)
        )
    if checkpoint is not None:
        for i in range(max_iters):  # the run is over: drop iteration results
            checkpoint.delete_result(f"kmeans/iter{i}")
    rss = metrics.rss_from_assignment_stats(sums, counts, jnp.sum(sumsq), k)
    return KMeansResult(
        centers=centers,
        assignment=idx,
        best_sim=best_sim,
        rss=rss,
        objective=obj,
        iterations=jnp.int32(iters),
    )


def kmeans_stream(
    stream,
    k: int,
    key: jax.Array,
    *,
    max_iters: int = 8,
    tol: float = 1e-4,
    impl: str = "auto",
    checkpoint=None,
    guard=None,
) -> KMeansResult:
    """Streaming convenience entry: the paper's random-document init drawn by
    the one-pass reservoir (exact uniform k-sample), then the streaming fit."""
    from repro.core.sampling import reservoir_sample_stream

    rows, _ = reservoir_sample_stream(
        stream, k, key, checkpoint=checkpoint, guard=guard
    )
    result = kmeans_fit_stream(
        stream, l2_normalize(rows), k, max_iters=max_iters, tol=tol, impl=impl,
        checkpoint=checkpoint, guard=guard,
    )
    if checkpoint is not None:
        checkpoint.delete_result("reservoir")  # the run is over
    return result
