"""Distributed K-Means / BKC / Buckshot: the paper's MapReduce jobs on a mesh.

Data layout: document matrix rows sharded over the data axes
(``P(("pod","data"), None)`` on the production mesh); centers and micro-cluster
statistics replicated. Padding rows carry weight 0 and never contribute.

Job structure mirrors the paper exactly:
  K-Means   : one job per iteration (map=assign, combine=partial stats,
              reduce=psum) — PKMeans [26].
  BKC       : job 1 = micro-cluster build (psum/pmin of CF stats);
              job 2 = joinToGroups on replicated (BigK)-sized stats
              (the paper's single reducer);
              job 3 = final assignment (sharded labels + RSS stats).
  Buckshot  : job 0a = distributed uniform sample (local top-s + gathered
              global top-s); job 0b = sample row collection (psum of
              one-owner buffers); phase 1 HAC on replicated sample;
              phase 2 = 2-3 K-Means jobs.

Every algorithm also has an out-of-core ``*_distributed_stream`` twin: the
same jobs in the engine's fold mode, driven chunk-by-chunk by the shared
streaming executor (text/stream.run_pass — chunks shard on arrival while the
prefetcher regenerates the next one), with ONE collective per pass. Buckshot's
streaming sample is the sharded running reservoir
(``reservoir_sample_distributed_stream``, fold-mode 'topk').
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.common import l2_normalize
from repro.core.bkc import join_to_groups
from repro.core.hac import single_link_labels_boruvka
from repro.core.microcluster import MicroClusters
from repro.distrib.engine import make_fold_job, make_job
from repro.distrib.sharding import (
    check_stream_shardable,
    mesh_axis_size,
    shard_rows,
)
from repro.kernels import ops


class DistClusterResult(NamedTuple):
    centers: jax.Array  # (k, d) replicated
    assignment: jax.Array  # (n,) sharded like the input rows
    rss: jax.Array  # scalar (replicated)
    objective: jax.Array  # scalar cosine objective
    iterations: int


# ----------------------------------------------------------------- common jobs


def _assign_stats_map(
    k: int, impl: str, *, prezeroed: bool = False, unit_norm: bool = False
):
    """map+combine for one K-Means iteration (also BKC job 3).

    ONE fused assign_stats kernel per shard: assignment, weighted sums,
    counts, and squared norms all come from a single HBM read of the document
    shard (the weights are applied in-kernel, so the old ``x * w`` temporary
    and the separate cluster_stats / segment_sum passes are gone entirely —
    the shard is the paper's combiner, now at kernel granularity).

    prezeroed is retained for API compatibility but no longer changes the
    computation: the fused kernel weights rows in VMEM either way.

    unit_norm=True asserts real rows are L2-normalized (tf-idf pipeline
    guarantees it): sum of squared norms is exactly sum(w), skipping even the
    fused kernel's sumsq term in the scalar reduction.
    """
    del prezeroed

    def map_combine(data, bcast):
        x, w = data["x"], data["w"]
        st = ops.assign_stats(x, bcast["centers"], w, impl=impl)
        if unit_norm:
            sq = jnp.sum(w)  # |x_i|^2 == 1 for real rows, 0 for padding
        else:
            sq = jnp.sum(st.sumsq)
        obj = jnp.sum(w * (1.0 - st.best_sim))
        return {
            "sums": st.sums,
            "counts": st.counts,
            "sq": sq,
            "obj": obj,
            "idx": st.idx,
            "sim": st.best_sim,
        }

    kinds = {
        "sums": "sum",
        "counts": "sum",
        "sq": "sum",
        "obj": "sum",
        "idx": "shard",
        "sim": "shard",
    }
    return map_combine, kinds


def _assign_stats_bounded_map(
    k: int, impl: str, *, use_index: bool = False, unit_norm: bool = False
):
    """Bound-pruned twin of ``_assign_stats_map``.

    The triangle-inequality bounds are SHARD-LOCAL row state: each shard's
    rows carry their own (idx, lo, hi) triple in the data pytree (kind
    'shard' on the way out), so pruning never adds a collective — the only
    new wire traffic is the replicated (k,) drift vector riding the existing
    bcast, and a scalar 'pruned' count joining the one psum per pass.
    ``use_index`` expects the two-level center index (perm, group_of) in the
    bcast (replicated (k,) i32 vectors).
    """

    def map_combine(data, bcast):
        x, w = data["x"], data["w"]
        bounds = ops.Bounds(data["bidx"], data["blo"], data["bhi"])
        index = (
            ops.CenterIndex(bcast["perm"], bcast["group_of"])
            if use_index else None
        )
        st = ops.assign_stats_bounded(
            x, bcast["centers"], bounds, bcast["drift"], w,
            index=index, impl=impl,
        )
        if unit_norm:
            sq = jnp.sum(w)  # |x_i|^2 == 1 for real rows, 0 for padding
        else:
            sq = jnp.sum(st.sumsq)
        obj = jnp.sum(w * (1.0 - st.best_sim))
        return {
            "sums": st.sums,
            "counts": st.counts,
            "sq": sq,
            "obj": obj,
            "pruned": jnp.sum(
                jnp.where(jnp.logical_and(st.pruned, w > 0), 1.0, 0.0)
            ),
            "idx": st.idx,
            "sim": st.best_sim,
            "bidx": st.bounds.idx,
            "blo": st.bounds.lo,
            "bhi": st.bounds.hi,
        }

    kinds = {
        "sums": "sum",
        "counts": "sum",
        "sq": "sum",
        "obj": "sum",
        "pruned": "sum",
        "idx": "shard",
        "sim": "shard",
        "bidx": "shard",
        "blo": "shard",
        "bhi": "shard",
    }
    return map_combine, kinds


def _bounds_bcast(centers, drift, index):
    """Broadcast pytree for a bounded job: drift defaults to the zero vector
    (sentinel bounds never prune, so zeros are exact for a first pass)."""
    k = centers.shape[0]
    b = {
        "centers": centers,
        "drift": (
            jnp.zeros((k,), jnp.float32) if drift is None else drift
        ),
    }
    if index is not None:
        b["perm"], b["group_of"] = index.perm, index.group_of
    return b


def _new_centers(sums, counts, old):
    means = sums / jnp.maximum(counts, 1.0)[:, None]
    return jnp.where(counts[:, None] > 0, l2_normalize(means), old)


def _rss(sums, counts, sq):
    means = sums / jnp.maximum(counts, 1.0)[:, None]
    return sq - jnp.sum(counts * jnp.sum(means * means, axis=1))


# ----------------------------------------------------------------- K-Means


def kmeans_distributed(
    mesh: Mesh,
    axes: tuple[str, ...],
    x: jax.Array,
    w: jax.Array,
    init_centers: jax.Array,
    k: int,
    *,
    max_iters: int = 8,
    tol: float = 1e-4,
    impl: str = "auto",
    bounded: bool | None = None,
) -> DistClusterResult:
    """PKMeans: the host drives iterations (the paper's job-chaining driver);
    each iteration is ONE MapReduce job on the mesh.

    ``bounded`` (None → REPRO_ASSIGN_BOUNDS) carries shard-local
    triangle-inequality bounds between iterations: the per-row (idx, lo, hi)
    state rides the data pytree, the (k,) drift vector rides the bcast, and
    labels stay bit-identical to the brute sweep."""
    bounded = ops.bounds_enabled(bounded)
    if bounded:
        use_index = ops._resolve(impl) != "xla"
        map_combine, kinds = _assign_stats_bounded_map(
            k, impl, use_index=use_index
        )
    else:
        use_index = False
        map_combine, kinds = _assign_stats_map(k, impl)
    job = make_job(mesh, axes, map_combine, kinds, name="kmeans_iter")

    def run(centers, bounds, drift):
        if not bounded:
            return job({"x": x, "w": w}, {"centers": centers})
        index = _center_index(mesh)(centers) if use_index else None
        data = {
            "x": x, "w": w,
            "bidx": bounds.idx, "blo": bounds.lo, "bhi": bounds.hi,
        }
        return job(data, _bounds_bcast(centers, drift, index))

    centers = init_centers
    bounds = ops.bounds_identity(x.shape[0]) if bounded else None
    drift = None
    out = None
    it = 0
    for it in range(1, max_iters + 1):
        out = run(centers, bounds, drift)
        if bounded:
            bounds = ops.Bounds(out["bidx"], out["blo"], out["bhi"])
        new_centers = _new_centers(out["sums"], out["counts"], centers)
        moved = float(jnp.max(jnp.sum((new_centers - centers) ** 2, axis=1)))
        if bounded:
            drift = jnp.sqrt(jnp.sum((new_centers - centers) ** 2, axis=1))
        centers = new_centers
        if moved <= tol * tol:
            break
    # final assignment against the converged centers
    out = run(centers, bounds, drift)
    return DistClusterResult(
        centers=centers,
        assignment=out["idx"],
        rss=_rss(out["sums"], out["counts"], out["sq"]),
        objective=out["obj"],
        iterations=it,
    )


# ------------------------------------------------------- streaming K-Means


def _fold_pass(
    job,
    mesh,
    axes,
    stream,
    centers,
    collect: bool,
    *,
    pass_id: str = "fold",
    checkpoint=None,
    guard=None,
    bounded: bool = False,
    bounds_blocks=None,
    drift=None,
    index=None,
):
    """One streaming pass of the fold job, driven by the shared executor
    (text/stream.run_pass): every chunk is sharded onto the mesh on arrival
    while the prefetcher regenerates the next chunk on a background thread,
    map+combine folds into the per-shard carry, and ONE collective
    (finalize) closes the pass — the combiner discipline at chunk-stream
    granularity.

    ``bounded`` expects the job to be built from ``_assign_stats_bounded_map``:
    each chunk's prior (idx, lo, hi) bounds come from ``bounds_blocks[ci]``
    (host numpy triples, or the sentinel when None — e.g. a fresh run or a
    resume past a result-skip), ride the data pytree onto the chunk's own
    shards, and come back per chunk as 'shard' outputs — nothing about
    pruning crosses the wire beyond the (k,) drift bcast and the scalar
    pruned count already inside the one finalize collective.

    The run_pass carry is (job_carry, collected idx blocks, bounds blocks):
    all live in the snapshot, and a restored job carry is re-sharded onto
    the mesh by ``FoldJob.carry_device`` — a killed distributed pass resumes
    with every per-shard partial back on its shard."""
    from repro.text.stream import run_pass, to_device  # lazy: keeps layering acyclic

    meta = None
    if checkpoint is not None:
        from repro.resilience import array_token

        meta = {"centers": array_token(centers)}

    bcast = (
        _bounds_bcast(centers, drift, index)
        if bounded else {"centers": centers}
    )

    def fold(state, ch, ci):
        carry, idxs, bblocks = state
        x, w = to_device(ch)
        data = {
            "x": shard_rows(mesh, axes, x),
            "w": shard_rows(mesh, axes, w),
        }
        if bounded:
            if bounds_blocks is None:
                b = ops.bounds_identity(ch.x.shape[0])
                bi, bl, bh = b.idx, b.lo, b.hi
            else:
                bi, bl, bh = bounds_blocks[ci]
            data["bidx"] = shard_rows(mesh, axes, jnp.asarray(bi))
            data["blo"] = shard_rows(mesh, axes, jnp.asarray(bl))
            data["bhi"] = shard_rows(mesh, axes, jnp.asarray(bh))
        carry, shard_outs = job.step(carry, data, bcast)
        if collect:
            idxs = idxs + [np.asarray(shard_outs["idx"])]
        if bounded:
            bblocks = bblocks + [(
                np.asarray(shard_outs["bidx"]),
                np.asarray(shard_outs["blo"]),
                np.asarray(shard_outs["bhi"]),
            )]
        return carry, idxs, bblocks

    def restore(host):
        carry, idxs, bblocks = host
        return (
            (None if carry is None else job.carry_device(carry)),
            idxs,
            bblocks,
        )

    carry, idxs, bblocks = run_pass(
        stream,
        fold,
        (None, [], []),
        pass_id=pass_id,
        checkpoint=checkpoint,
        guard=guard,
        meta=meta,
        restore_carry=restore,
    )
    out = job.finalize(carry)
    idx = np.concatenate(idxs)[: stream.n] if collect else None
    return out, idx, (bblocks if bounded else None)


def kmeans_distributed_stream(
    mesh: Mesh,
    axes: tuple[str, ...],
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
) -> DistClusterResult:
    """Out-of-core PKMeans on the mesh: each iteration is one streaming fold
    job — chunks are sharded on arrival, per-shard partials carry across
    chunks, and the k·d stats cross the wire ONCE per pass instead of once
    per chunk. Device residency is O(chunk·d / P + k·d) for any n.

    Resilience mirrors the single-device ``kmeans_fit_stream``: each
    iteration's centers persist as a pass result, the in-flight pass
    snapshots its per-shard carry (re-sharded on restore), and a restart
    replays only the killed pass — bit-identical to an uninterrupted run
    on the same mesh. ``bounded`` carries the per-chunk bounds blocks
    between passes (host numpy, shard-local per row); a resume that skips
    an iteration via its stored result restarts the NEXT pass from sentinel
    bounds — labels are bounds-state independent, so still bit-identical.
    ``profile`` (optional dict) collects per-pass ``prune_rate``."""
    check_stream_shardable(stream, mesh, axes)
    bounded = ops.bounds_enabled(bounded)
    use_index = bounded and ops._resolve(impl) != "xla"
    if bounded:
        map_combine, kinds = _assign_stats_bounded_map(
            k, impl, use_index=use_index
        )
    else:
        map_combine, kinds = _assign_stats_map(k, impl)
    job = make_fold_job(mesh, axes, map_combine, kinds, name="kmeans_fold")

    if checkpoint is not None:
        from repro.resilience import array_token

    def bkwargs(centers, drift, bblocks):
        if not bounded:
            return {}
        return {
            "bounded": True,
            "bounds_blocks": bblocks,
            "drift": drift,
            "index": _center_index(mesh)(centers) if use_index else None,
        }

    def note_prune(out):
        if bounded and profile is not None:
            profile.setdefault("prune_rate", []).append(
                float(out["pruned"]) / max(stream.n, 1)
            )

    centers = init_centers
    bblocks = None
    drift = None
    it = 0
    for it in range(1, max_iters + 1):
        pid = f"kmeans/iter{it - 1}"
        done = checkpoint.load_result(pid) if checkpoint is not None else None
        if done is not None and done["token"] == array_token(centers):
            centers, moved = jnp.asarray(done["centers"]), done["moved"]
            bblocks, drift = None, None  # skipped pass: restart from sentinel
            if moved <= tol * tol:
                break
            continue
        out, _, nb = _fold_pass(
            job, mesh, axes, stream, centers, collect=False,
            pass_id=pid, checkpoint=checkpoint, guard=guard,
            **bkwargs(centers, drift, bblocks),
        )
        note_prune(out)
        new_centers = _new_centers(out["sums"], out["counts"], centers)
        moved = float(jnp.max(jnp.sum((new_centers - centers) ** 2, axis=1)))
        if checkpoint is not None:
            checkpoint.save_result(
                pid,
                {
                    "token": array_token(centers),  # keyed by the INPUT centers
                    "centers": np.asarray(new_centers),
                    "moved": moved,
                },
            )
        if bounded:
            bblocks = nb
            drift = jnp.sqrt(jnp.sum((new_centers - centers) ** 2, axis=1))
        centers = new_centers
        if moved <= tol * tol:
            break
    # final assignment against the converged centers
    out, idx, _ = _fold_pass(
        job, mesh, axes, stream, centers, collect=True,
        pass_id="kmeans/final", checkpoint=checkpoint, guard=guard,
        **bkwargs(centers, drift, bblocks),
    )
    note_prune(out)
    if checkpoint is not None:
        for i in range(max_iters):  # the run is over: drop iteration results
            checkpoint.delete_result(f"kmeans/iter{i}")
    return DistClusterResult(
        centers=centers,
        assignment=idx,
        rss=_rss(out["sums"], out["counts"], out["sq"]),
        objective=out["obj"],
        iterations=it,
    )


# ----------------------------------------------------------------- BKC


def bkc_distributed(
    mesh: Mesh,
    axes: tuple[str, ...],
    x: jax.Array,
    w: jax.Array,
    init_centers: jax.Array,
    big_k: int,
    k: int,
    *,
    impl: str = "auto",
    bounded: bool | None = None,
) -> DistClusterResult:
    """BKC-for-documents as the paper's three MapReduce jobs.

    ``bounded`` routes both data jobs through the bound-pruned op with
    sentinel bounds — single-pass jobs have no carry to prune with, but the
    Pallas path gets the two-level center index (BigK ≫ k is where the
    group-skip pays)."""
    bounded = ops.bounds_enabled(bounded)
    use_index = bounded and ops._resolve(impl) != "xla"

    # ---- job 1: micro-cluster statistics (map+combine: ONE fused kernel per
    # shard yielding n/CF1/CF2/min_sim from a single read; reduce: psum / pmin)
    def mc_map(data, bcast):
        if bounded:
            index = (
                ops.CenterIndex(bcast["perm"], bcast["group_of"])
                if use_index else None
            )
            st = ops.assign_stats_bounded(
                data["x"], bcast["centers"],
                ops.Bounds(data["bidx"], data["blo"], data["bhi"]),
                bcast["drift"], data["w"], index=index, impl=impl,
            )
        else:
            st = ops.assign_stats(
                data["x"], bcast["centers"], data["w"], impl=impl
            )
        return {
            "n": st.counts,
            "cf1": st.sums,
            "cf2": st.sumsq,
            "min_sim": st.min_sim,
        }

    job1 = make_job(
        mesh,
        axes,
        mc_map,
        {"n": "sum", "cf1": "sum", "cf2": "sum", "min_sim": "min"},
        name="bkc_microclusters",
    )
    if bounded:
        b = ops.bounds_identity(x.shape[0])
        index = _center_index(mesh)(init_centers) if use_index else None
        stats = job1(
            {"x": x, "w": w, "bidx": b.idx, "blo": b.lo, "bhi": b.hi},
            _bounds_bcast(init_centers, None, index),
        )
    else:
        stats = job1({"x": x, "w": w}, {"centers": init_centers})

    valid = stats["n"] > 0
    mc = MicroClusters(
        n=stats["n"],
        cf1=stats["cf1"],
        cf2=stats["cf2"],
        centers=init_centers,
        min_sim=jnp.where(valid, stats["min_sim"], 1.0),
        valid=valid,
    )

    # ---- job 2: joinToGroups on the replicated (BigK)-sized state. The paper
    # uses a single reducer; here every device runs the same tiny computation.
    group, _thr = join_to_groups(mc, k)
    sums = jax.ops.segment_sum(mc.cf1, group, num_segments=k)
    counts = jax.ops.segment_sum(mc.n, group, num_segments=k)
    centers = jnp.where(counts[:, None] > 0, l2_normalize(sums), 0.0)

    # ---- job 3: final assignment pass
    if bounded:
        map_combine, kinds = _assign_stats_bounded_map(
            k, impl, use_index=use_index
        )
        job3 = make_job(mesh, axes, map_combine, kinds, name="bkc_final_assign")
        b = ops.bounds_identity(x.shape[0])
        index = _center_index(mesh)(centers) if use_index else None
        out = job3(
            {"x": x, "w": w, "bidx": b.idx, "blo": b.lo, "bhi": b.hi},
            _bounds_bcast(centers, None, index),
        )
    else:
        map_combine, kinds = _assign_stats_map(k, impl)
        job3 = make_job(mesh, axes, map_combine, kinds, name="bkc_final_assign")
        out = job3({"x": x, "w": w}, {"centers": centers})
    return DistClusterResult(
        centers=centers,
        assignment=out["idx"],
        rss=_rss(out["sums"], out["counts"], out["sq"]),
        objective=out["obj"],
        iterations=2,  # two full passes over the data
    )


def bkc_distributed_stream(
    mesh: Mesh,
    axes: tuple[str, ...],
    stream,
    init_centers: jax.Array,
    big_k: int,
    k: int,
    *,
    impl: str = "auto",
    checkpoint=None,
    guard=None,
    bounded: bool | None = None,
) -> DistClusterResult:
    """Out-of-core distributed BKC: jobs 1 and 3 are streaming fold jobs
    (chunks sharded on arrival, one collective per pass); job 2 runs on the
    replicated O(BigK·d) micro-cluster statistics exactly as the resident
    path — only the two full passes over the collection ever touch chunks.
    Pass-1 stats persist as a pass result (ids ``bkc/mc``, ``bkc/final``) so
    a restart killed in pass 3 never re-streams pass 1. ``bounded`` routes
    both passes through the bound-pruned op with sentinel bounds."""
    from repro.core.bkc import _group_centers

    check_stream_shardable(stream, mesh, axes)
    bounded = ops.bounds_enabled(bounded)
    use_index = bounded and ops._resolve(impl) != "xla"

    # ---- job 1: micro-cluster statistics folded over the chunk stream (ONE
    # fused kernel per shard per chunk, CF additivity as the chunk monoid)
    def mc_map(data, bcast):
        if bounded:
            index = (
                ops.CenterIndex(bcast["perm"], bcast["group_of"])
                if use_index else None
            )
            st = ops.assign_stats_bounded(
                data["x"], bcast["centers"],
                ops.Bounds(data["bidx"], data["blo"], data["bhi"]),
                bcast["drift"], data["w"], index=index, impl=impl,
            )
        else:
            st = ops.assign_stats(
                data["x"], bcast["centers"], data["w"], impl=impl
            )
        return {
            "n": st.counts,
            "cf1": st.sums,
            "cf2": st.sumsq,
            "min_sim": st.min_sim,
            # sentinel bounds in, bounds out dropped: single-pass job — but
            # the fold protocol still wants the shard kinds when bounded
            **(
                {"bidx": st.bounds.idx, "blo": st.bounds.lo,
                 "bhi": st.bounds.hi, "idx": st.idx}
                if bounded else {}
            ),
        }

    mc_kinds = {"n": "sum", "cf1": "sum", "cf2": "sum", "min_sim": "min"}
    if bounded:
        mc_kinds.update(
            {"bidx": "shard", "blo": "shard", "bhi": "shard", "idx": "shard"}
        )
    job1 = make_fold_job(mesh, axes, mc_map, mc_kinds, name="bkc_mc_fold")

    def bkwargs(centers):
        if not bounded:
            return {}
        return {
            "bounded": True,
            "bounds_blocks": None,  # sentinel: no prior pass to carry from
            "drift": None,
            "index": _center_index(mesh)(centers) if use_index else None,
        }

    stats = None
    if checkpoint is not None:
        from repro.resilience import array_token

        mc_meta = {"centers": array_token(init_centers)}
        stats = checkpoint.load_result("bkc/mc", meta=mc_meta)
    if stats is None:
        stats, _, _ = _fold_pass(
            job1, mesh, axes, stream, init_centers, collect=False,
            pass_id="bkc/mc", checkpoint=checkpoint, guard=guard,
            **bkwargs(init_centers),
        )
        if checkpoint is not None:
            stats = {
                k_: v for k_, v in stats.items() if v is not None
            }  # drop 'shard' placeholders before persisting
            checkpoint.save_result("bkc/mc", dict(stats), meta=mc_meta)

    valid = stats["n"] > 0
    mc = MicroClusters(
        n=stats["n"],
        cf1=stats["cf1"],
        cf2=stats["cf2"],
        centers=init_centers,
        min_sim=jnp.where(valid, stats["min_sim"], 1.0),
        valid=valid,
    )
    centers, _group, _thr = _group_centers(mc, k)

    # ---- job 3: final assignment pass (streamed)
    if bounded:
        map_combine, kinds = _assign_stats_bounded_map(
            k, impl, use_index=use_index
        )
    else:
        map_combine, kinds = _assign_stats_map(k, impl)
    job3 = make_fold_job(mesh, axes, map_combine, kinds, name="bkc_final_fold")
    out, idx, _ = _fold_pass(
        job3, mesh, axes, stream, centers, collect=True,
        pass_id="bkc/final", checkpoint=checkpoint, guard=guard,
        **bkwargs(centers),
    )
    if checkpoint is not None:
        checkpoint.delete_result("bkc/mc")  # the run is over
    return DistClusterResult(
        centers=centers,
        assignment=idx,
        rss=_rss(out["sums"], out["counts"], out["sq"]),
        objective=out["obj"],
        iterations=2,  # two full passes over the data
    )


# ----------------------------------------------------------------- Buckshot


def sample_rows_distributed(
    mesh: Mesh,
    axes: tuple[str, ...],
    x: jax.Array,
    w: jax.Array,
    s: int,
    key: jax.Array,
) -> jax.Array:
    """Uniform sample (without replacement) of s real rows -> (s, d) replicated.

    Exactness: global top-s of iid uniform scores is a uniform s-subset, and it
    is contained in the union of per-shard top-s sets; each winner row is owned
    by exactly one shard, so the psum of per-shard scatter buffers reconstructs
    the sample." """
    n_shards = mesh_axis_size(mesh, axes)
    n_local = x.shape[0] // n_shards
    n_real = int(jnp.sum(w > 0))
    if s > n_real:
        raise ValueError(
            f"cannot sample {s} rows from {n_real} real rows without"
            " replacement"
        )

    def sample_map(data, bcast):
        ws = data["w"]
        me = jax.lax.axis_index(axes)
        sub = jax.random.fold_in(bcast["key"], me)
        # pad rows score -1, strictly below any real row's [0, 1) draw —
        # multiplying by the mask instead would score pads exactly 0.0,
        # tied with (and interleaved among) real rows drawing 0.0
        u = jnp.where(ws > 0, jax.random.uniform(sub, ws.shape), -1.0)
        top = min(s, n_local)
        scores, li = jax.lax.top_k(u, top)
        gi = li.astype(jnp.int32) + me.astype(jnp.int32) * n_local
        return {"scores": scores, "gidx": gi}

    job_a = make_job(
        mesh, axes, sample_map, {"scores": "gather", "gidx": "gather"}, name="sample_topk"
    )
    cand = job_a({"x": x, "w": w}, {"key": key})
    top_scores, pos = jax.lax.top_k(cand["scores"], s)
    del top_scores
    sample_gidx = cand["gidx"][pos]  # (s,) replicated

    def collect_map(data, bcast):
        xs = data["x"]
        me = jax.lax.axis_index(axes)
        gidx = bcast["gidx"]
        owner = gidx // n_local
        local = jnp.where(owner == me, gidx % n_local, 0)
        rows = xs[local]
        rows = jnp.where((owner == me)[:, None], rows, 0.0)
        return {"rows": rows}

    job_b = make_job(mesh, axes, collect_map, {"rows": "sum"}, name="sample_collect")
    out = job_b({"x": x, "w": w}, {"gidx": sample_gidx})
    return out["rows"]


def _phase1_init_centers(
    mesh: Mesh,
    axes: tuple[str, ...],
    xs: jax.Array,
    k: int,
    *,
    impl: str,
    hac: str,
    sweep: str = "auto",
    overlap: bool = True,
) -> jax.Array:
    """Buckshot phase 1 on the replicated (s, d) sample rows -> (k, d)
    initial centers. Shared by the resident and streaming distributed
    drivers; both paths are matrix-free (no (s, s) block on any device):

    hac = "replicated": phase 1 runs replicated on every device — the sample
      is s = sqrt(kn), tiny next to the collection, and replicating it avoids
      a scatter/gather round-trip. Same Borůvka rounds as core/buckshot.py.
    hac = "boruvka": phase 1's per-row edge search is sharded over the mesh
      (distrib/hac_parallel.py) — the paper's PARABLE partition+align, with an
      O(log s) round guarantee. Same labels, bit-for-bit. ``sweep``/
      ``overlap`` pass through to ``boruvka_mst_distributed`` — the default
      ring-sharded sweep keeps per-device sample memory at O(s/P·d + c·d)
      instead of replicating the (s, d) sample each round."""
    xs = l2_normalize(xs)
    if hac == "boruvka":
        from repro.distrib.hac_parallel import single_link_labels_distributed

        labels = single_link_labels_distributed(
            mesh, axes, xs, k, impl=impl, sweep=sweep, overlap=overlap
        )
        centers_of = functools.partial(_label_centers, k=k, impl=impl)
        return _on_every_device(mesh, centers_of)(xs, labels)
    phase1 = functools.partial(_phase1_replicated, k=k, impl=impl)
    return _on_every_device(mesh, phase1)(xs)


def _label_centers(xs, labels, *, k: int, impl: str) -> jax.Array:
    """(k, d) unit centers of the labelled sample rows; empty labels give 0."""
    sums, counts = ops.label_stats(xs, labels, k, impl=impl)
    return jnp.where(counts[:, None] > 0, l2_normalize(sums), 0.0)


def _phase1_replicated(xs, *, k: int, impl: str) -> jax.Array:
    return _label_centers(
        xs, single_link_labels_boruvka(xs, k, impl=impl), k=k, impl=impl
    )


def _replicated_job(mesh: Mesh, fn):
    """``fn`` jitted to run whole on every device of the mesh, over
    replicated inputs, to a replicated result. XLA cannot partition a Pallas
    kernel, so replicated work on a mesh goes through ``shard_map`` with
    every spec replicated rather than through the automatic partitioner."""
    return jax.jit(
        jax.shard_map(fn, mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False)
    )


def _on_every_device(mesh: Mesh, fn):
    """``_replicated_job`` that first replicates its arguments on the mesh."""
    rep = NamedSharding(mesh, P())
    f = _replicated_job(mesh, fn)
    return lambda *args: f(*(jax.device_put(a, rep) for a in args))


@functools.lru_cache(maxsize=8)
def _center_index(mesh: Mesh):
    """``ops.build_center_index`` for centers that live replicated on the mesh."""
    return _on_every_device(mesh, ops.build_center_index)


def buckshot_distributed(
    mesh: Mesh,
    axes: tuple[str, ...],
    x: jax.Array,
    w: jax.Array,
    k: int,
    key: jax.Array,
    *,
    sample_size: int,
    kmeans_iters: int = 3,
    impl: str = "auto",
    hac: str = "replicated",
    sweep: str = "auto",
    overlap: bool = True,
    sample_rows: jax.Array | None = None,
    bounded: bool | None = None,
) -> DistClusterResult:
    """Buckshot: distributed sample -> single-link HAC -> 2-3 distributed
    K-Means iterations (phase-1 flavors: see ``_phase1_init_centers``;
    ``sweep``/``overlap`` tune the hac='boruvka' candidate sweep).

    ``sample_rows`` (s, d) overrides the internal sampler — parity harness
    hook shared with ``buckshot_distributed_stream``."""
    if sample_rows is None:
        sample_rows = sample_rows_distributed(mesh, axes, x, w, sample_size, key)
    init_centers = _phase1_init_centers(
        mesh, axes, sample_rows, k, impl=impl, hac=hac, sweep=sweep,
        overlap=overlap,
    )
    res = kmeans_distributed(
        mesh,
        axes,
        x,
        w,
        init_centers,
        k,
        max_iters=kmeans_iters,
        tol=0.0,
        impl=impl,
        bounded=bounded,
    )
    return res


# ------------------------------------------------------- streaming Buckshot


def reservoir_finalize_bytes(
    s: int, d: int, n_shards: int, *, owner_scatter: bool = True
) -> int:
    """Analytic wire bytes of the reservoir's finalize collective.

    owner_scatter (the shipped path): the (P·s,) f32 score vector is
    gathered whole (every device must rank identically), then the s winning
    payload rows — (d,) f32 row + i32 gidx each — move once from their owner
    shards. Legacy whole-payload gather: all P per-shard top-s candidate
    sets crossed the wire, rows included, before ranking:
    O(P·s + s·d) vs O(P·s·d)."""
    score_bytes = n_shards * s * 4
    if owner_scatter:
        return score_bytes + s * (d * 4 + 4)
    return score_bytes + n_shards * s * (d * 4 + 4)


def reservoir_sample_distributed_stream(
    mesh: Mesh,
    axes: tuple[str, ...],
    stream,
    s: int,
    key: jax.Array,
    *,
    checkpoint=None,
    guard=None,
) -> tuple[jax.Array, np.ndarray]:
    """Sharded ONE-pass uniform s-sample of a chunk stream, without
    replacement — the per-shard running top-s reservoir riding the engine's
    fold-mode 'topk' kind.

    Per chunk, every shard scores its local rows with iid uniforms (keyed
    ``fold_in(fold_in(key, chunk_index), shard)``; chunk-padding rows score
    -1 and lose to every real uniform) and emits its local top-s (score,
    global index, row) candidates; the fold carry keeps each shard's running
    top-s LOCALLY (top-s is a monoid — core/sampling.merge_top_s's argument,
    here across chunks AND shards), and the owner-scatter finalize picks the
    global top-s once at the end of the pass: ONE gather of the P·s SCORES
    ranks the winners identically on every device, then each owner shard
    contributes just its s winning rows (engine.FoldJob). Global top-s of
    iid uniforms is an exact uniform s-subset; the carry holds the rows
    themselves, so nothing revisits the stream. O(s·d) carry per shard; the
    finalize moves O(P·s + s·d) bytes instead of the O(P·s·d) whole-payload
    gather it replaced (``reservoir_finalize_bytes``).

    Returns (rows (s, d) replicated, global indices (s,) np.int32), in
    descending-score order — a uniformly shuffled order."""
    from repro.text.stream import run_pass, to_device  # lazy: keeps layering acyclic

    if s > stream.n:
        raise ValueError(f"sample size {s} exceeds stream rows {stream.n}")
    check_stream_shardable(stream, mesh, axes)
    n_shards = mesh_axis_size(mesh, axes)
    chunk_local = stream.chunk // n_shards

    meta = None
    if checkpoint is not None:
        from repro.resilience import array_token

        meta = {"key": array_token(jax.random.key_data(key)), "s": s}
        done = checkpoint.load_result("reservoir", meta=meta)
        if done is not None:
            return jnp.asarray(done["rows"]), np.asarray(done["gidx"])

    def sample_map(data, bcast):
        ws = data["w"]
        me = jax.lax.axis_index(axes)
        u = jax.random.uniform(jax.random.fold_in(bcast["key"], me), ws.shape)
        scores = jnp.where(ws > 0, u, -1.0)
        gidx = (
            bcast["start"]
            + me.astype(jnp.int32) * chunk_local
            + jnp.arange(chunk_local, dtype=jnp.int32)
        )
        rows = data["x"]
        if chunk_local < s:
            # pad the candidate set to s; fillers score below even the
            # chunk-pad sentinel, so they never survive a merge
            pad = s - chunk_local
            scores = jnp.concatenate(
                [scores, jnp.full((pad,), -2.0, jnp.float32)]
            )
            gidx = jnp.concatenate([gidx, jnp.full((pad,), -1, jnp.int32)])
            rows = jnp.concatenate(
                [rows, jnp.zeros((pad, rows.shape[1]), rows.dtype)]
            )
        top, pos = jax.lax.top_k(scores, s)
        return {"sample": {"score": top, "gidx": gidx[pos], "rows": rows[pos]}}

    job = make_fold_job(
        mesh, axes, sample_map, {"sample": "topk"}, name="sample_reservoir"
    )

    def fold(carry, ch, ci):
        x, w = to_device(ch)
        data = {
            "x": shard_rows(mesh, axes, x),
            "w": shard_rows(mesh, axes, w),
        }
        bcast = {
            "key": jax.random.fold_in(key, ci),
            "start": jnp.int32(ch.start),
        }
        carry, _ = job.step(carry, data, bcast)
        return carry

    carry = run_pass(
        stream,
        fold,
        None,
        pass_id="reservoir",
        checkpoint=checkpoint,
        guard=guard,
        meta=meta,
        restore_carry=lambda host: job.carry_device(host),
    )
    out = job.finalize(carry)["sample"]
    if checkpoint is not None:
        checkpoint.save_result(
            "reservoir",
            {"rows": np.asarray(out["rows"]), "gidx": np.asarray(out["gidx"])},
            meta=meta,
        )
    return out["rows"], np.asarray(out["gidx"])


def buckshot_distributed_stream(
    mesh: Mesh,
    axes: tuple[str, ...],
    stream,
    k: int,
    key: jax.Array,
    *,
    sample_size: int,
    kmeans_iters: int = 3,
    impl: str = "auto",
    hac: str = "replicated",
    sweep: str = "auto",
    overlap: bool = True,
    sample_rows: jax.Array | None = None,
    checkpoint=None,
    guard=None,
    bounded: bool | None = None,
) -> DistClusterResult:
    """Out-of-core distributed Buckshot — the last algorithm of the
    out-of-core distributed matrix.

    Phase 1's s = √(kn) sample comes from the sharded one-pass streaming
    reservoir (fold-mode 'topk' — one owner-scatter finalize for the whole
    sampling pass: scores gathered, winning rows moved once),
    the sample HAC runs matrix-free (``_phase1_init_centers``; under
    hac='boruvka' the default sharded sweep keeps its per-device sample
    state at O(s/P·d)), and phase 2 rides the streaming distributed
    K-Means fold (chunks sharded on arrival, k·d across the wire once per
    pass). Peak device residency O(chunk·d/P + s·d + k·d) at any n.

    Handed the same ``sample_rows``, assignments are identical to resident
    ``buckshot_distributed`` (tests/test_streaming.py)."""
    check_stream_shardable(stream, mesh, axes)
    if sample_rows is None:
        sample_rows, _ = reservoir_sample_distributed_stream(
            mesh, axes, stream, sample_size, key,
            checkpoint=checkpoint, guard=guard,
        )
    init_centers = _phase1_init_centers(
        mesh, axes, sample_rows, k, impl=impl, hac=hac, sweep=sweep,
        overlap=overlap,
    )
    result = kmeans_distributed_stream(
        mesh,
        axes,
        stream,
        init_centers,
        k,
        max_iters=kmeans_iters,
        tol=0.0,
        impl=impl,
        checkpoint=checkpoint.scoped("buckshot") if checkpoint is not None else None,
        guard=guard,
        bounded=bounded,
    )
    if checkpoint is not None:
        checkpoint.delete_result("reservoir")  # the run is over
    return result
