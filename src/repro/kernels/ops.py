"""Jit'd public wrappers for the kernel layer, with implementation dispatch.

``impl`` selects:
  - "xla":              pure-jnp (ref.py) path, compiled by XLA. Default on CPU.
  - "pallas":           Pallas TPU kernel (pl.pallas_call, Mosaic backend).
  - "pallas_interpret": Pallas kernel body executed by the interpreter on CPU —
                        used by tests to validate kernel logic without a TPU.
  - "auto":             "pallas" on TPU, "xla" elsewhere.

Core code imports ONLY from this module, never from the kernels directly.
"""

from __future__ import annotations

import functools
import os
import warnings
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels import ref


def _auto_impl() -> str:
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def _resolve(impl: str) -> str:
    return _auto_impl() if impl == "auto" else impl


# ------------------------------------------------------- pallas degradation
#
# A real Pallas lowering or compile failure ends the run: on the chip, a
# silent switch to XLA would time XLA and report it as the kernel. Only a
# fault injected through REPRO_FAULTS (testing/faults.py) degrades: the
# dispatch catches it, flips a once-per-process flag with a logged warning,
# and every subsequent trace takes the XLA path. tests/test_faults.py pins
# that contract: degraded results are identical to the XLA oracle.

_PALLAS_DEGRADED = False


def _reset_pallas_degradation() -> None:
    """Re-arm the Pallas path (test hook)."""
    global _PALLAS_DEGRADED
    _PALLAS_DEGRADED = False


def pallas_degraded() -> bool:
    return _PALLAS_DEGRADED


def _pallas_guard(name: str, pallas_call: Callable, xla_call: Callable):
    """Run the Pallas path of one op; an injected fault degrades to XLA once
    per process, any other failure propagates."""
    global _PALLAS_DEGRADED
    if _PALLAS_DEGRADED:
        return xla_call()
    from repro.testing import faults as _faults

    plan = _faults.active()
    if plan is not None:
        try:
            plan.pallas_fault()
        except _faults.InjectedFault as e:
            _PALLAS_DEGRADED = True
            warnings.warn(
                f"Pallas path failed in {name} ({e!r}); degrading to the XLA"
                " path for the rest of this process",
                RuntimeWarning,
                stacklevel=2,
            )
            return xla_call()
    return pallas_call()


# ---------------------------------------------------------------- assign


@functools.partial(jax.jit, static_argnames=("impl",))
def assign_argmax(
    x: jax.Array, centers: jax.Array, *, impl: str = "auto"
) -> tuple[jax.Array, jax.Array]:
    """(n,d),(k,d) -> ((n,) best center idx, (n,) best similarity)."""
    impl = _resolve(impl)
    if impl == "xla":
        return ref.assign_argmax(x, centers)

    def pallas():
        from repro.kernels import assign_argmax as kmod

        return kmod.assign_argmax_pallas(
            x, centers, interpret=impl == "pallas_interpret"
        )

    return _pallas_guard(
        "assign_argmax", pallas, lambda: ref.assign_argmax(x, centers)
    )


# ---------------------------------------------------------------- fused


class AssignStats(NamedTuple):
    """Everything one K-Means/BKC iteration needs, from ONE pass over x."""

    idx: jax.Array  # (n,) int32 nearest-center assignment
    best_sim: jax.Array  # (n,) f32 best similarity
    sums: jax.Array  # (k, d) f32 weighted per-cluster sums (CF1)
    counts: jax.Array  # (k,) f32 per-cluster weight totals
    min_sim: jax.Array  # (k,) f32 lowest member similarity (ref.BIG if empty)
    sumsq: jax.Array  # (k,) f32 weighted sum of squared row norms (CF2)


@functools.partial(jax.jit, static_argnames=("impl",))
def assign_stats(
    x: jax.Array,
    centers: jax.Array,
    w: jax.Array | None = None,
    *,
    impl: str = "auto",
) -> AssignStats:
    """Fused map+combine: assignment AND cluster statistics in one pass.

    The single-read replacement for assign_argmax + cluster_stats (+ the
    segment_sum/segment_min passes the BKC micro-cluster build used to make).
    ``w`` optionally weights rows; weight-0 rows are excluded everywhere.
    """
    impl = _resolve(impl)
    if impl == "xla":
        return AssignStats(*ref.assign_stats_scatter(x, centers, w))

    def pallas():
        from repro.kernels import assign_stats as kmod

        return AssignStats(
            *kmod.assign_stats_pallas(
                x, centers, w, interpret=impl == "pallas_interpret"
            )
        )

    return _pallas_guard(
        "assign_stats",
        pallas,
        lambda: AssignStats(*ref.assign_stats_scatter(x, centers, w)),
    )


def stats_identity(k: int, d: int) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Monoid identity for the carried (sums, counts, min_sim, sumsq) fold —
    the accumulator every streaming pass starts from."""
    return (
        jnp.zeros((k, d), jnp.float32),
        jnp.zeros((k,), jnp.float32),
        jnp.full((k,), ref.BIG, jnp.float32),
        jnp.zeros((k,), jnp.float32),
    )


def merge_stats(
    carry: tuple[jax.Array, jax.Array, jax.Array, jax.Array], st: "AssignStats"
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Fold one chunk's AssignStats into the carried accumulators (the monoid
    combine shared by assign_stats_chunked and every core streaming pass)."""
    sums, counts, min_sim, sumsq = carry
    return (
        sums + st.sums,
        counts + st.counts,
        jnp.minimum(min_sim, st.min_sim),
        sumsq + st.sumsq,
    )


@functools.partial(jax.jit, static_argnames=("chunk", "impl"))
def assign_stats_chunked(
    x: jax.Array,
    centers: jax.Array,
    w: jax.Array | None = None,
    *,
    chunk: int = 65_536,
    impl: str = "auto",
) -> AssignStats:
    """Streaming fused pass: scan over row blocks with carried accumulators.

    Runs n far beyond device memory at the same per-row cost as the one-shot
    kernel: each scan step reads one (chunk, d) block, issues the fused op,
    and folds (sums, counts, min_sim, sumsq) into the carry while stacking
    per-row (idx, best_sim). Rows padded to a chunk multiple carry weight 0.
    """
    n, d = x.shape
    k = centers.shape[0]
    if n <= chunk:
        return assign_stats(x, centers, w, impl=impl)

    wv = jnp.ones((n,), jnp.float32) if w is None else w.astype(jnp.float32)
    pad = (-n) % chunk
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad, d), x.dtype)])
        wv = jnp.concatenate([wv, jnp.zeros((pad,), jnp.float32)])
    xb = x.reshape(-1, chunk, d)
    wb = wv.reshape(-1, chunk)

    def body(carry, blk):
        st = assign_stats(blk["x"], centers, blk["w"], impl=impl)
        return merge_stats(carry, st), (st.idx, st.best_sim)

    (sums, counts, min_sim, sumsq), (idxs, sims) = jax.lax.scan(
        body, stats_identity(k, d), {"x": xb, "w": wb}
    )
    return AssignStats(
        idx=idxs.reshape(-1)[:n],
        best_sim=sims.reshape(-1)[:n],
        sums=sums,
        counts=counts,
        min_sim=min_sim,
        sumsq=sumsq,
    )


# ---------------------------------------------------------------- bounded


def bounds_enabled(flag: bool | None = None) -> bool:
    """Resolve the bound-pruned assignment default: an explicit flag wins;
    otherwise REPRO_ASSIGN_BOUNDS=1 turns it on process-wide (CI runs the
    fault-injection matrix once under it)."""
    if flag is not None:
        return bool(flag)
    return os.environ.get("REPRO_ASSIGN_BOUNDS", "") == "1"


class Bounds(NamedTuple):
    """Per-row Elkan/Hamerly carry for bound-pruned assignment.

    Lives in the streaming fold carry (host blocks between passes, device
    arrays inside one) — never as global (n, k) state. ``idx == -1`` marks
    the unknown sentinel (first pass, post-reseed invalidation, or a
    checkpoint-skipped iteration); sentinel rows always take the full sweep,
    so the bounds state is a pure performance hint.
    """

    idx: jax.Array  # (n,) int32 prior assignment; -1 = unknown
    lo: jax.Array  # (n,) f32 lower bound on sim(x, c_idx)
    hi: jax.Array  # (n,) f32 upper bound on sim(x, any OTHER center)


def bounds_identity(n: int) -> Bounds:
    """The unknown-sentinel Bounds every bounded pass can start from."""
    return Bounds(
        jnp.full((n,), -1, jnp.int32),
        jnp.full((n,), -ref.BIG, jnp.float32),
        jnp.full((n,), ref.BIG, jnp.float32),
    )


def bounds_invalidate(b: Bounds, rows: jax.Array) -> Bounds:
    """Force the unknown sentinel on a (n,) bool row mask (reseed guard)."""
    return Bounds(
        jnp.where(rows, -1, b.idx).astype(jnp.int32),
        jnp.where(rows, -ref.BIG, b.lo),
        jnp.where(rows, ref.BIG, b.hi),
    )


class CenterIndex(NamedTuple):
    """Two-level center index: a clustered ORDER over the centers.

    ``perm[slot] = original center id``: centers are permuted so that
    similar centers (same √k Lloyd group) sit in the same kernel slab; the
    Pallas path then bounds whole slabs with a cone bound and skips the ones
    that provably cannot hold the winner (see assign_stats.py). The index
    changes only the visit order — labels stay in ORIGINAL center ids and
    bit-identical to the flat sweep.
    """

    perm: jax.Array  # (k,) int32 original center id per slab-ordered slot
    group_of: jax.Array  # (k,) int32 Lloyd group of each original center


@functools.partial(jax.jit, static_argnames=("groups", "iters", "impl"))
def build_center_index(
    centers: jax.Array,
    *,
    groups: int | None = None,
    iters: int = 2,
    impl: str = "auto",
) -> CenterIndex:
    """Cluster the k centers into ~√k groups (mini-Lloyd over ``label_stats``)
    and emit the slab-ordered permutation. Deterministic: representatives
    start as a fixed stride of the centers (no RNG), ties break to the lowest
    index everywhere. Cost is O(k·g·d·iters) — noise next to one n·k·d
    assignment pass — so callers rebuild it after every center update.
    """
    k = centers.shape[0]
    g = groups if groups is not None else max(1, int(round(k ** 0.5)))
    arange_k = jnp.arange(k, dtype=jnp.int32)
    if g >= k:
        return CenterIndex(arange_k, arange_k)
    stride = -(-k // g)  # ceil
    reps = centers[::stride]
    g = reps.shape[0]
    cf = centers.astype(jnp.float32)
    for _ in range(iters):
        gidx, _ = ref.assign_argmax(cf, reps)
        sums, cnts = label_stats(cf, gidx, g, impl=impl)
        norm = jnp.sqrt(jnp.sum(sums * sums, axis=1, keepdims=True))
        reps = jnp.where(cnts[:, None] > 0, sums / jnp.maximum(norm, 1e-12), reps)
    gidx, _ = ref.assign_argmax(cf, reps)
    # stable (group, original id) order; values unique so argsort is exact
    perm = jnp.argsort(gidx * k + arange_k).astype(jnp.int32)
    return CenterIndex(perm, gidx.astype(jnp.int32))


class AssignStatsBounded(NamedTuple):
    """AssignStats + the refreshed bounds carry + the analytic prune mask."""

    idx: jax.Array  # (n,) int32 nearest-center assignment (original ids)
    best_sim: jax.Array  # (n,) f32 best similarity
    sums: jax.Array  # (k, d) f32 weighted per-cluster sums (CF1)
    counts: jax.Array  # (k,) f32 per-cluster weight totals
    min_sim: jax.Array  # (k,) f32 lowest member similarity (ref.BIG if empty)
    sumsq: jax.Array  # (k,) f32 weighted sum of squared row norms (CF2)
    bounds: Bounds  # refreshed carry, valid against THESE centers
    pruned: jax.Array  # (n,) bool — row skipped the full center sweep


def _pack_bounded(raw) -> AssignStatsBounded:
    idx, sim, sums, counts, min_sim, sumsq, bidx, lo, hi, pruned = raw
    return AssignStatsBounded(
        idx, sim, sums, counts, min_sim, sumsq, Bounds(bidx, lo, hi), pruned
    )


@functools.partial(jax.jit, static_argnames=("impl", "margin"))
def assign_stats_bounded(
    x: jax.Array,
    centers: jax.Array,
    bounds: Bounds,
    drift: jax.Array,
    w: jax.Array | None = None,
    *,
    index: CenterIndex | None = None,
    impl: str = "auto",
    margin: float = ref.PRUNE_MARGIN,
) -> AssignStatsBounded:
    """Bound-pruned fused map+combine: ``assign_stats`` with an Elkan/Hamerly
    carry that lets provably-settled rows skip the k-sweep.

    Labels are bit-identical to the brute-force oracle on every row and for
    ANY bounds state (sentinel included) — pruning fires only when the
    deflated bounds prove the winner unchanged. The XLA path still computes
    the full sweep (static shapes; it pays only bookkeeping) — real compute
    skipping is the Pallas path's block-level ``@pl.when``, optionally
    steered by a two-level ``CenterIndex``.
    """
    impl = _resolve(impl)

    def xla():
        return _pack_bounded(
            ref.assign_stats_bounded_scatter(
                x, centers, bounds.idx, bounds.lo, bounds.hi, drift, w,
                margin=margin,
            )
        )

    if impl == "xla":
        return xla()

    def pallas():
        from repro.kernels import assign_stats as kmod

        return _pack_bounded(
            kmod.assign_stats_bounded_pallas(
                x, centers, bounds.idx, bounds.lo, bounds.hi, drift, w,
                perm=None if index is None else index.perm,
                margin=margin,
                interpret=impl == "pallas_interpret",
            )
        )

    return _pallas_guard("assign_stats_bounded", pallas, xla)


@functools.partial(jax.jit, static_argnames=("chunk", "impl", "margin"))
def assign_stats_bounded_chunked(
    x: jax.Array,
    centers: jax.Array,
    bounds: Bounds,
    drift: jax.Array,
    w: jax.Array | None = None,
    *,
    chunk: int = 65_536,
    index: CenterIndex | None = None,
    impl: str = "auto",
    margin: float = ref.PRUNE_MARGIN,
) -> AssignStatsBounded:
    """Streaming bounded pass: scan over row blocks, bounds sliced per block.

    Chunking is bit-transparent for labels and bounds (every row's sweep is
    independent); the stats fold through the same monoid as
    ``assign_stats_chunked``. Rows padded to a chunk multiple carry weight 0
    and the unknown-bounds sentinel.
    """
    n, d = x.shape
    k = centers.shape[0]
    if n <= chunk:
        return assign_stats_bounded(
            x, centers, bounds, drift, w, index=index, impl=impl, margin=margin
        )

    wv = jnp.ones((n,), jnp.float32) if w is None else w.astype(jnp.float32)
    ident = bounds_identity((-n) % chunk)
    pad = (-n) % chunk
    bi, bl, bh = bounds
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad, d), x.dtype)])
        wv = jnp.concatenate([wv, jnp.zeros((pad,), jnp.float32)])
        bi = jnp.concatenate([bi, ident.idx])
        bl = jnp.concatenate([bl, ident.lo])
        bh = jnp.concatenate([bh, ident.hi])
    blocks = {
        "x": x.reshape(-1, chunk, d),
        "w": wv.reshape(-1, chunk),
        "bi": bi.reshape(-1, chunk),
        "bl": bl.reshape(-1, chunk),
        "bh": bh.reshape(-1, chunk),
    }

    def body(carry, blk):
        st = assign_stats_bounded(
            blk["x"], centers, Bounds(blk["bi"], blk["bl"], blk["bh"]),
            drift, blk["w"], index=index, impl=impl, margin=margin,
        )
        out = (st.idx, st.best_sim, st.bounds.lo, st.bounds.hi, st.pruned)
        return merge_stats(carry, st), out

    (sums, counts, min_sim, sumsq), (idxs, sims, los, his, prs) = jax.lax.scan(
        body, stats_identity(k, d), blocks
    )
    idx = idxs.reshape(-1)[:n]
    return AssignStatsBounded(
        idx=idx,
        best_sim=sims.reshape(-1)[:n],
        sums=sums,
        counts=counts,
        min_sim=min_sim,
        sumsq=sumsq,
        bounds=Bounds(idx, los.reshape(-1)[:n], his.reshape(-1)[:n]),
        pruned=prs.reshape(-1)[:n],
    )


# ---------------------------------------------------------------- label stats


@functools.partial(jax.jit, static_argnames=("k", "impl"))
def label_stats(
    x: jax.Array,
    idx: jax.Array,
    k: int,
    w: jax.Array | None = None,
    *,
    impl: str = "auto",
) -> tuple[jax.Array, jax.Array]:
    """(n,d),(n,)[,(n,)] -> ((k,d) weighted sums, (k,) weight totals).

    The labels-are-given combiner (Buckshot phase 1: HAC hands over labels, so
    there is no argmax to fuse — only the accumulator build). Out-of-range
    labels (e.g. -1 padding) and weight-0 rows contribute nothing. The Pallas
    path runs the same d-tiled accumulator grid the fused assign_stats kernel
    spills into, so k*d beyond one VMEM tile streams in (k, BD) blocks.
    """
    impl = _resolve(impl)
    if impl == "xla":
        return ref.label_stats_scatter(x, idx, k, w)

    def pallas():
        from repro.kernels import assign_stats as kmod

        return kmod.label_stats_pallas(
            x, idx, k, w, interpret=impl == "pallas_interpret"
        )

    return _pallas_guard(
        "label_stats", pallas, lambda: ref.label_stats_scatter(x, idx, k, w)
    )


# ---------------------------------------------------------------- best edge


@functools.partial(jax.jit, static_argnames=("impl",))
def best_edge(
    sim: jax.Array,
    labels_row: jax.Array,
    labels_col: jax.Array,
    *,
    impl: str = "auto",
) -> tuple[jax.Array, jax.Array]:
    """Per-row best cross-component edge (single-link / Boruvka inner step)."""
    impl = _resolve(impl)
    if impl == "xla":
        return ref.best_edge(sim, labels_row, labels_col)

    def pallas():
        from repro.kernels import best_edge as kmod

        return kmod.best_edge_pallas(
            sim, labels_row, labels_col, interpret=impl == "pallas_interpret"
        )

    return _pallas_guard(
        "best_edge", pallas, lambda: ref.best_edge(sim, labels_row, labels_col)
    )


# ---------------------------------------------------------------- fused sim+edge


@functools.partial(jax.jit, static_argnames=("impl", "block"))
def sim_best_edge(
    xs_rows: jax.Array,
    xs_all: jax.Array,
    labels_row: jax.Array,
    labels_col: jax.Array,
    *,
    impl: str = "auto",
    block: int = 1024,
) -> tuple[jax.Array, jax.Array]:
    """Matrix-free per-row best cross-component edge — sim build fused in.

    The single-pass replacement for ``xs_rows @ xs_all.T`` followed by
    ``best_edge``: the (r, c) similarity matrix never reaches HBM. The Pallas
    kernel folds MXU sim tiles into a VMEM-resident (max, argmax); the XLA
    fallback scans (block, c) row chunks, so peak memory is O(block * c)
    instead of O(r * c). Chunking is bit-transparent: every row's candidate
    search is independent, so chunked == one-shot exactly.
    """
    impl = _resolve(impl)

    def xla():
        r, d = xs_rows.shape
        if r <= block:
            return ref.sim_best_edge(xs_rows, xs_all, labels_row, labels_col)
        pad = (-r) % block
        xr = xs_rows
        lr = labels_row.astype(jnp.int32)
        if pad:
            xr = jnp.concatenate([xr, jnp.zeros((pad, d), xr.dtype)])
            lr = jnp.concatenate([lr, jnp.full((pad,), -1, jnp.int32)])
        xb = xr.reshape(-1, block, d)
        lb = lr.reshape(-1, block)

        def body(_, blk):
            bj, bs = ref.sim_best_edge(blk["x"], xs_all, blk["l"], labels_col)
            return None, (bj, bs)

        _, (js, ss) = jax.lax.scan(body, None, {"x": xb, "l": lb})
        return js.reshape(-1)[:r], ss.reshape(-1)[:r]

    if impl != "xla":

        def pallas():
            from repro.kernels import sim_best_edge as kmod

            return kmod.sim_best_edge_pallas(
                xs_rows, xs_all, labels_row, labels_col,
                interpret=impl == "pallas_interpret",
            )

        return _pallas_guard("sim_best_edge", pallas, xla)
    return xla()


# ---------------------------------------------------------------- component pre-reduce


@functools.partial(jax.jit, static_argnames=("c", "impl"))
def component_best_edge(
    row_w: jax.Array,
    row_j: jax.Array,
    rows: jax.Array,
    comp: jax.Array,
    c: int,
    *,
    impl: str = "auto",
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Per-shard Borůvka combiner: per-COMPONENT lexicographic best candidate.

    Folds a shard's per-row best-edge candidates into one (weight, row, col)
    triple per dense component id — ordered (w desc, row asc), exactly the
    winner ``core.hac._merge_round`` would pick — so only O(#components)
    values cross the shuffle instead of O(rows). Out-of-range comp ids (pad
    rows tagged ``c``) contribute nothing; empty segments get
    (f32.min, BIG_I, -1).

    The XLA path is three segment reductions (max on w, then min on row among
    the w-winners, then the unique winner's col) — O(r) scatter work, no sort.
    """
    impl = _resolve(impl)
    if impl != "xla":

        def pallas():
            from repro.kernels import component_reduce as kmod

            return kmod.component_best_edge_pallas(
                row_w, row_j, rows, comp, c,
                interpret=impl == "pallas_interpret",
            )

        return _pallas_guard(
            "component_best_edge",
            pallas,
            lambda: component_best_edge(row_w, row_j, rows, comp, c, impl="xla"),
        )
    neg = jnp.finfo(jnp.float32).min
    w = row_w.astype(jnp.float32)
    rows = rows.astype(jnp.int32)
    comp = comp.astype(jnp.int32)
    # segment_max fills empty segments with -inf; normalize to the NEG sentinel
    best_w = jnp.maximum(jax.ops.segment_max(w, comp, num_segments=c), neg)
    on_max = w == best_w[comp]
    best_row = jax.ops.segment_min(
        jnp.where(on_max, rows, ref.BIG_I), comp, num_segments=c
    )
    winner = jnp.logical_and(on_max, rows == best_row[comp])  # unique per segment
    best_j = jax.ops.segment_min(
        jnp.where(winner, row_j.astype(jnp.int32), ref.BIG_I),
        comp, num_segments=c,
    )
    return best_w, best_row, jnp.where(best_j == ref.BIG_I, -1, best_j)
