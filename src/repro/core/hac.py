"""Single-link hierarchical agglomerative clustering via MST (paper §4).

Single-link HAC is equivalent to building the maximum-similarity spanning tree
and cutting its k-1 weakest edges — that equivalence is what makes the paper's
PARABLE-style 'local dendrograms + alignment' parallelizable, and what we
exploit on TPU:

  * ``boruvka_mst`` / ``single_link_labels_boruvka``: the PRODUCTION path —
    matrix-free Borůvka over ops.sim_best_edge, O(log s) rounds, never
    materializing the (s, s) similarity matrix (DESIGN.md §8).
  * ``mst_prim`` / ``single_link_labels``: dense O(s^2) Prim inside jit —
    survives as the exact test oracle (and for callers that already hold a
    similarity matrix).
  * ``components_from_edges``: min-label propagation + pointer jumping over the
    kept forest edges (jit, while_loop).
  * distrib/hac_parallel.py lifts the per-round best-edge search onto the mesh
    (same merge machinery) — the TPU version of dendrogram alignment.

Tie handling (Borůvka): edges are totally ordered by (weight desc, row asc,
col asc), which makes each component's proposal unique, so the only duplicate
proposals are mutual pairs (dropped on the higher root). With that total order
Borůvka provably emits a max spanning FOREST of s-1 edges.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.common import l2_normalize
from repro.kernels import ops

NEG = jnp.finfo(jnp.float32).min


class MSTEdges(NamedTuple):
    u: jax.Array  # (E,) int32 row endpoint (global point id)
    v: jax.Array  # (E,) int32 col endpoint
    w: jax.Array  # (E,) f32 similarity
    valid: jax.Array  # (E,) bool — exactly s-1 True after a full run


@jax.jit
def mst_prim(sim: jax.Array) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Maximum spanning tree of a dense similarity matrix.

    Args:
      sim: (s, s) symmetric similarity (diagonal ignored).

    Returns:
      (eu, ev, ew): (s-1,) arrays — edge endpoints and similarities, in the
      order Prim added them.
    """
    s = sim.shape[0]
    sim = sim.astype(jnp.float32)
    in_tree = jnp.zeros((s,), bool).at[0].set(True)
    best_sim = sim[0].at[0].set(NEG)  # best similarity from each node to tree
    best_from = jnp.zeros((s,), jnp.int32)

    def body(i, carry):
        in_tree, best_sim, best_from, eu, ev, ew = carry
        cand = jnp.where(in_tree, NEG, best_sim)
        j = jnp.argmax(cand).astype(jnp.int32)
        eu = eu.at[i].set(best_from[j])
        ev = ev.at[i].set(j)
        ew = ew.at[i].set(cand[j])
        in_tree = in_tree.at[j].set(True)
        better = sim[j] > best_sim
        best_sim = jnp.where(better, sim[j], best_sim)
        best_from = jnp.where(better, j, best_from)
        return in_tree, best_sim, best_from, eu, ev, ew

    init = (
        in_tree,
        best_sim,
        best_from,
        jnp.zeros((s - 1,), jnp.int32),
        jnp.zeros((s - 1,), jnp.int32),
        jnp.zeros((s - 1,), jnp.float32),
    )
    _, _, _, eu, ev, ew = jax.lax.fori_loop(0, s - 1, body, init)
    return eu, ev, ew


@functools.partial(jax.jit, static_argnames=("n",))
def components_from_edges(
    n: int, eu: jax.Array, ev: jax.Array, mask: jax.Array
) -> jax.Array:
    """Min-id component labels of the graph with edges (eu[i], ev[i]) where
    mask[i]. Edges form a forest here, but the routine is general."""
    labels0 = jnp.arange(n, dtype=jnp.int32)
    big = jnp.int32(n)

    def cond(state):
        _, changed = state
        return changed

    def body(state):
        labels, _ = state
        lu = labels[eu]
        lv = labels[ev]
        m = jnp.where(mask, jnp.minimum(lu, lv), big)
        new = labels.at[eu].min(jnp.where(mask, m, big))
        new = new.at[ev].min(jnp.where(mask, m, big))
        new = jnp.minimum(new, new[new])  # pointer jumping
        return new, jnp.any(new != labels)

    labels, _ = jax.lax.while_loop(cond, body, (labels0, jnp.bool_(True)))
    return labels


@functools.partial(jax.jit, static_argnames=("k",))
def cut_forest(
    eu: jax.Array, ev: jax.Array, ew: jax.Array, n: int | jax.Array, k: int
) -> jax.Array:
    """Cut the k-1 weakest MST edges -> exactly k components; dense labels."""
    n = int(n) if not isinstance(n, jax.Array) else n
    order = jnp.argsort(-ew)  # strongest first; stable -> deterministic ties
    rank = jnp.argsort(order)  # rank[i] = position of edge i in that order
    keep = rank < (eu.shape[0] + 1 - k)  # keep s-k strongest of s-1 edges
    labels = components_from_edges(eu.shape[0] + 1, eu, ev, keep)
    # densify to [0, k)
    m = labels.shape[0]
    is_root = labels == jnp.arange(m, dtype=labels.dtype)
    dense = (jnp.cumsum(is_root.astype(jnp.int32)) - 1)[labels]
    return dense


@functools.partial(jax.jit, static_argnames=("k",))
def single_link_labels(sim: jax.Array, k: int) -> jax.Array:
    """Exact single-link HAC cut at k clusters for a dense similarity matrix."""
    eu, ev, ew = mst_prim(sim)
    return cut_forest(eu, ev, ew, sim.shape[0], k)


# ----------------------------------------------------------------- Borůvka
# Matrix-free production path: per round, every point finds its best
# cross-component edge via ops.sim_best_edge (the (s, s) similarity matrix
# never exists), then one replicated O(s) alignment merges components.


def _align_merge(
    labels: jax.Array,  # (s,) current component labels (min-id)
    eu: jax.Array,  # (s,) proposed edge row endpoint, slotted at the root id
    ev: jax.Array,  # (s,) proposed edge col endpoint
    ew: jax.Array,  # (s,) proposed edge weight (NEG where no proposal)
    propose: jax.Array,  # (s,) bool, True iff slot's root proposes an edge
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """Shared Borůvka alignment tail: mutual-edge dedupe + label propagation.

    Consumed by both winner-selection front ends: `_merge_round` (per-row
    candidates, replicated lexsort) and `_merge_round_pre` (pre-reduced
    per-component winners from the distributed combiner).
    """
    s = labels.shape[0]
    rows = jnp.arange(s, dtype=jnp.int32)
    target = labels[ev]  # component the edge lands in

    # mutual dedupe: if target proposes back to us with the same undirected
    # edge, keep only the lower root's copy.
    root = rows
    t_eu = eu[target]
    t_ev = ev[target]
    mutual_same = jnp.logical_and(t_eu == ev, t_ev == eu)
    drop = jnp.logical_and(
        jnp.logical_and(propose, propose[target]),
        jnp.logical_and(mutual_same, root > target),
    )
    evalid = jnp.logical_and(propose, ~drop)

    # merge: label propagation over the proposal edges (roots <-> targets)
    new_labels = components_from_edges(s, root, target, propose)
    # carry through to point level: every point takes its root's new label
    new_point_labels = new_labels[labels]
    return new_point_labels, eu, ev, ew, evalid


@jax.jit
def _merge_round(
    labels: jax.Array,  # (s,) current component labels (min-id)
    row_w: jax.Array,  # (s,) best cross-edge weight per row (NEG if none)
    row_j: jax.Array,  # (s,) best cross-edge col per row (-1 if none)
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """One Borůvka alignment: per-component best edge, dedupe, merge.

    Returns (new_labels, eu, ev, ew, evalid) with one slot per point id
    (slot c used iff c is a component root that proposed an edge).
    """
    s = labels.shape[0]
    rows = jnp.arange(s, dtype=jnp.int32)

    # per-component lexicographic best (w desc, row asc, col asc):
    # sort rows by (label asc, w desc, row asc); first row per label wins.
    # jnp.lexsort: LAST key is primary.
    order = jnp.lexsort((rows, -row_w, labels))
    lab_sorted = labels[order]
    first = jnp.concatenate(
        [jnp.ones((1,), bool), lab_sorted[1:] != lab_sorted[:-1]]
    )
    # winner row per component root: only first-per-label positions scatter
    # (others are redirected to the out-of-range slot and dropped)
    win_row = jnp.zeros((s,), jnp.int32).at[
        jnp.where(first, lab_sorted, s)
    ].set(order.astype(jnp.int32), mode="drop")

    has_edge = row_j[win_row] >= 0
    is_root = labels == rows
    propose = jnp.logical_and(is_root, has_edge)

    eu = jnp.where(propose, win_row, 0)
    ev = jnp.where(propose, row_j[win_row], 0)
    ew = jnp.where(propose, row_w[win_row], NEG)
    return _align_merge(labels, eu, ev, ew, propose)


@jax.jit
def _merge_round_pre(
    labels: jax.Array,  # (s,) current component labels (min-id)
    best_w: jax.Array,  # (c,) pre-reduced best weight per dense component
    best_row: jax.Array,  # (c,) winning global row id per dense component
    best_j: jax.Array,  # (c,) winning col per dense component (-1 if none)
    comp_to_root: jax.Array,  # (c,) dense component id -> root point id
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """Pre-reduced Borůvka alignment: the shuffle-light entry point.

    Consumes per-COMPONENT winners straight off the distributed combiner
    (`ops.component_best_edge` + the engine's 'component' reduce) instead of
    per-row candidates — no replicated O(s log s) lexsort, just an O(c)
    scatter into the point-id slot layout `_align_merge` expects. The winner
    ordering (w desc, row asc) is identical to `_merge_round`'s, so both
    entry points build the same forest.
    """
    s = labels.shape[0]
    has_edge = best_j >= 0
    slot = jnp.where(has_edge, comp_to_root, s)  # no-edge comps are dropped
    eu = jnp.zeros((s,), jnp.int32).at[slot].set(
        best_row.astype(jnp.int32), mode="drop"
    )
    ev = jnp.zeros((s,), jnp.int32).at[slot].set(
        jnp.maximum(best_j, 0).astype(jnp.int32), mode="drop"
    )
    ew = jnp.full((s,), NEG, jnp.float32).at[slot].set(best_w, mode="drop")
    propose = jnp.zeros((s,), bool).at[slot].set(has_edge, mode="drop")
    return _align_merge(labels, eu, ev, ew, propose)


@functools.partial(jax.jit, static_argnames=("next_cap",))
def _merge_round_comp(
    best_w: jax.Array,  # (cap,) pre-reduced best weight per dense component
    best_row: jax.Array,  # (cap,) winning global row id per dense component
    best_j: jax.Array,  # (cap,) winning col per dense component (-1 if none)
    best_tcomp: jax.Array,  # (cap,) dense component id of the winning col
    comp_to_root: jax.Array,  # (cap,) dense component id -> root point id
    n_real: jax.Array,  # () real component count entering the round (<= cap)
    *,
    next_cap: int,  # halving bound entering the NEXT round
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array, jax.Array,
           jax.Array]:
    """Component-graph Borůvka alignment — the pod-scale merge entry point.

    ``_merge_round_pre`` still walks POINT-level state: an O(s) scatter into
    point-id slots plus label propagation over s nodes, replicated every
    round. This variant never touches an (s,) array: the proposal graph has
    one node per DENSE component, so dedupe + propagation + densify all run
    on (cap,) arrays with cap following the Borůvka halving bound. Point
    labels are updated afterwards by a single shard-local gather through the
    returned ``relabel`` map (distrib/hac_parallel), so per-device label
    state stays O(s/P) and only c-sized arrays ever cross the wire.

    Parity: old dense ids are root-point-id ranks (cumsum order), so the
    min-OLD-DENSE-id group representative IS the min-root-point-id
    representative `_align_merge` picks, the mutual-edge dedupe compares the
    same point-level endpoints, and the re-densified ids keep root order —
    expanded through `_expand_round_edges` the forest is bit-identical to
    the point-level path.

    The halving bound usually exceeds the live component count, so slots
    [n_real, cap) are PHANTOM ids: their segments are empty (the reduce
    emits (NEG, BIG_I, -1) — no proposal), they stay isolated singletons
    through the merge, and because every real id is smaller than every
    phantom id the cumsum densify ranks real roots first — real new ids are
    exactly the ids `_round_prep` would assign. ``n_real`` threads the live
    count through so termination never mistakes phantoms for components.

    Returns (relabel (cap,) old dense -> new dense id, new_comp_to_root
    (next_cap,), eu, ev, ew, evalid (cap,) compact edge slots indexed by OLD
    dense id, n_real scalar LIVE component count after the merge).
    """
    cap = best_w.shape[0]
    u = jnp.arange(cap, dtype=jnp.int32)
    propose = best_j >= 0
    target = jnp.where(propose, best_tcomp, u)

    # mutual dedupe on the POINT-level endpoints, same rule as _align_merge:
    # if the target proposes back the same undirected edge, the higher old
    # dense id (== higher root point id — dense ids are root ranks) drops.
    t_eu = best_row[target]
    t_ev = best_j[target]
    mutual_same = jnp.logical_and(t_eu == best_j, t_ev == best_row)
    drop = jnp.logical_and(
        jnp.logical_and(propose, propose[target]),
        jnp.logical_and(mutual_same, u > target),
    )
    evalid = jnp.logical_and(propose, ~drop)

    eu = jnp.where(propose, best_row, 0).astype(jnp.int32)
    ev = jnp.where(propose, jnp.maximum(best_j, 0), 0).astype(jnp.int32)
    ew = jnp.where(propose, best_w, NEG)

    # merge + densify on the COMPONENT graph (cap nodes, not s)
    group = components_from_edges(cap, u, target, propose)  # min old dense id
    is_root = group == u
    dense = jnp.cumsum(is_root.astype(jnp.int32)) - 1  # rank of each new root
    relabel = dense[group]
    new_root = jnp.zeros((next_cap,), jnp.int32).at[
        jnp.where(is_root, dense, next_cap)
    ].set(comp_to_root, mode="drop")
    n_real_new = jnp.sum(jnp.logical_and(is_root, u < n_real)).astype(
        jnp.int32
    )
    return relabel, new_root, eu, ev, ew, evalid, n_real_new


def _expand_round_edges(
    slots: jax.Array | int,  # (s,) template array OR the slot count itself
    eu: jax.Array,  # (cap,) compact edge slots, indexed by dense comp id
    ev: jax.Array,
    ew: jax.Array,
    evalid: jax.Array,
    comp_to_root: jax.Array,  # (cap,) dense comp id -> root point id
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Scatter one round's compact (cap,) edges into the (s,) point-id slot
    layout `_merge_round_pre` emits — the bit-parity bridge between the
    component-level and point-level merge paths (tests + cut compatibility).

    ``slots`` may be the slot COUNT instead of a template array: the sharded
    sweep (DESIGN.md §16) keeps no replicated (s,) point-level array at all,
    so there is nothing to pass but the number itself.
    """
    s = slots if isinstance(slots, int) else slots.shape[0]
    return _expand_round_edges_n(eu, ev, ew, evalid, comp_to_root, s=s)


@functools.partial(jax.jit, static_argnames=("s",))
def _expand_round_edges_n(
    eu: jax.Array,
    ev: jax.Array,
    ew: jax.Array,
    evalid: jax.Array,
    comp_to_root: jax.Array,
    *,
    s: int,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    propose = ew > NEG
    slot = jnp.where(propose, comp_to_root, s)
    eu_s = jnp.zeros((s,), jnp.int32).at[slot].set(eu, mode="drop")
    ev_s = jnp.zeros((s,), jnp.int32).at[slot].set(ev, mode="drop")
    ew_s = jnp.full((s,), NEG, jnp.float32).at[slot].set(ew, mode="drop")
    valid_s = jnp.zeros((s,), bool).at[slot].set(evalid, mode="drop")
    return eu_s, ev_s, ew_s, valid_s


@functools.partial(jax.jit, static_argnames=("cap",))
def _round_prep(
    labels: jax.Array, cap: int
) -> tuple[jax.Array, jax.Array]:
    """Dense component ids for one Borůvka round.

    Labels are min-id (sparse in [0, s)); the combiner and the 'component'
    reduce want DENSE ids so the per-round arrays are O(cap), where cap is
    the Borůvka halving bound ceil(s / 2^round) >= #components.

    Returns (comp (s,) dense id per point, comp_to_root (cap,) dense id ->
    root point id).
    """
    s = labels.shape[0]
    rows = jnp.arange(s, dtype=jnp.int32)
    is_root = labels == rows
    dense = jnp.cumsum(is_root.astype(jnp.int32)) - 1  # rank of each root
    comp = dense[labels]
    comp_to_root = jnp.zeros((cap,), jnp.int32).at[
        jnp.where(is_root, dense, cap)
    ].set(rows, mode="drop")
    return comp, comp_to_root


def _rounds_for(s: int) -> int:
    return max(1, math.ceil(math.log2(max(s, 2)))) + 1


@functools.partial(jax.jit, static_argnames=("impl", "block"))
def boruvka_mst(
    xs: jax.Array, *, impl: str = "auto", block: int = 1024
) -> MSTEdges:
    """Max spanning forest of the cosine graph of xs (s, d) — single device.

    O(log s) rounds of the fused sim+best-edge search; each round is one
    matrix-free pass (peak memory O(s*d + block*s), never O(s^2)). The round
    loop is a while_loop with an early exit once everything has merged into
    one component, so typical inputs run well under the _rounds_for bound.
    """
    s = xs.shape[0]
    xs = l2_normalize(xs)
    rounds = _rounds_for(s)

    def cond(state):
        r, labels, *_ = state
        # labels are min-id: a single component means everyone carries 0
        return jnp.logical_and(r < rounds, ~jnp.all(labels == 0))

    def body(state):
        r, labels, eu, ev, ew, evalid = state
        bj, bw = ops.sim_best_edge(
            xs, xs, labels, labels, impl=impl, block=block
        )
        labels, u, v, w, valid = _merge_round(labels, bw, bj.astype(jnp.int32))
        return (
            r + 1,
            labels,
            eu.at[r].set(u),
            ev.at[r].set(v),
            ew.at[r].set(w),
            evalid.at[r].set(valid),
        )

    init = (
        jnp.int32(0),
        jnp.arange(s, dtype=jnp.int32),
        jnp.zeros((rounds, s), jnp.int32),
        jnp.zeros((rounds, s), jnp.int32),
        jnp.full((rounds, s), NEG, jnp.float32),
        jnp.zeros((rounds, s), bool),
    )
    _, _, eu, ev, ew, evalid = jax.lax.while_loop(cond, body, init)
    return MSTEdges(
        u=eu.reshape(-1), v=ev.reshape(-1), w=ew.reshape(-1),
        valid=evalid.reshape(-1),
    )


@functools.partial(jax.jit, static_argnames=("k", "n"))
def cut_mst_edges(edges: MSTEdges, n: int, k: int) -> jax.Array:
    """Single-link labels at k clusters from a masked MST edge set.

    Keeps the n-k strongest valid edges (the k-1 weakest merges are undone),
    then labels connected components — dense ids in [0, k).

    Only the first n-k slots of the stable strongest-first order can be
    kept, so the labelling runs over those slots alone, not over every slot
    of the (rounds·s) edge history. Invalid slots sort last and stay masked
    where they still fall among them.
    """
    neg = float(jnp.finfo(jnp.float32).min)
    w = jnp.where(edges.valid, edges.w, neg)
    top = jnp.argsort(-w)[: max(n - k, 0)]
    labels = components_from_edges(
        n, edges.u[top], edges.v[top], edges.valid[top]
    )
    is_root = labels == jnp.arange(n, dtype=labels.dtype)
    return (jnp.cumsum(is_root.astype(jnp.int32)) - 1)[labels]


def single_link_labels_boruvka(
    xs: jax.Array, k: int, *, impl: str = "auto"
) -> jax.Array:
    """Drop-in equivalent of single_link_labels, matrix-free Borůvka-style."""
    edges = boruvka_mst(xs, impl=impl)
    return cut_mst_edges(edges, xs.shape[0], k)
