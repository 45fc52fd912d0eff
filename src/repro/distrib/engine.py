"""MapReduce-on-JAX: the paper's execution model as a shard_map combinator.

A job is `map_combine` (runs per shard: the paper's map task + combiner) plus a
per-output reduction kind (the shuffle+reduce):

  'sum' / 'min' / 'max'  -> jax.lax.psum / pmin / pmax over the data axes
                            (replicated result on every device)
  'shard'                -> stays sharded like the input rows (e.g. per-doc
                            assignment labels)
  'component'            -> segmented lexicographic best-edge merge: the leaf
                            is a {'w', 'row', 'col'} dict of per-shard
                            per-component winners; three pmax/pmin passes pick
                            the global (w desc, row asc) winner per segment —
                            O(#components) wire traffic, never O(rows). On a
                            multi-axis (pod, data) mesh the passes run per
                            tier, innermost first: intra-pod links resolve
                            each pod's winner before the c-sized per-pod
                            winners cross pods (bit-identical to the flat
                            reduce; see _component_reduce).

Reduce kinds may sit at any PREFIX of the output pytree (a single kind can
cover a whole subtree — 'component' relies on this to see its w/row/col
triple together). Fold mode (FoldJob below) additionally supports the
'topk' running-reservoir kind, which has a chunk monoid but no one-shot
reduce; make_job rejects it.

The combiner discipline is what made PKMeans efficient on Hadoop and is what
keeps the ICI traffic at O(k*d) here: map_combine must aggregate locally before
the reduction kind crosses shards.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.distrib.sharding import data_spec, ring_permutation


def _component_reduce(v: dict, axes) -> dict:
    """Cross-shard fold of per-component best edges, (w desc, row asc).

    Each shard contributes its local winner per dense component id
    (ops.component_best_edge output; empty segments carry (f32.min, BIG_I,
    -1), which lose every comparison). Global row ids are unique across
    shards, so after the (w, row) fold the winner is unique and every other
    leaf of the subtree — 'col' plus any extra int32 payload such as the
    sharded sweep's 'tcomp' target-component id — follows by one more pmin
    each: O(#components) collectives replace the O(rows) per-row gather.

    The fold runs PER MESH AXIS, innermost first: on a (pod, data) mesh the
    'data' tier resolves each pod's winner over the fast intra-pod links,
    and only then do the c-sized per-pod winners cross pods. Because the
    (w desc, row asc) order is total (rows globally unique) the sequential
    per-axis fold is bit-identical to the joint reduce over all axes — the
    tiering changes where the bytes flow, not the answer.
    """
    big_i = jnp.iinfo(jnp.int32).max
    payload = [k for k in v if k not in ("w", "row")]
    for ax in reversed(axes):  # innermost axis = intra-pod tier goes first
        w = jax.lax.pmax(v["w"], ax)
        on_max = v["w"] == w
        row = jax.lax.pmin(jnp.where(on_max, v["row"], big_i), ax)
        mine = jnp.logical_and(on_max, v["row"] == row)
        out = {"w": w, "row": row}
        for k in payload:
            pk = jax.lax.pmin(jnp.where(mine, v[k], big_i), ax)
            out[k] = jnp.where(pk == big_i, -1, pk)
        v = out
    return v


_REDUCERS: dict[str, Callable[[Any, Any], Any]] = {
    "sum": jax.lax.psum,
    "min": jax.lax.pmin,
    "max": jax.lax.pmax,
    # 'gather': concatenate per-shard results (replicated) — used when the
    # reducer needs all candidates (e.g. distributed top-s sampling).
    "gather": lambda v, axes: jax.lax.all_gather(v, axes, tiled=True),
    "component": _component_reduce,
}


def make_job(
    mesh: Mesh,
    axes: tuple[str, ...],
    map_combine: Callable,
    reduce_kinds: Any,
    *,
    name: str = "job",
) -> Callable:
    """Build a jitted MapReduce job.

    Args:
      mesh: device mesh.
      axes: mesh axis name(s) the data rows are sharded over.
      map_combine: (data_shard_pytree, bcast_pytree) -> out_pytree. Runs on each
        shard; must do its own local aggregation (the combiner).
      reduce_kinds: pytree PREFIX of out_pytree with
        'sum'|'min'|'max'|'gather'|'component'|'shard' string leaves; a kind
        covers the whole out subtree below it ('component' expects a
        {'w','row','col'} dict there).
      name: debugging label.

    Returns:
      jitted fn (data_pytree, bcast_pytree) -> out_pytree. Data arrays are
      sharded on dim 0; bcast arrays are replicated.
    """
    flat_kinds, kinds_def = jax.tree_util.tree_flatten(reduce_kinds)
    bad = sorted({k for k in flat_kinds if k != "shard" and k not in _REDUCERS})
    if bad:
        raise ValueError(
            f"make_job supports {sorted(_REDUCERS)}/shard reduce kinds"
            f" ('topk' is fold-mode only), got {bad}"
        )

    def inner(data, bcast):
        out = map_combine(data, bcast)
        # reduce_kinds is a prefix tree: each kind leaf reduces its whole out
        # subtree (psum-family collectives accept pytrees).
        out_parts = kinds_def.flatten_up_to(out)
        reduced = [
            part if kind == "shard" else _REDUCERS[kind](part, axes)
            for part, kind in zip(out_parts, flat_kinds)
        ]
        return jax.tree_util.tree_unflatten(kinds_def, reduced)

    # PartitionSpec need not enumerate trailing dims: P(axes) shards dim 0 and
    # replicates the rest, so specs derive purely from pytree structure.
    out_specs = jax.tree_util.tree_map(
        lambda kind: P(axes) if kind == "shard" else P(), reduce_kinds
    )

    @jax.jit
    def run(data, bcast):
        in_specs = (
            jax.tree_util.tree_map(lambda _: P(axes), data),
            jax.tree_util.tree_map(lambda _: P(), bcast),
        )
        # check_vma=False: the 'gather' reducer (all_gather tiled) produces
        # replicated values that the static VMA inference cannot prove.
        f = jax.shard_map(
            inner, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
        )
        return f(data, bcast)

    run.__name__ = f"mr_job_{name}"
    return run


def run_job(
    mesh: Mesh,
    axes: tuple[str, ...],
    map_combine: Callable,
    reduce_kinds: Any,
    data: Any,
    bcast: Any = (),
    *,
    name: str = "job",
) -> Any:
    """One-shot convenience wrapper around make_job."""
    return make_job(mesh, axes, map_combine, reduce_kinds, name=name)(data, bcast)


# ------------------------------------------------------- sharded-bcast path


def ring_sweep(
    axes_sizes: tuple[tuple[str, int], ...],
    block: Any,
    fold: Callable[[Any, Any], Any],
    acc: Any,
    *,
    overlap: bool = True,
) -> Any:
    """Visit every shard's row block of a dim-0-sharded pytree via nested
    ppermute rings — the sharded-bcast data path (DESIGN.md §16).

    Runs INSIDE a shard_map body. ``block`` is this shard's resident slice of
    the sharded pytree; instead of replicating the full array to all shards
    (the O(s·d) broadcast this combinator exists to kill), the blocks rotate
    through the shards and ``fold(acc, visiting_block)`` consumes each one as
    it arrives. Per-device residency never exceeds a few block slices; total
    wire traffic equals one all-to-all of the sharded array, but as P
    point-to-point hops of O(s/P·d) each instead of a P-way O(s·d) broadcast.

    ``axes_sizes`` is ((axis, size), ...) OUTERMOST first (sharding.tier
    order). On a (pod, data) mesh the traversal nests: the inner 'data' ring
    rotates a COPY of the current panel around the pod's fast links, and
    between inner rings the pristine panel rotates once across pods — each
    device sees all P blocks after n_pods·pod_size fold steps.

    ``overlap=True`` issues the next rotation BEFORE folding the block in
    hand (the §11 double-buffered prefetch discipline applied to
    collectives): the cross-pod panel exchange of outer step t is dispatched
    while the whole intra-pod ring of step t computes, and each intra-pod
    hop overlaps the previous block's fold. ``overlap=False`` threads the
    accumulator through an optimization_barrier ahead of every rotation, so
    the exchange cannot be scheduled before the fold completes. Both
    schedules fold the same values in the same per-device order — callers
    whose fold is order-independent (e.g. a total-order running max) get
    bit-identical results with overlap on or off, which the pod-scale tests
    enforce.
    """
    if not axes_sizes:
        return fold(acc, block)
    (ax, size), rest = axes_sizes[0], axes_sizes[1:]
    perm = ring_permutation(size)
    tmap = jax.tree_util.tree_map
    cur = block
    for step in range(size):
        last = step == size - 1
        if not last and overlap:
            nxt = tmap(lambda v: jax.lax.ppermute(v, ax, perm), cur)
        acc = ring_sweep(rest, cur, fold, acc, overlap=overlap)
        if not last and not overlap:
            # serialize: the rotation's operand now depends on the fold
            # result, so the exchange cannot overlap the compute
            cur, acc = jax.lax.optimization_barrier((cur, acc))
            nxt = tmap(lambda v: jax.lax.ppermute(v, ax, perm), cur)
        if not last:
            cur = nxt
    return acc


# --------------------------------------------------------------- fold mode

_MONOID: dict[str, Callable[[Any, Any], Any]] = {
    "sum": jnp.add,
    "min": jnp.minimum,
    "max": jnp.maximum,
}


def _topk_merge(a: dict, b: dict) -> dict:
    """Chunk monoid of the 'topk' fold kind: top-s (by the 'score' leaf) of
    the union of two fixed-size candidate sets. Every other leaf in the
    subtree is payload, carried along axis 0 — top_s(A ∪ B) =
    top_s(top_s(A) ∪ top_s(B)), the same monoid as core/sampling.merge_top_s.
    """
    s = a["score"].shape[0]
    _, pos = jax.lax.top_k(jnp.concatenate([a["score"], b["score"]]), s)
    return jax.tree_util.tree_map(
        lambda av, bv: jnp.concatenate([av, bv])[pos], a, b
    )


def _component_merge(a: dict, b: dict) -> dict:
    """Chunk monoid of the 'component' fold kind: per-segment lexicographic
    best of two {'w','row','col'} winner sets, (w desc, row asc). Global row
    ids are unique, so the order is total and the merge associative — the
    per-shard carry holds the running winner locally and finalize reuses the
    tiered `_component_reduce` as its single collective pass.
    """
    take_b = jnp.logical_or(
        b["w"] > a["w"],
        jnp.logical_and(b["w"] == a["w"], b["row"] < a["row"]),
    )
    return jax.tree_util.tree_map(
        lambda av, bv: jnp.where(take_b, bv, av), a, b
    )


def _check_component(subtree: Any) -> None:
    if not (
        isinstance(subtree, dict) and {"w", "row", "col"} <= set(subtree)
    ):
        raise ValueError(
            "'component' fold kind expects a dict subtree with at least"
            " {'w','row','col'} per-segment winners"
            " (ops.component_best_edge layout, extra int32 payload leaves"
            f" allowed), got {type(subtree).__name__}"
        )


def _check_topk(subtree: Any) -> None:
    if not (isinstance(subtree, dict) and "score" in subtree):
        raise ValueError(
            "'topk' fold kind expects a dict subtree with a 'score' leaf"
            " (plus payload arrays aligned on axis 0), got"
            f" {type(subtree).__name__}"
        )
    s = subtree["score"].shape[0]
    for path, leaf in jax.tree_util.tree_flatten_with_path(subtree)[0]:
        if leaf.shape[:1] != (s,):
            # enforce here: a misaligned payload would otherwise survive the
            # merge as clamped-gather garbage instead of an error
            raise ValueError(
                "'topk' payload leaves must share the score leaf's axis-0"
                f" length {s}; leaf {jax.tree_util.keystr(path)} has shape"
                f" {leaf.shape}"
            )


class FoldJob:
    """Streaming fold mode of a MapReduce job (out-of-core chunk streams).

    ``make_job`` maps ONE resident data pytree and reduces immediately;
    a FoldJob consumes a SEQUENCE of same-shaped chunks:

      step(carry, data_chunk, bcast) -> (carry, shard_outs)
          map the chunk per shard and merge the monoid partials into the
          per-shard carry LOCALLY — no collective touches the wire here.
          ``carry=None`` starts a fold. 'shard'-kind outputs pass through
          per chunk (sharded like the chunk rows); fold-kind positions in
          ``shard_outs`` are None.
      finalize(carry) -> out
          ONE collective pass (psum/pmin/pmax) over the carried per-shard
          partials. 'shard' positions in the result are None.

    This is the paper's combiner discipline lifted across chunks: a mapper
    folds every split it is handed before anything shuffles, so the wire cost
    of an entire multi-chunk pass equals that of one resident job. Fold mode
    supports 'sum' | 'min' | 'max' | 'topk' | 'component' (+ 'shard'
    passthrough); only 'gather' has no chunk-monoid form.

    'component' carries each shard's running per-segment best edge (the
    (w desc, row asc) winner of a {'w','row','col'} subtree — a total order
    since rows are globally unique, hence a monoid) and finalizes with the
    same tiered `_component_reduce` the one-shot job uses, so streaming
    drivers get the hierarchical intra-pod/cross-pod reduce for free.

    'topk' is the running-reservoir kind: the subtree must be a dict with a
    'score' leaf of fixed size s (plus payload leaves aligned on axis 0 —
    e.g. global row indices and the rows themselves). Each chunk the map
    emits s candidates per shard; the carry keeps the shard's running top-s
    LOCALLY (top-s is a monoid), and finalize owner-scatters: ONE gather of
    the P·s SCORES ranks the winners identically everywhere, then each owner
    shard psum-contributes just its s winning payload rows — O(P·s + s·d)
    wire instead of the O(P·s·d) whole-payload gather, still one collective
    pass for the whole stream. This is how the distributed Buckshot sample
    reservoir rides fold mode (distrib/cluster).

    The carry is a tuple of (P, ...) arrays sharded over ``axes`` — shard p's
    running partial lives in slice p and never moves between devices until
    finalize.
    """

    def __init__(
        self,
        mesh: Mesh,
        axes: tuple[str, ...],
        map_combine: Callable,
        reduce_kinds: Any,
        *,
        name: str = "fold",
    ):
        flat_kinds, kinds_def = jax.tree_util.tree_flatten(reduce_kinds)
        bad = sorted(
            {
                k
                for k in flat_kinds
                if k not in ("shard", "topk", "component", *_MONOID)
            }
        )
        if bad:
            raise ValueError(
                "fold mode supports sum/min/max/topk/component/shard reduce"
                f" kinds, got {bad}"
            )
        fold_kinds = [k for k in flat_kinds if k != "shard"]
        self.name = name
        self.mesh = mesh
        self.axes = axes

        def split(out):
            parts = kinds_def.flatten_up_to(out)
            folds = tuple(p for p, k in zip(parts, flat_kinds) if k != "shard")
            shards = jax.tree_util.tree_unflatten(
                kinds_def,
                [p if k == "shard" else None for p, k in zip(parts, flat_kinds)],
            )
            return folds, shards

        # kinds are a pytree PREFIX (same as make_job): each fold entry may
        # cover a whole out subtree, so carries/merges tree_map over it
        tmap = jax.tree_util.tree_map

        def inner_first(data, bcast):
            folds, shards = split(map_combine(data, bcast))
            for f, k in zip(folds, fold_kinds):
                if k == "topk":
                    _check_topk(f)
                elif k == "component":
                    _check_component(f)
            return tuple(tmap(lambda v: v[None], f) for f in folds), shards

        def merge_fold(c, f, k):
            if k == "topk":  # joint merge across the subtree, not leafwise
                merged = _topk_merge(tmap(lambda cv: cv[0], c), f)
                return tmap(lambda v: v[None], merged)
            if k == "component":  # joint: selector reads w/row together
                merged = _component_merge(tmap(lambda cv: cv[0], c), f)
                return tmap(lambda v: v[None], merged)
            return tmap(lambda cv, fv, op=_MONOID[k]: op(cv[0], fv)[None], c, f)

        def inner_step(carry, data, bcast):
            folds, shards = split(map_combine(data, bcast))
            carry = tuple(
                merge_fold(c, f, k)
                for c, f, k in zip(carry, folds, fold_kinds)
            )
            return carry, shards

        axis_sizes = tuple(mesh.shape[a] for a in axes)

        def topk_finalize(v):
            # owner-scatter finalize: only the (P·s,) SCORE vector is gathered
            # whole — every device ranks it identically (top_k is
            # deterministic) and decodes winner -> (owner shard, local slot).
            # Each owner then contributes exactly its winning payload rows
            # into a psum, zeros elsewhere: one nonzero addend per output slot
            # makes the sum an exact move, bit-identical to gathering all
            # payloads and indexing. Wire: O(P·s) score + O(s·d) payload,
            # replacing the O(P·s·d) whole-subtree gather.
            s = v["score"].shape[0]
            g_score = jax.lax.all_gather(v["score"], axes, tiled=True)
            top, pos = jax.lax.top_k(g_score, s)
            owner = pos // s  # all_gather tiles shards in flat axis order
            local = pos % s
            me = jnp.int32(0)
            for ax, size in zip(axes, axis_sizes):
                me = me * size + jax.lax.axis_index(ax)
            mine = owner == me

            def collect(x):
                rows = x[jnp.where(mine, local, 0)]
                keep = mine.reshape(mine.shape + (1,) * (rows.ndim - 1))
                return jax.lax.psum(jnp.where(keep, rows, 0), axes)

            out = tmap(collect, v)
            out["score"] = top  # already exact from the ranked gather
            return out

        def inner_finalize(carry):
            # psum-family collectives accept pytrees, so a subtree reduces whole
            reduced = iter(
                topk_finalize(tmap(lambda cv: cv[0], c))
                if k == "topk"
                else _REDUCERS[k](tmap(lambda cv: cv[0], c), axes)
                for c, k in zip(carry, fold_kinds)
            )
            return jax.tree_util.tree_unflatten(
                kinds_def,
                [None if k == "shard" else next(reduced) for k in flat_kinds],
            )

        shard_specs = jax.tree_util.tree_unflatten(
            kinds_def, [P(axes) if k == "shard" else None for k in flat_kinds]
        )
        carry_spec = tuple(P(axes) for _ in fold_kinds)

        def data_specs(data, bcast):
            return (
                jax.tree_util.tree_map(lambda _: P(axes), data),
                jax.tree_util.tree_map(lambda _: P(), bcast),
            )

        @jax.jit
        def first(data, bcast):
            f = jax.shard_map(
                inner_first,
                mesh=mesh,
                in_specs=data_specs(data, bcast),
                out_specs=(carry_spec, shard_specs),
                check_vma=False,
            )
            return f(data, bcast)

        @jax.jit
        def step(carry, data, bcast):
            f = jax.shard_map(
                inner_step,
                mesh=mesh,
                in_specs=(carry_spec, *data_specs(data, bcast)),
                out_specs=(carry_spec, shard_specs),
                check_vma=False,
            )
            return f(carry, data, bcast)

        @jax.jit
        def finalize(carry):
            f = jax.shard_map(
                inner_finalize,
                mesh=mesh,
                in_specs=(carry_spec,),
                out_specs=jax.tree_util.tree_unflatten(
                    kinds_def,
                    [None if k == "shard" else P() for k in flat_kinds],
                ),
                check_vma=False,
            )
            return f(carry)

        self._first, self._step, self._finalize = first, step, finalize

    def step(self, carry, data, bcast):
        """Fold one chunk; ``carry=None`` opens the fold."""
        if carry is None:
            return self._first(data, bcast)
        return self._step(carry, data, bcast)

    def finalize(self, carry):
        """One collective pass over the carried per-shard partials."""
        if carry is None:
            raise ValueError("finalize before any step: empty stream")
        return self._finalize(carry)

    def carry_device(self, host_carry):
        """Place a host-restored fold carry back onto the mesh.

        A checkpointed fold carry is a tuple of (P, ...) per-shard partials;
        restoring it on the default device would feed ``step`` a carry whose
        sharding disagrees with ``carry_spec``. This is the ``restore_carry``
        hook for run_pass: every leaf goes back to rows-sharded-over-``axes``
        placement, so the resumed fold is indistinguishable from one that
        never stopped."""
        from jax.sharding import NamedSharding

        from repro.resilience import carry_from_host

        def put(v):
            a = jnp.asarray(v)
            return jax.device_put(
                a, NamedSharding(self.mesh, data_spec(self.axes, a.ndim))
            )

        return carry_from_host(host_carry, device_put=put)


def make_fold_job(
    mesh: Mesh,
    axes: tuple[str, ...],
    map_combine: Callable,
    reduce_kinds: Any,
    *,
    name: str = "fold",
) -> FoldJob:
    """Streaming fold mode: map each chunk, combine monoid partials locally,
    one collective at the end (see FoldJob). Supports
    sum/min/max/topk/component/shard reduce kinds."""
    return FoldJob(mesh, axes, map_combine, reduce_kinds, name=name)
