"""Parallel single-link HAC via Borůvka MST over shard_map (paper §4.2.1).

The paper parallelizes HAC PARABLE-style: random partitions -> local
dendrograms -> dendrogram alignment. For single-link, the dendrogram IS the
maximum spanning tree, and 'local clustering + alignment' is exactly one
Borůvka round: every component finds its best outgoing edge locally, and the
merge step aligns them globally. Borůvka gives the same fixpoint with an
O(log s) round guarantee, so that is the TPU-native form (DESIGN.md §2, §8).

The single-device machinery (merge round, edge cut, matrix-free candidate
search) lives in core/hac.py — this module only lifts the per-round edge
search onto the mesh:

Layout: each device owns a ROW BLOCK of the (s, s) similarity matrix, which
never exists anywhere — not even per shard: ops.sim_best_edge folds the MXU
similarity tiles straight into a per-row (max, argmax). Under the default
SHARDED sweep (DESIGN.md §16) the columns are sharded too: each device keeps
only its (s/P, d) slice resident and block copies rotate through the mesh via
nested per-axis ppermute rings, so no (s, d) broadcast ever lands anywhere —
per-device point memory is O(s/P·d + c·d), with c the halving component cap.
``sweep='bcast'`` keeps the replicated-columns sweep (s = sqrt(kn) is small
next to the collection, but its (s, d) broadcast is the first thing to hit a
per-device memory wall — benchmarks/run.py phase1_sharded). Per round:

  map     : per-row best cross-component edge on the local rows
            (kernels.ops.sim_best_edge — fused sim build+mask+rowmax+argmax);
            sharded sweep: a ring fold of the visiting column blocks keeping
            the (w desc, global col asc) winner — bit-identical to the
            replicated argmax, overlap=True prefetches the next hop
  combine : per-shard per-COMPONENT pre-reduce (ops.component_best_edge) —
            of the shard's O(s/P) candidates only O(#components) can survive
            the merge, so only those leave the shard (the paper's combiner
            discipline applied to the edge search, DESIGN.md §9)
  reduce  : the engine's 'component' fold — three O(#components) collectives
            pick the global (w desc, row asc) winner per component, TIERED
            on a pod mesh: intra-pod links resolve each pod's winner before
            the c-sized per-pod winners cross pods (DESIGN.md §15)
  merge   : mutual-edge dedupe + label propagation on the pre-reduced
            winners. merge='comp' (default) runs the whole alignment on the
            COMPONENT graph (core.hac._merge_round_comp) — O(cap) dedupe,
            pointer jumping, and densify, point state touched only through
            an elementwise relabel gather; merge='point' is the replicated
            (s,)-slot alignment (core.hac._merge_round_pre), kept for
            parity and benches

Component ids are DENSIFIED each round and capped by the Borůvka halving
bound ceil(s / 2^round), so the per-round shuffle SHRINKS geometrically:
O(s·P) bytes per round under the old per-row gather, O(c·P) now — split
per tier by ``shuffle_bytes_per_tier``. The fully-merged check is computed
on device every round but the host syncs on it only every ``check_every``
rounds, so rounds keep streaming to the device without a per-round host
round-trip; a late exit is bounded at check_every - 1 no-op rounds and the
executed round count is deterministic.

``pre_reduce=False`` keeps the legacy per-row gather path for benchmarking
the shuffle win (benchmarks/run.py phase1_distributed rows), and
``synthetic_merge_rounds`` isolates the merge subsystem at sample sizes
where the replicated point-level path exceeds any fixed memory budget
(benchmarks/run.py phase1_merge rows).

The replicated sample is PADDED to a shard multiple (paper-default s rarely
divides a 3-device mesh): pad rows carry label -1, which the edge-search
kernels mask out of the map itself (they propose nothing), and component id
== cap, which the segmented pre-reduce drops — nothing is sliced after the
reduce because pad rows never produce candidates in the first place.
"""

from __future__ import annotations

import atexit
import functools
import math
import os
import threading

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.common import l2_normalize
from repro.core.hac import (  # noqa: F401  (re-exported: historical home)
    MSTEdges,
    _expand_round_edges,
    _merge_round,
    _merge_round_comp,
    _merge_round_pre,
    _round_prep,
    _rounds_for,
    boruvka_mst,
    cut_mst_edges,
    single_link_labels_boruvka,
)
from repro.distrib.engine import make_job, ring_sweep
from repro.distrib.sharding import (
    mesh_axis_size,
    ring_block_rows,
    shard_rows,
    tier_sizes,
)
from repro.resilience.checkpoint import carry_to_host
from repro.kernels import ops
from repro.kernels.ref import BIG_I as _BIG_I


def round_cap(s: int, r: int) -> int:
    """Borůvka halving bound: #components entering round r is <= ceil(s/2^r).

    Every component with any cross edge merges with at least one other per
    round, and on a complete similarity graph every component has a cross
    edge until a single component remains.
    """
    return max(1, math.ceil(s / (1 << r)))


@functools.lru_cache(maxsize=32)
def _cand_job(
    mesh: Mesh, tiers: tuple[int, ...], axes: tuple[str, ...], impl: str,
    mode: str, overlap: bool = False,
):
    """Cached per-(mesh, tiers, axes, impl, mode, overlap) candidate job:
    host-chained rounds re-enter the same jitted shard_map instead of
    re-tracing per call. The cache is BOUNDED (long-lived serve processes
    that reshape meshes must not leak one compiled job per topology forever)
    and ``clear_job_caches`` empties it explicitly.

    ``tiers`` (sharding.tier_sizes) is the explicit tier topology — a mesh
    reshaped over the same devices (flat (8,) -> pod (2, 4)) lowers DIFFERENT
    collectives for the tiered 'component' reduce AND a different ring
    schedule for the sharded sweep, so the topology must be part of the
    cache identity rather than an implicit property of the Mesh hash.
    Modes: 'comp_sharded' (ring-sharded sweep — no (s, d) xs broadcast,
    blocks rotate via engine.ring_sweep; ``overlap`` selects the
    double-buffered exchange schedule and is part of the identity because it
    changes the lowered program), 'comp' (replicated sweep, dense component
    ids end-to-end, compact merge), 'pre' (point labels + per-component
    pre-reduce), 'rowgather' (legacy per-row gather).
    """

    def cand_map(data, bcast):
        bj, bw = ops.sim_best_edge(
            data["rows"], bcast["xs"], data["labels"], bcast["all_labels"],
            impl=impl,
        )
        return {"j": bj.astype(jnp.int32), "w": bw}

    def cand_map_pre(data, bcast):
        bj, bw = ops.sim_best_edge(
            data["rows"], bcast["xs"], data["labels"], bcast["all_labels"],
            impl=impl,
        )
        bj = bj.astype(jnp.int32)
        cap = bcast["comp_to_root"].shape[0]
        s = bcast["xs"].shape[0]
        if cap == s:
            # round 0: every point is its own component, so the segmented
            # reduce is the identity — scatter each row's candidate straight
            # into its component slot (pad rows carry comp == cap: dropped)
            slot = data["comp"]
            neg = float(jnp.finfo(jnp.float32).min)
            w = jnp.full((cap,), neg, jnp.float32).at[slot].set(
                bw, mode="drop")
            row = jnp.full((cap,), _BIG_I, jnp.int32).at[slot].set(
                data["rowid"], mode="drop")
            col = jnp.full((cap,), -1, jnp.int32).at[slot].set(
                bj, mode="drop")
        else:
            w, row, col = ops.component_best_edge(
                bw, bj, data["rowid"], data["comp"], cap, impl=impl,
            )
        return {"best": {"w": w, "row": row, "col": col}}

    def cand_map_comp(data, bcast):
        # dense comp ids double as the masking labels: they induce the same
        # same-component partition as min-id point labels, so the edge search
        # is unchanged — but no point-label array exists anywhere. Pad rows
        # carry comp == -1 (kernels mask them out of the map itself); the
        # segmented reduce needs them redirected to the dropped segment cap
        # instead (negative segment ids are unsafe in XLA segment/scatter
        # ops).
        comp = data["comp"]
        bj, bw = ops.sim_best_edge(
            data["rows"], bcast["xs"], comp, bcast["comp_all"], impl=impl,
        )
        bj = bj.astype(jnp.int32)
        cap = bcast["comp_to_root"].shape[0]
        s = bcast["xs"].shape[0]
        seg = jnp.where(comp < 0, cap, comp)
        if cap == s:
            neg = float(jnp.finfo(jnp.float32).min)
            w = jnp.full((cap,), neg, jnp.float32).at[seg].set(
                bw, mode="drop")
            row = jnp.full((cap,), _BIG_I, jnp.int32).at[seg].set(
                data["rowid"], mode="drop")
            col = jnp.full((cap,), -1, jnp.int32).at[seg].set(
                bj, mode="drop")
        else:
            w, row, col = ops.component_best_edge(
                bw, bj, data["rowid"], seg, cap, impl=impl,
            )
        return {"best": {"w": w, "row": row, "col": col}}

    def cand_map_comp_sharded(data, bcast):
        # Ring-sharded sweep (DESIGN.md §16): no (s, d) xs broadcast and no
        # (s,) comp broadcast exist anywhere. Each shard holds one (B, d) row
        # block plus its rowid/comp slices; COPIES of the blocks rotate
        # through the mesh via engine.ring_sweep while the resident slice
        # stays put, so per-device point data is O(s/P·d) and the only
        # replicated per-round state is the (cap,) comp_to_root map. The fold
        # keeps the per-row running (w desc, global col asc) winner — the
        # same total order the replicated argmax resolves ties by — so the
        # result is bit-identical to cand_map_comp regardless of visit order.
        # The winner's TARGET COMPONENT id rides along as reduce payload
        # because no replicated comp array exists to look it up in later.
        comp = data["comp"]
        rowid = data["rowid"]
        cap = bcast["comp_to_root"].shape[0]
        b = comp.shape[0]
        neg = float(jnp.finfo(jnp.float32).min)
        acc0 = {
            "w": jnp.full((b,), neg, jnp.float32),
            "col": jnp.full((b,), _BIG_I, jnp.int32),
            "tcomp": jnp.full((b,), -1, jnp.int32),
        }
        block = {"rows": data["rows"], "rowid": rowid, "comp": comp}

        def fold(acc, vis):
            # vis comp carries -1 on pad rows: the kernels mask those columns
            # out of the map itself (negative col labels = padding contract)
            bj, bw = ops.sim_best_edge(
                data["rows"], vis["rows"], comp, vis["comp"], impl=impl,
            )
            bj = bj.astype(jnp.int32)
            safe = jnp.maximum(bj, 0)
            gcol = jnp.where(bj >= 0, vis["rowid"][safe], _BIG_I)
            tc = jnp.where(bj >= 0, vis["comp"][safe], -1)
            take = jnp.logical_or(
                bw > acc["w"],
                jnp.logical_and(bw == acc["w"], gcol < acc["col"]),
            )
            return {
                "w": jnp.where(take, bw, acc["w"]),
                "col": jnp.where(take, gcol, acc["col"]),
                "tcomp": jnp.where(take, tc, acc["tcomp"]),
            }

        axes_sizes = tuple(zip(axes, tiers))
        acc = ring_sweep(axes_sizes, block, fold, acc0, overlap=overlap)
        bw = acc["w"]
        bj = jnp.where(acc["col"] == _BIG_I, -1, acc["col"])
        seg = jnp.where(comp < 0, cap, comp)
        w, row, col = ops.component_best_edge(
            bw, bj, rowid, seg, cap, impl=impl,
        )
        # same (w, rowid, seg) keys -> same per-segment winner: the second
        # call only swaps the rider payload (target comp instead of col)
        _, _, tcomp = ops.component_best_edge(
            bw, acc["tcomp"], rowid, seg, cap, impl=impl,
        )
        return {"best": {"w": w, "row": row, "col": col, "tcomp": tcomp}}

    if mode == "comp_sharded":
        return make_job(
            mesh, axes, cand_map_comp_sharded, {"best": "component"},
            name="boruvka_cand_ring",
        )
    if mode == "comp":
        return make_job(
            mesh, axes, cand_map_comp, {"best": "component"},
            name="boruvka_cand_compid",
        )
    if mode == "pre":
        return make_job(
            mesh, axes, cand_map_pre, {"best": "component"},
            name="boruvka_cand_comp",
        )
    if mode != "rowgather":
        raise ValueError(f"unknown candidate-job mode {mode!r}")
    return make_job(
        mesh, axes, cand_map, {"j": "shard", "w": "shard"},
        name="boruvka_cand",
    )


@functools.lru_cache(maxsize=32)
def _relabel_job(mesh: Mesh, tiers: tuple[int, ...], axes: tuple[str, ...]):
    """Shard-local component relabel after a comp-mode merge: each device
    gathers its O(s/P) comp slice through the c-sized ``relabel`` broadcast.
    Only the (cap,) relabel map crosses the wire — per-device label state
    never leaves O(s/P), which is the whole point of the sharded merge."""
    del tiers  # cache-key only (see _cand_job)

    def relabel_map(data, bcast):
        comp = data["comp"]
        new = bcast["relabel"][jnp.maximum(comp, 0)]
        return {"comp": jnp.where(comp < 0, -1, new)}

    return make_job(
        mesh, axes, relabel_map, {"comp": "shard"}, name="comp_relabel"
    )


# ------------------------------------------------------- async shape pre-warm
#
# The pre-reduce path's per-round arrays are sized by the halving cap, so each
# round is a DISTINCT jit specialization of the candidate job — O(log s)
# shapes. Paying those compiles inside the host-chained round loop serializes
# compile behind compute; instead ONE background worker AOT-compiles the round
# shapes IN ROUND ORDER, kicked off before round 1 executes, so round r+1's
# compile overlaps round r's execution (XLA compilation releases the GIL).
# Round order + cancellation matter: the early exit typically stops well
# before the _rounds_for bound, and eagerly compiling every bound shape would
# burn cores on rounds that never run — when the loop exits, still-pending
# shapes are cancelled. Compiled executables are cached per
# (mesh, axes, impl, s, d, pad, cap), so repeated calls (bench best-of-N,
# phase 1 inside a fitted driver) compile once.

_WARM: dict = {}  # insertion-ordered; oldest completed entries evicted
_WARM_CAP = 128  # executables are MBs each; s = sqrt(kn) varies per corpus
_WARM_ROUNDS_HINT: dict = {}  # (mesh,axes,impl,s,d,pad) -> rounds last run:
# the early exit usually stops well short of the _rounds_for bound, so
# repeats pre-warm only to the observed depth (+ slack) instead of
# re-submitting cancelled never-executed shapes every call
_WARM_LOCK = threading.Lock()
_WARM_WORKERS: set = set()  # live worker threads, joined at interpreter exit


def _evict_warm_locked(keep: set) -> None:
    """Drop oldest COMPLETED cache entries beyond _WARM_CAP (caller holds
    _WARM_LOCK); in-flight slots and ``keep`` keys stay."""
    if len(_WARM) <= _WARM_CAP:
        return
    for key in list(_WARM):
        if len(_WARM) <= _WARM_CAP:
            break
        slot = _WARM[key]
        if key not in keep and slot._ev.is_set():
            del _WARM[key]


@atexit.register
def _drain_warm_workers() -> None:  # pragma: no cover — exit path
    """Join in-flight compile workers before the interpreter tears down:
    a daemon thread killed inside an XLA compile aborts the process. Cancel
    leaves each worker at most one compile from exit, so this is bounded."""
    with _WARM_LOCK:
        workers = list(_WARM_WORKERS)
        for slot in _WARM.values():
            slot.cancelled = True
    for t in workers:
        t.join()


def _auto_prewarm() -> bool:
    """Default for ``prewarm=None``: the compile worker only helps when it
    can run on cores the round execution is not saturating."""
    return (os.cpu_count() or 1) >= 4


def _compile_timeout() -> float | None:
    """Watchdog on waiting for a pre-warmed compile (``REPRO_COMPILE_TIMEOUT``
    seconds; unset/empty = wait indefinitely, the seed behavior)."""
    env = os.environ.get("REPRO_COMPILE_TIMEOUT", "").strip()
    if not env:
        return None
    try:
        t = float(env)
    except ValueError:
        raise ValueError(
            f"REPRO_COMPILE_TIMEOUT={env!r}: expected seconds (float)"
        ) from None
    return t if t > 0 else None


class _WarmSlot:
    """A minimal cancellable future (daemon worker + event — no executor, so
    interpreter exit never blocks on queued compiles)."""

    __slots__ = ("_ev", "value", "key", "started", "cancelled")

    def __init__(self, key):
        self._ev = threading.Event()
        self.value = None
        self.key = key
        self.started = False
        self.cancelled = False

    def result(self, timeout: float | None = None):
        """The compiled executable, or None — on cancellation, compile
        failure, or a worker wedged past ``timeout`` seconds (the round loop
        then falls back to the plain jitted call instead of hanging the pass
        behind a stuck compile)."""
        if not self._ev.wait(timeout):
            return None
        return self.value


def _cancel_pending(slots: list["_WarmSlot"]) -> None:
    """Cancel compiles that have not started (early exit left them unneeded);
    a cancelled slot resolves to None (jit fallback) and leaves the cache so
    a later call can resubmit the shape."""
    with _WARM_LOCK:
        for slot in slots:
            if slot._ev.is_set() or slot.started:
                continue
            slot.cancelled = True
            slot._ev.set()
            if _WARM.get(slot.key) is slot:
                del _WARM[slot.key]


def clear_job_caches() -> None:
    """Drop every cached candidate/relabel job AND the AOT round-executable
    table. The job caches are bounded (lru), but bounded is not zero: a
    long-lived serve process that is done with a mesh topology can release
    the compiled programs (MBs each) and the Mesh objects they pin
    explicitly instead of waiting for eviction. Pending background compiles
    are cancelled; one already inside XLA finishes and is then dropped."""
    with _WARM_LOCK:
        slots = list(_WARM.values())
    _cancel_pending(slots)
    with _WARM_LOCK:
        _WARM.clear()
        _WARM_ROUNDS_HINT.clear()
    _cand_job.cache_clear()
    _relabel_job.cache_clear()


def _round_structs(
    mesh, axes, s: int, d: int, pad: int, cap: int, mode: str = "pre"
):
    """Abstract (data, bcast) arguments of one round's candidate job, with
    EXPLICIT shardings (rows sharded over ``axes``, broadcast replicated) —
    both the AOT lowering and the per-round ``device_put`` placement use
    these, so the compiled executable and the runtime arrays always agree."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.distrib.sharding import data_spec

    f32, i32 = jnp.float32, jnp.int32

    def sd(shape, dtype, sharded):
        spec = data_spec(axes, len(shape)) if sharded else P()
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh, spec)
        )

    if mode in ("comp", "comp_sharded"):
        data = {
            "rows": sd((s + pad, d), f32, True),
            "rowid": sd((s + pad,), i32, True),
            "comp": sd((s + pad,), i32, True),
        }
        if mode == "comp_sharded":
            # the whole point of the ring sweep: the ONLY replicated
            # argument is the (cap,) comp_to_root map
            return data, {"comp_to_root": sd((cap,), i32, False)}
        bcast = {
            "xs": sd((s, d), f32, False),
            "comp_all": sd((s,), i32, False),
            "comp_to_root": sd((cap,), i32, False),
        }
        return data, bcast
    data = {
        "rows": sd((s + pad, d), f32, True),
        "labels": sd((s + pad,), i32, True),
        "rowid": sd((s + pad,), i32, True),
        "comp": sd((s + pad,), i32, True),
    }
    bcast = {
        "xs": sd((s, d), f32, False),
        "all_labels": sd((s,), i32, False),
        "comp_to_root": sd((cap,), i32, False),
    }
    return data, bcast


def _place_round_args(mesh, axes, data: dict, bcast: dict):
    """Commit one round's arrays to the shardings the AOT executable was
    compiled with (no-op when already placed)."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.distrib.sharding import data_spec

    data = {
        k: jax.device_put(
            v, NamedSharding(mesh, data_spec(axes, jnp.ndim(v)))
        )
        for k, v in data.items()
    }
    rep = NamedSharding(mesh, P())
    bcast = {k: jax.device_put(v, rep) for k, v in bcast.items()}
    return data, bcast


def _compile_candidate_round(
    job, mesh, axes, s: int, d: int, pad: int, cap: int, mode: str = "pre"
):
    """AOT-compile the pre-reduce candidate job for one round's shapes.

    Returns the compiled executable, or None when this backend cannot AOT
    round-trip it — the round loop then falls back to the plain jitted call,
    which compiles synchronously exactly as before the pre-warm existed."""
    try:
        data, bcast = _round_structs(mesh, axes, s, d, pad, cap, mode)
        return job.lower(data, bcast).compile()
    except Exception:  # pragma: no cover — backend-specific AOT gaps
        return None


def prewarm_candidate_rounds(
    mesh: Mesh,
    axes: tuple[str, ...],
    impl: str,
    *,
    s: int,
    d: int,
    pad: int,
    rounds: int,
    mode: str = "comp",
    overlap: bool = False,
) -> list[_WarmSlot]:
    """Kick off background compilation of the candidate-job round shapes
    (the ROADMAP 'pre-warm the round shapes asynchronously' item): one
    daemon worker compiles them in ROUND ORDER. Returns one slot per round;
    ``slot.result()`` blocks only until THAT round's compile lands.

    Cache keys carry the explicit tier topology (``sharding.tier_sizes``)
    alongside the Mesh: a reshape of the same devices into a different
    pod layout lowers different collectives, and a stale flat-mesh
    executable must never serve a pod-mesh call (or vice versa). They also
    carry the sweep ``mode`` and the ``overlap`` schedule — the ring sweep's
    overlap=True/False programs differ (double-buffered ppermute vs
    barrier-serialized), so each is its own executable identity."""
    tiers = tier_sizes(mesh, axes)
    job = _cand_job(mesh, tiers, axes, impl, mode, overlap)
    slots = []
    todo = []
    with _WARM_LOCK:
        keys = set()
        for r in range(rounds):
            cap = round_cap(s, r)
            key = (mesh, tiers, axes, impl, mode, overlap, s, d, pad, cap)
            keys.add(key)
            slot = _WARM.get(key)
            if slot is None:
                slot = _WarmSlot(key)
                _WARM[key] = slot
                todo.append((slot, cap))
            slots.append(slot)
        _evict_warm_locked(keys)
    if todo:

        def worker():
            try:
                for slot, cap in todo:
                    with _WARM_LOCK:  # started/cancelled handshake with
                        if slot.cancelled:  # _cancel_pending is atomic
                            continue
                        slot.started = True
                    try:
                        slot.value = _compile_candidate_round(
                            job, mesh, axes, s, d, pad, cap, mode
                        )
                    finally:
                        slot._ev.set()
            finally:
                for slot, _ in todo:  # a dead worker must never strand a
                    slot._ev.set()  # waiter: unresolved slots -> jit fallback
                with _WARM_LOCK:
                    _WARM_WORKERS.discard(threading.current_thread())

        t = threading.Thread(target=worker, daemon=True, name="boruvka-prewarm")
        with _WARM_LOCK:
            _WARM_WORKERS.add(t)
        t.start()
    return slots


def shuffle_bytes_per_round(
    s: int, n_shards: int, rounds: int, *, pre_reduce: bool = True
) -> list[int]:
    """Analytic per-round shuffle footprint of the candidate exchange.

    pre_reduce: each shard contributes one (w f32, row i32, col i32) triple
    per component, capped by the halving bound — O(c·P) bytes, shrinking
    geometrically. Legacy per-row gather: every shard's (j i32, w f32) pair
    for every row crosses shards every round — O(s·P) bytes, constant.
    """
    if pre_reduce:
        return [n_shards * round_cap(s, r) * 12 for r in range(rounds)]
    return [n_shards * s * 8 for _ in range(rounds)]


def shuffle_bytes_per_tier(
    s: int, tiers: tuple[int, ...], rounds: int, *, merge: str = "comp"
) -> dict[str, list[int]]:
    """Analytic per-round shuffle footprint of the tiered candidate exchange.

    ``tiers`` is sharding.tier_sizes output, outermost first — (n_pods,
    pod_size) on a pod mesh, (P,) on a flat one. Per round the 'component'
    reduce moves one (w f32, row i32, col i32) triple per component per
    participating shard, per tier:

      intra: within each pod, pod_size shards exchange cap-sized triples
             over the fast links — n_pods · pod_size · cap · 12 bytes.
      cross: only the per-pod winners cross pods — n_pods · cap · 12 bytes.

    A flat mesh has no intra tier (zeros) and all P shards on the cross
    tier — the pod layout's headline is the cross-tier column shrinking
    from P·cap·12 to n_pods·cap·12. merge='comp' additionally broadcasts
    the (cap,) relabel map back to the shards each round (cross tier,
    4 bytes per entry); merge='point' rebuilds point labels replicated
    instead (no per-shard relabel traffic, but O(s) state per device).
    """
    if len(tiers) == 1:
        intra_shards = 0  # single tier: everything is the cross exchange
        cross_shards = tiers[0]
    else:
        intra_shards = int(math.prod(tiers))  # every shard, intra-pod links
        cross_shards = int(math.prod(tiers[:-1]))  # one winner set per pod
    intra, cross = [], []
    for r in range(rounds):
        cap = round_cap(s, r)
        intra.append(intra_shards * cap * 12)
        relabel = cap * 4 if merge == "comp" else 0
        cross.append(cross_shards * cap * 12 + relabel)
    return {"intra": intra, "cross": cross}


def bcast_bytes_per_round(
    s: int, d: int, n_shards: int, rounds: int, *,
    sweep: str = "sharded", merge: str = "comp",
) -> list[int]:
    """Analytic per-round bytes REPLICATED onto the shards by the candidate
    sweep — the broadcast the sharded sweep exists to kill (DESIGN.md §16).

    sweep='bcast': every round lands the full (s, d) f32 xs, the (s,) i32
    comp labels, and the (cap,) i32 comp_to_root on ALL n_shards devices —
    n_shards·(s·d·4 + s·4 + cap·4) bytes per round, CONSTANT in r up to the
    shrinking cap term. This is the O(s·d) replication wall the phase1_sharded
    bench drives into an rlimit.

    sweep='sharded': xs never replicates (blocks rotate peer-to-peer — that
    traffic is the ring's shuffle, not broadcast); the only replicated
    per-round state is the (cap,) comp_to_root in and, under merge='comp',
    the (cap,) relabel map back — n_shards·(1 or 2)·cap·4 bytes, HALVING
    with the Borůvka bound.
    """
    if sweep not in ("sharded", "bcast"):
        raise ValueError(f"sweep must be 'sharded' or 'bcast', got {sweep!r}")
    out = []
    for r in range(rounds):
        cap = round_cap(s, r)
        if sweep == "bcast":
            out.append(n_shards * (s * d * 4 + s * 4 + cap * 4))
        else:
            relabel = cap * 4 if merge == "comp" else 0
            out.append(n_shards * (cap * 4 + relabel))
    return out


def sweep_peak_bytes_per_device(
    s: int, d: int, n_shards: int, *, sweep: str = "sharded",
    overlap: bool = True,
) -> int:
    """Analytic peak per-device residency of one candidate round's POINT
    data (the (·, d) f32 arrays — label/id vectors are noise next to them).

    sweep='bcast': the device's own (B, d) row slice plus the full (s, d)
    replicated broadcast — B·d·4 + s·d·4, linear in s per device.

    sweep='sharded': the own slice, the visiting block, and (overlap=True)
    the prefetched next block plus the outer ring's pristine panel copy —
    k·B·d·4 with k = 4 when overlapped, 3 when barrier-serialized, where
    B = ring_block_rows(s, n_shards). Never a function of s beyond the
    B = ceil(s/P) slice itself: that is the O(s/P·d + c·d) memory model.
    """
    if sweep not in ("sharded", "bcast"):
        raise ValueError(f"sweep must be 'sharded' or 'bcast', got {sweep!r}")
    b = ring_block_rows(s, n_shards)
    if sweep == "bcast":
        return b * d * 4 + s * d * 4
    return (4 if overlap else 3) * b * d * 4


def boruvka_mst_distributed(
    mesh: Mesh,
    axes: tuple[str, ...],
    xs: jax.Array,
    *,
    impl: str = "auto",
    pre_reduce: bool = True,
    merge: str = "comp",
    sweep: str = "auto",
    overlap: bool = True,
    compact: bool = True,
    check_every: int = 3,
    prewarm: bool | None = None,
    checkpoint=None,
    pass_id: str = "boruvka_mst",
) -> MSTEdges:
    """Borůvka MST with the per-row edge search sharded over the mesh.

    Each shard owns ~s/P rows of the edge search (matrix-free — no (s, s)
    block exists on any device). Rounds are host-chained like the paper's
    job driver, with a device-side early exit synced to the host every
    ``check_every`` rounds.

    sweep selects how a shard's rows see the other shards' columns:
      'sharded' (the 'auto' resolution whenever merge='comp' allows it):
        the ring sweep of DESIGN.md §16 — xs is NEVER replicated; each
        device keeps its (s/P, d) slice resident and block COPIES rotate
        through the mesh via nested per-axis ppermute rings (outer = pod
        hops, inner = intra-pod hops on a pod mesh). Per-device point
        memory is O(s/P·d + c·d) and the only replicated per-round state
        is the (cap,) comp_to_root map. Edges are bit-identical to
        sweep='bcast' (same similarity bits, same (w desc, col asc) tie
        order — tests/test_pod_scale.py).
      'bcast': the replicated sweep — the full (s, d) xs broadcast lands
        on every device each round. Kept for parity tests and as the
        memory-wall twin in benchmarks (phase1_sharded rows).
    overlap (sharded sweep only): dispatch the NEXT block's ring exchange
    before folding the current block — the §11 double-buffered prefetch
    discipline applied to collectives, so the ppermute hop hides behind
    the fold's compute. The fold is order-independent, so overlap on/off
    is bit-identical (enforced in tests); overlap=False serializes each
    hop after the fold via an optimization barrier.

    checkpoint (merge='comp' paths only): a resilience.Checkpointer; the
    round loop snapshots its full carry — comp state (the sharded slice's
    host gather under sweep='sharded'), comp_to_root, live count, and the
    compact per-round edge lists — at every ``check_every`` host sync, and
    resumes bit-identically from the last snapshot after a kill
    (tests/test_pod_scale.py SIGKILL parity). The snapshot is deleted on
    completion. ``pass_id`` namespaces it within the store.

    pre_reduce=True (default) folds each shard's candidates per component
    before anything crosses shards — O(#components) shuffle per round, with
    the per-round arrays shrinking along the halving bound. pre_reduce=False
    is the legacy O(s)-per-shard per-row gather, kept for benchmarks.

    merge selects the alignment step (pre_reduce only; the row-gather path
    always merges at point level):
      'comp' (default): the merge itself runs on the COMPONENT graph
        (core.hac._merge_round_comp) — dedupe, pointer jumping, and densify
        all on (cap,) arrays following the halving bound, point state touched
        only by an elementwise relabel gather. With ``compact=True`` the
        returned MSTEdges hold one slot per component per round (total
        ~2s over a full run instead of s·rounds) — ``cut_mst_edges`` is
        length-agnostic, and ``compact=False`` re-expands each round into
        the (s,)-slot layout, bit-identical to merge='point'.
      'point': the replicated point-level alignment
        (core.hac._merge_round_pre), kept for parity tests and benches.

    prewarm (pre_reduce only) AOT-compiles the round shapes on a background
    worker kicked off before round 1, in round order, so the O(log s)
    per-cap recompiles overlap the round loop instead of serializing inside
    it; shapes still pending when the loop exits early are cancelled. The
    default (None) enables it only when the host has cores to spare
    (cpu_count >= 4 — on a 2-core box the compile worker steals cycles from
    the round execution and the overlap cannot pay). ``prewarm=False`` keeps
    the synchronous-compile behavior for benches.
    """
    if merge not in ("comp", "point"):
        raise ValueError(f"merge must be 'comp' or 'point', got {merge!r}")
    if sweep not in ("auto", "sharded", "bcast"):
        raise ValueError(
            f"sweep must be 'auto', 'sharded' or 'bcast', got {sweep!r}"
        )
    if not pre_reduce:
        merge = "point"  # row-gather candidates only exist at point level
    mode = {True: "comp" if merge == "comp" else "pre", False: "rowgather"}[
        pre_reduce
    ]
    if sweep == "sharded" and mode != "comp":
        raise ValueError(
            "sweep='sharded' requires pre_reduce=True and merge='comp' "
            "(the ring sweep carries component ids, not point labels)"
        )
    if mode == "comp" and sweep != "bcast":
        mode = "comp_sharded"
    overlap = bool(overlap) if mode == "comp_sharded" else False
    if checkpoint is not None and mode not in ("comp", "comp_sharded"):
        raise ValueError(
            "checkpointed Borůvka requires merge='comp' (the comp-graph "
            "carry is the snapshot unit)"
        )
    s, d = xs.shape
    xs = l2_normalize(xs)
    n_shards = mesh_axis_size(mesh, axes)
    tiers = tier_sizes(mesh, axes)
    pad = (-s) % n_shards
    xs_p = (
        jnp.concatenate([xs, jnp.zeros((pad, d), xs.dtype)]) if pad else xs
    )
    rowid_p = jnp.arange(s + pad, dtype=jnp.int32)
    if mode == "comp_sharded":
        # place the row slices ONCE: the ring sweep never broadcasts them,
        # and committed placement keeps every round's dispatch a no-op put
        xs_p = shard_rows(mesh, axes, xs_p)
        rowid_p = shard_rows(mesh, axes, rowid_p)
    job = _cand_job(mesh, tiers, axes, impl, mode, overlap)

    rounds = _rounds_for(s)
    if prewarm is None:
        prewarm = _auto_prewarm()
    warm = None
    hint_key = (mesh, tiers, axes, impl, mode, overlap, s, d, pad)
    if pre_reduce and prewarm:
        with _WARM_LOCK:
            hint = _WARM_ROUNDS_HINT.get(hint_key)
        depth = rounds if hint is None else min(rounds, hint + check_every)
        warm = prewarm_candidate_rounds(
            mesh, axes, impl, s=s, d=d, pad=pad, rounds=depth, mode=mode,
            overlap=overlap,
        ) + [None] * (rounds - depth)  # beyond the hint: sync-compile lazily
    try:
        edges, rounds_run = _boruvka_rounds(
            job, warm, mesh, axes, xs, xs_p, rowid_p, s, pad, rounds,
            mode, compact, check_every, checkpoint, pass_id,
        )
        if checkpoint is not None:
            checkpoint.delete(pass_id)  # the pass completed
        if warm is not None:
            with _WARM_LOCK:
                _WARM_ROUNDS_HINT.pop(hint_key, None)  # re-insert as newest
                _WARM_ROUNDS_HINT[hint_key] = rounds_run
                while len(_WARM_ROUNDS_HINT) > _WARM_CAP:  # keys pin Meshes
                    _WARM_ROUNDS_HINT.pop(next(iter(_WARM_ROUNDS_HINT)))
        return edges
    finally:
        if warm is not None:  # early exit leaves later shapes unneeded
            _cancel_pending([w for w in warm if w is not None])


def _boruvka_rounds(
    job, warm, mesh, axes, xs, xs_p, rowid_p, s, pad, rounds,
    mode, compact, check_every, checkpoint=None, pass_id="boruvka_mst",
) -> tuple[MSTEdges, int]:
    """The host-chained round loop of ``boruvka_mst_distributed``.

    Returns (edges, rounds_run) — compact edges make the round count
    unrecoverable from the edge array length, so it is explicit.
    """
    labels = jnp.arange(s, dtype=jnp.int32)
    pad_labels = jnp.full((pad,), -1, jnp.int32)
    # comp-mode state: dense component ids replace point labels end-to-end.
    # Under the replicated sweep the (s,) comp_all survives ONLY as the
    # candidate sweep's column-label broadcast; under the sharded sweep not
    # even that exists — comp_p is the device-resident slice, updated in
    # place through the (cap,) relabel broadcast, and the reduce carries the
    # winner's target comp so nothing ever gathers it.
    comp_all = jnp.arange(s, dtype=jnp.int32)
    comp_to_root = jnp.arange(s, dtype=jnp.int32)
    n_real = jnp.int32(s)
    comp_p = None
    relabel_job = None
    if mode == "comp_sharded":
        tiers = tier_sizes(mesh, axes)
        relabel_job = _relabel_job(mesh, tiers, axes)
        comp_p = shard_rows(
            mesh, axes,
            jnp.concatenate([comp_all, jnp.full((pad,), -1, jnp.int32)])
            if pad else comp_all,
        )
    eus, evs, ews, evalids = [], [], [], []
    rounds_run = 0
    start_r = 0
    ck_fp = None
    if checkpoint is not None:
        # structural fingerprint: the round schedule and every carry shape
        # are functions of these, so a parameter change cold-starts instead
        # of restoring into the wrong loop. The shapes themselves shrink
        # per round (halving cap), hence a static string rather than
        # carry_fingerprint.
        d = xs.shape[1]
        tiers = tier_sizes(mesh, axes)
        ck_fp = (
            f"boruvka:{mode}:tiers{tiers}:s{s}:d{d}:pad{pad}"
            f":compact{int(compact)}:ck{check_every}"
        )
        snap = checkpoint.load(pass_id, fingerprint=ck_fp)
        if snap is not None:
            from repro.resilience.checkpoint import carry_from_host

            carry = carry_from_host(snap["carry"])
            start_r = int(snap["chunk"]) + 1
            rounds_run = start_r
            comp_to_root = carry["comp_to_root"]
            n_real = carry["n_real"]
            eus = list(carry["eu"])
            evs = list(carry["ev"])
            ews = list(carry["ew"])
            evalids = list(carry["evalid"])
            if mode == "comp_sharded":
                comp_p = shard_rows(mesh, axes, carry["comp"])
            else:
                comp_all = carry["comp"]
    for r in range(start_r, rounds):
        rounds_run = r + 1
        cap = round_cap(s, r)
        # pre-warmed AOT executable for this round's shapes if it landed
        # (or will land — result() blocks only on THIS round's compile);
        # None falls back to the jitted call (compiles synchronously).
        # REPRO_COMPILE_TIMEOUT bounds the wait: a wedged compile worker
        # degrades to the jit fallback instead of hanging the round loop.
        slot = warm[r] if warm is not None else None
        ex = slot.result(_compile_timeout()) if slot is not None else None
        if mode in ("comp", "comp_sharded"):
            if mode == "comp":
                comp_p_r = (
                    jnp.concatenate(
                        [comp_all, jnp.full((pad,), -1, jnp.int32)]
                    )
                    if pad else comp_all
                )
                data = {"rows": xs_p, "rowid": rowid_p, "comp": comp_p_r}
                bcast = {"xs": xs, "comp_all": comp_all,
                         "comp_to_root": comp_to_root}
            else:
                data = {"rows": xs_p, "rowid": rowid_p, "comp": comp_p}
                bcast = {"comp_to_root": comp_to_root}
            if ex is not None:
                data, bcast = _place_round_args(mesh, axes, data, bcast)
            best = (job if ex is None else ex)(data, bcast)["best"]
            # the ring sweep carries the winner's target comp through the
            # reduce (no replicated comp_all exists to look it up in); the
            # replicated sweep gathers it. Identical wherever col >= 0, and
            # the merge never reads tcomp where col < 0 (no proposal).
            tcomp = (
                best["tcomp"] if mode == "comp_sharded"
                else comp_all[jnp.maximum(best["col"], 0)]
            )
            next_cap = round_cap(s, r + 1)
            relabel, new_root, eu, ev, ew, evalid, n_real = _merge_round_comp(
                best["w"], best["row"], best["col"], tcomp, comp_to_root,
                n_real, next_cap=next_cap,
            )
            if not compact:
                eu, ev, ew, evalid = _expand_round_edges(
                    s if mode == "comp_sharded" else comp_all,
                    eu, ev, ew, evalid, comp_to_root,
                )
            if mode == "comp":
                comp_all = relabel[comp_all]
            else:
                comp_p = relabel_job(
                    {"comp": comp_p}, {"relabel": relabel}
                )["comp"]
            comp_to_root = new_root
            done = n_real == 1
        elif mode == "pre":
            labels_p = jnp.concatenate([labels, pad_labels]) if pad else labels
            comp, comp_to_root_r = _round_prep(labels, cap)
            comp_p = (
                jnp.concatenate([comp, jnp.full((pad,), cap, jnp.int32)])
                if pad else comp
            )
            data = {"rows": xs_p, "labels": labels_p, "rowid": rowid_p,
                    "comp": comp_p}
            bcast = {"xs": xs, "all_labels": labels,
                     "comp_to_root": comp_to_root_r}
            if ex is not None:
                data, bcast = _place_round_args(mesh, axes, data, bcast)
            best = (job if ex is None else ex)(data, bcast)["best"]
            labels, eu, ev, ew, evalid = _merge_round_pre(
                labels, best["w"], best["row"], best["col"], comp_to_root_r
            )
            done = jnp.all(labels == 0)  # single component: forest complete
        else:
            labels_p = jnp.concatenate([labels, pad_labels]) if pad else labels
            out = job(
                {"rows": xs_p, "labels": labels_p},
                {"xs": xs, "all_labels": labels},
            )
            bj = jnp.asarray(out["j"])[:s]  # gather + drop pad-row candidates
            bw = jnp.asarray(out["w"])[:s]
            labels, eu, ev, ew, evalid = _merge_round(labels, bw, bj)
            done = jnp.all(labels == 0)
        eus.append(eu)
        evs.append(ev)
        ews.append(ew)
        evalids.append(evalid)
        # early exit: the done flag is computed ON DEVICE every round but the
        # host only syncs on it every check_every rounds, so rounds keep
        # streaming to the device without a per-round host round-trip. The
        # trade is DETERMINISTIC: a late exit costs at most check_every - 1
        # no-op rounds (cheap merges — evalid stays False — but full candidate
        # sweeps), and the executed round count never depends on dispatch
        # timing, so bench-recorded rounds/shuffle bytes are reproducible.
        if (r + 1) % check_every == 0 or r == rounds - 1:
            if bool(done):
                break
            if checkpoint is not None:
                # save only when CONTINUING: a snapshot therefore always
                # points at a round the uninterrupted run executes, so a
                # resume replays the identical round sequence (bit-parity).
                # Completion deletes the snapshot in the driver.
                carry = {
                    "comp": comp_p if mode == "comp_sharded" else comp_all,
                    "comp_to_root": comp_to_root,
                    "n_real": n_real,
                    "eu": eus, "ev": evs, "ew": ews, "evalid": evalids,
                }
                checkpoint.save(
                    pass_id, chunk=r, carry_host=carry_to_host(carry),
                    fingerprint=ck_fp,
                )
    edges = MSTEdges(
        u=jnp.concatenate(eus),
        v=jnp.concatenate(evs),
        w=jnp.concatenate(ews),
        valid=jnp.concatenate(evalids),
    )
    return edges, rounds_run


@functools.partial(jax.jit, static_argnames=("cap",))
def _synth_candidates(comp_to_root, n_real, cap: int):
    """Deterministic per-component best edges for the merge-only driver:
    live component c proposes to its pair partner c^1 (the last odd one
    pairs downward), weights a fixed function of the unordered pair so
    mutual proposals agree — halves the component count every round, the
    Borůvka worst case for merge work. Dead/phantom slots emit the empty
    sentinel the real reduce would ((NEG, BIG_I, -1))."""
    neg = float(jnp.finfo(jnp.float32).min)
    c = jnp.arange(cap, dtype=jnp.int32)
    t = c ^ 1
    t = jnp.where(t >= n_real, c - 1, t)
    propose = jnp.logical_and(c < n_real, n_real > 1)
    t = jnp.where(propose, jnp.maximum(t, 0), c)
    wval = 1.0 - (jnp.minimum(c, t) + 1.0) / (2.0 * (cap + 1.0))
    w = jnp.where(propose, wval.astype(jnp.float32), neg)
    row = jnp.where(propose, comp_to_root[c], _BIG_I)
    col = jnp.where(propose, comp_to_root[t], -1)
    return w, row, col, t


def synthetic_merge_rounds(
    mesh: Mesh,
    axes: tuple[str, ...],
    s: int,
    *,
    merge: str = "comp",
    check_every: int = 3,
) -> tuple[MSTEdges, int]:
    """Borůvka MERGE rounds in isolation, on synthetic pair-merge candidates.

    The full phase-1 driver couples the merge to the O(s²·d/P) candidate
    sweep, so the merge's replication ceiling hides behind compute at any s
    a test box can sweep. This driver replaces the sweep with
    ``_synth_candidates`` (same post-reduce contract) and runs ONLY the
    per-round alignment — the subsystem this PR shards — at sample sizes
    where the two merge paths separate:

      merge='comp': component-graph alignment. Per-point state is ONE
        sharded (s/P per device) comp vector updated through the c-sized
        relabel broadcast (`_relabel_job`); everything else is O(cap).
        Edge history is compact — Σ cap_r ≈ 2s slots total.
      merge='point': the replicated `_merge_round_pre` twin — (s,) labels
        plus O(s) scatter/propagation per round, and an (s,)-slot edge
        history growing by 13·s bytes per round. At s = 4M that history
        alone is ~1.2 GB, which is what the bench's memory budget shows
        failing (benchmarks/run.py phase1_merge rows).

    Both paths see identical candidates, so at sizes where both run the
    expanded edges match bit-for-bit (tests/test_pod_scale.py).

    Returns (edges, rounds_run).
    """
    if merge not in ("comp", "point"):
        raise ValueError(f"merge must be 'comp' or 'point', got {merge!r}")
    from repro.distrib.sharding import shard_rows

    rounds = _rounds_for(s)
    eus, evs, ews, evalids = [], [], [], []
    rounds_run = 0
    if merge == "comp":
        tiers = tier_sizes(mesh, axes)
        relabel_job = _relabel_job(mesh, tiers, axes)
        n_shards = mesh_axis_size(mesh, axes)
        pad = (-s) % n_shards
        comp_p = shard_rows(
            mesh, axes,
            jnp.concatenate([
                jnp.arange(s, dtype=jnp.int32),
                jnp.full((pad,), -1, jnp.int32),
            ]) if pad else jnp.arange(s, dtype=jnp.int32),
        )
        comp_to_root = jnp.arange(s, dtype=jnp.int32)
        n_real = jnp.int32(s)
        for r in range(rounds):
            rounds_run = r + 1
            cap = round_cap(s, r)
            w, row, col, tcomp = _synth_candidates(comp_to_root, n_real, cap)
            relabel, comp_to_root, eu, ev, ew, evalid, n_real = (
                _merge_round_comp(
                    w, row, col, tcomp, comp_to_root, n_real,
                    next_cap=round_cap(s, r + 1),
                )
            )
            comp_p = relabel_job({"comp": comp_p}, {"relabel": relabel})[
                "comp"
            ]
            eus.append(eu)
            evs.append(ev)
            ews.append(ew)
            evalids.append(evalid)
            if (r + 1) % check_every == 0 or r == rounds - 1:
                if bool(n_real == 1):
                    break
    else:
        labels = jnp.arange(s, dtype=jnp.int32)
        rows = jnp.arange(s, dtype=jnp.int32)
        for r in range(rounds):
            rounds_run = r + 1
            cap = round_cap(s, r)
            comp, comp_to_root = _round_prep(labels, cap)
            n_real = jnp.sum(labels == rows).astype(jnp.int32)
            w, row, col, _ = _synth_candidates(comp_to_root, n_real, cap)
            labels, eu, ev, ew, evalid = _merge_round_pre(
                labels, w, row, col, comp_to_root
            )
            eus.append(eu)
            evs.append(ev)
            ews.append(ew)
            evalids.append(evalid)
            if (r + 1) % check_every == 0 or r == rounds - 1:
                if bool(jnp.all(labels == 0)):
                    break
    edges = MSTEdges(
        u=jnp.concatenate(eus),
        v=jnp.concatenate(evs),
        w=jnp.concatenate(ews),
        valid=jnp.concatenate(evalids),
    )
    return edges, rounds_run


def single_link_labels_distributed(
    mesh: Mesh, axes: tuple[str, ...], xs: jax.Array, k: int, *,
    impl: str = "auto", pre_reduce: bool = True, sweep: str = "auto",
    overlap: bool = True,
) -> jax.Array:
    edges = boruvka_mst_distributed(
        mesh, axes, xs, impl=impl, pre_reduce=pre_reduce, sweep=sweep,
        overlap=overlap,
    )
    return cut_mst_edges(edges, xs.shape[0], k)
