"""Segmented component pre-reduce Pallas TPU kernel — the Borůvka combiner.

Distributed Borůvka phase 1 used to ship EVERY row's best-edge candidate
through the shuffle (O(s) values per shard per round) even though only one
candidate per component can survive the replicated merge. This kernel is the
paper's combiner discipline applied to the edge search: fold each shard's
per-row candidates into a per-COMPONENT lexicographic best (weight desc,
row asc) BEFORE anything crosses shards, so the wire carries O(#components)
triples instead of O(s) pairs (DESIGN.md §9).

Grid: (comp_tiles, n_tiles), n innermost; the (BCOMP, 1) running best blocks
are indexed by the component tile only, so they stay VMEM-resident across the
row sweep (the same revisited-output idiom as assign_stats.py / the
label_stats accumulator — a segmented argmax IS a label_stats whose reduction
is max instead of add). Membership is an iota compare in VMEM; the winner
row/column inside a tile come from a masked min + one-hot select (no
gathers, so the body stays VPU-only and Mosaic-friendly).

Tie semantics match ref.component_best_edge: within a tile the lowest ROW ID
among the weight-winners takes the segment (row ids are globally unique, so
the winner and its column are unique); across tiles the fold is (w strictly
greater) OR (w equal AND row strictly lower) — global lexicographic
(w desc, row asc). Empty segments get (f32.min, BIG_I, -1). Out-of-range
component ids (pad rows are tagged with id == c) match no tile and
contribute nothing.

The same (w desc, row asc) total order governs every layer above this
kernel: the engine's 'component' fold carry merges two winner sets with it
(engine._component_merge), and the cross-shard reduce applies it per mesh
axis — intra-pod first, then across pods on the per-pod winners only
(engine._component_reduce, DESIGN.md §15). Because the order is total, the
tiered fold is bit-identical to a flat one; this kernel's output contract
(the empty sentinel included) is what makes that composition legal.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.assign_argmax import _pad_to
from repro.kernels.ref import BIG_I

NEG = float(jnp.finfo(jnp.float32).min)

BN = 512  # candidate rows per tile (lane-dense blocks: a 128 multiple)
BCOMP = 256  # component segments per tile


def _kernel(w_ref, j_ref, row_ref, comp_ref, bw_ref, brow_ref, bj_ref, *,
            bcomp: int):
    i = pl.program_id(0)  # component tile
    j = pl.program_id(1)  # n tile (innermost)

    @pl.when(j == 0)
    def _init():
        bw_ref[...] = jnp.full_like(bw_ref, NEG)
        brow_ref[...] = jnp.full_like(brow_ref, BIG_I)
        bj_ref[...] = jnp.full_like(bj_ref, -1)

    # lane-dense (1, BN) rows: they broadcast against the (BCOMP, BN)
    # membership tile with no relayout
    w = w_ref[...]  # f32 candidate weights
    col = j_ref[...]  # int32 candidate columns
    rows = row_ref[...]  # int32 global row ids
    comp = comp_ref[...]  # int32 dense component ids

    bn = w.shape[1]
    bins = i * bcomp + jax.lax.broadcasted_iota(jnp.int32, (bcomp, bn), 0)
    hot = bins == comp  # (BCOMP, BN) membership, VMEM only
    has_any = jnp.any(hot, axis=1, keepdims=True)  # (BCOMP, 1)

    wmask = jnp.where(hot, w, NEG)
    tile_w = jnp.max(wmask, axis=1, keepdims=True)  # (BCOMP, 1)
    # lowest ROW ID among the members achieving the tile max (row ids are
    # globally unique, so the winner — and its column — is unique too)
    cand = jnp.logical_and(hot, w == tile_w)
    tile_row = jnp.min(jnp.where(cand, rows, BIG_I), axis=1, keepdims=True)
    sel = jnp.logical_and(cand, rows == tile_row)
    tile_j = jnp.sum(jnp.where(sel, col, 0), axis=1, keepdims=True)

    best_w = bw_ref[...]
    best_row = brow_ref[...]
    better = jnp.logical_and(
        has_any,
        jnp.logical_or(
            tile_w > best_w,
            jnp.logical_and(tile_w == best_w, tile_row < best_row),
        ),
    )
    bw_ref[...] = jnp.where(better, tile_w, best_w)
    brow_ref[...] = jnp.where(better, tile_row, best_row)
    bj_ref[...] = jnp.where(better, tile_j, bj_ref[...])


@functools.partial(jax.jit, static_argnames=("c", "interpret", "bn", "bcomp"))
def component_best_edge_pallas(
    row_w: jax.Array,
    row_j: jax.Array,
    rows: jax.Array,
    comp: jax.Array,
    c: int,
    *,
    interpret: bool = False,
    bn: int = BN,
    bcomp: int = BCOMP,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """(r,) w, (r,) col, (r,) row id, (r,) comp id -> per-component best.

    Contract identical to ref.component_best_edge: (c,) best_w / best_row /
    best_j triples ordered lexicographically (w desc, row asc); empty
    segments get (f32.min, BIG_I, -1).
    """
    r = row_w.shape[0]
    # a (1, bn) block spans the whole row axis or a 128-lane multiple (BN)
    bn = min(bn, r)
    cp = c + ((-c) % 8)  # sublane-align the segment dimension
    bcomp = min(bcomp, cp)
    cp = cp + ((-cp) % bcomp)  # comp-grid divisible; surplus bins stay empty

    # pad rows are tagged comp id -1 (out of range): they match no tile bin
    wp = _pad_to(row_w.astype(jnp.float32)[None, :], 1, bn)
    jp = _pad_to(row_j.astype(jnp.int32)[None, :], 1, bn)
    rp = _pad_to(rows.astype(jnp.int32)[None, :], 1, bn)
    compp = _pad_to(comp.astype(jnp.int32)[None, :] + 1, 1, bn) - 1  # pad -> -1
    grid = (cp // bcomp, wp.shape[1] // bn)

    best_w, best_row, best_j = pl.pallas_call(
        functools.partial(_kernel, bcomp=bcomp),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((bcomp, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bcomp, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bcomp, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((cp, 1), jnp.float32),
            jax.ShapeDtypeStruct((cp, 1), jnp.int32),
            jax.ShapeDtypeStruct((cp, 1), jnp.int32),
        ],
        interpret=interpret,
    )(wp, jp, rp, compp)
    return best_w[:c, 0], best_row[:c, 0], best_j[:c, 0]
