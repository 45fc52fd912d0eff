"""Fused similarity+best-edge Pallas TPU kernel — matrix-free Borůvka step.

The Buckshot phase-1 bottleneck was never the HAC bookkeeping, it was the
(s, s) sample similarity matrix: `best_edge` consumed a sim block that some
caller first had to materialize in HBM (2 GB f32 at the paper's n = 1M /
k = 500 regime). This kernel folds the similarity build INTO the edge search:
each grid step does one (BR, BD) x (BC, BD) MXU matmul, masks same-component
and padded columns, and folds the tile into a running (max, argmax) pair
living in the revisited output block. The (BR, BC) sim tile dies in VMEM —
phase 1 peak memory drops from O(s^2) to O(s*d + BR*BC).

Grid: (r_tiles, c_tiles, d_tiles), d innermost; output blocks are indexed by
the row tile only, so they stay VMEM-resident across the column sweep (the
same revisiting idiom as assign_argmax.py — a Borůvka candidate search IS an
assign_argmax with a component mask).

d tiling (DESIGN.md §9): the original kernel kept the FULL contraction dim
per (BR/BC) block, which capped the sample at d ≈ 8k f32 (two (256, d) tiles
against the VMEM budget). Past BD the d axis gets its own innermost grid
dimension: partial products accumulate into a (BR, BC) f32 VMEM scratch
(zeroed on the first d step), and the mask+rowmax+argmax finalization runs
only on the LAST d step — so arbitrarily large d streams through at a fixed
(BR + BC) * BD + BR * BC f32 VMEM footprint.

Tie semantics match ref.sim_best_edge (== ref.best_edge on the full product):
lowest column index wins (strict > across tiles, first-argmax within a tile);
rows with no cross-component column get (-1, f32.min). NEGATIVE row labels
mark padding: those rows match no column at all (masked out of the map, not
sliced off afterwards).

bf16: row/column blocks may be bf16 — the MXU matmul accumulates f32
(``preferred_element_type``), halving the HBM read of the sample.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.assign_argmax import _pad_to, split_bytes, vmem_params
from repro.kernels.ref import dot_precision

NEG = float(jnp.finfo(jnp.float32).min)

BR = 256  # row points per tile (8-sublane multiple)
BC = 256  # column points per tile (lane-width multiple)
# contraction columns per d step: (BR + BC) * BD f32 of x tiles + the
# (BR, BC) scratch — 4.25 MiB at the defaults, comfortably inside VMEM
BD = 2048


def _kernel(
    xr_ref, xc_ref, lr_ref, lc_ref, j_ref, s_ref, acc_ref, *,
    c_real: int, bc: int, nd: int,
):
    j = pl.program_id(1)
    kd = pl.program_id(2)

    @pl.when(jnp.logical_and(j == 0, kd == 0))
    def _init():
        j_ref[...] = jnp.full_like(j_ref, -1)
        s_ref[...] = jnp.full_like(s_ref, NEG)

    @pl.when(kd == 0)
    def _zero_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    xr = xr_ref[...]  # (BR, BD) — one contraction slice
    xc = xc_ref[...]  # (BC, BD)
    acc_ref[...] += jax.lax.dot_general(
        xr,
        xc,
        (((1,), (1,)), ((), ())),  # contract on d: (BR, BD) x (BC, BD) -> (BR, BC)
        preferred_element_type=jnp.float32,
        precision=dot_precision(xr.dtype),
    )

    # mask + rowmax + argmax only once the contraction is complete
    @pl.when(kd == nd - 1)
    def _finalize():
        sims = acc_ref[...]
        lr = lr_ref[...]  # (BR, 1) int32
        lc = lc_ref[...]  # (1, BC) int32

        col = j * bc + jax.lax.broadcasted_iota(jnp.int32, sims.shape, 1)
        keep = jnp.logical_and(
            # cross-component, unpadded row AND column: negative col labels
            # mark caller-side pad columns (ring-sweep visiting blocks), same
            # contract as ref.best_edge
            jnp.logical_and(jnp.logical_and(lr != lc, lr >= 0), lc >= 0),
            col < c_real,  # tile-pad column
        )
        masked = jnp.where(keep, sims, NEG)

        local_s = jnp.max(masked, axis=1, keepdims=True)
        local_j = jnp.argmax(masked, axis=1).astype(jnp.int32)[:, None] + j * bc

        best_s = s_ref[...]
        better = local_s > best_s  # strict: earlier tiles win ties
        s_ref[...] = jnp.where(better, local_s, best_s)
        j_ref[...] = jnp.where(better, local_j, j_ref[...])


@functools.partial(
    jax.jit, static_argnames=("interpret", "br", "bc", "bd")
)
def sim_best_edge_pallas(
    xs_rows: jax.Array,
    xs_all: jax.Array,
    labels_row: jax.Array,
    labels_col: jax.Array,
    *,
    interpret: bool = False,
    br: int = BR,
    bc: int = BC,
    bd: int = BD,
) -> tuple[jax.Array, jax.Array]:
    """(r, d), (c, d), (r,), (c,) -> ((r,) best col, (r,) best sim).

    Contract identical to ref.sim_best_edge; the (r, c) similarity matrix
    never exists in HBM, and d beyond one VMEM tile streams through the
    innermost grid dimension (``bd`` columns per step).
    """
    r, d = xs_rows.shape
    c = xs_all.shape[0]
    br = min(br, max(8, r))
    bc = min(bc, max(8, c))
    dmult = 128 if d >= 128 else 8

    xr = _pad_to(_pad_to(xs_rows, 0, br), 1, dmult)
    xc = _pad_to(_pad_to(xs_all, 0, bc), 1, dmult)
    lr = _pad_to(labels_row.astype(jnp.int32)[:, None] + 1, 0, br) - 1  # pad -> -1
    # tile-pad col labels are irrelevant (cols >= c masked by c_real), but
    # CALLER pad columns arrive as negative labels and the keep mask drops them
    lc = _pad_to(labels_col.astype(jnp.int32)[None, :], 1, bc)
    bd = min(max(dmult, (bd // dmult) * dmult), xr.shape[1])
    xr = _pad_to(xr, 1, bd)  # d-grid divisible; zero columns add nothing
    xc = _pad_to(xc, 1, bd)
    rp, dp = xr.shape
    cp = xc.shape[0]
    grid = (rp // br, cp // bc, dp // bd)

    best_j, best_s = pl.pallas_call(
        functools.partial(_kernel, c_real=c, bc=bc, nd=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((br, bd), lambda i, j, kd: (i, kd)),
            pl.BlockSpec((bc, bd), lambda i, j, kd: (j, kd)),
            pl.BlockSpec((br, 1), lambda i, j, kd: (i, 0)),
            pl.BlockSpec((1, bc), lambda i, j, kd: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((br, 1), lambda i, j, kd: (i, 0)),
            pl.BlockSpec((br, 1), lambda i, j, kd: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rp, 1), jnp.int32),
            jax.ShapeDtypeStruct((rp, 1), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((br, bc), jnp.float32)],
        # double-buffered row/column slices, the (br, bc) accumulator with
        # three same-size temporaries, and the precision split of the dot
        compiler_params=vmem_params(
            2 * (br + bc) * bd * xr.dtype.itemsize
            + 4 * br * bc * 4
            + split_bytes((br * bd, xr.dtype.itemsize), (bc * bd, xr.dtype.itemsize))
        ),
        interpret=interpret,
    )(xr, xc, lr, lc)
    out_j = best_j[:r, 0]
    out_s = best_s[:r, 0]
    return jnp.where(out_s == NEG, -1, out_j), out_s
