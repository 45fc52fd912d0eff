"""Fused similarity+argmax Pallas TPU kernel — the paper's map step.

Computes, for every document row, the most similar center WITHOUT ever
materializing the (n, k) similarity matrix in HBM: each grid step does one
(BN, d) x (d, BK) MXU matmul into VMEM and folds it into a running
(max, argmax) pair that lives in the revisited output block.

Grid: (n_tiles, k_tiles), k innermost. Output blocks are indexed by the n
tile only, so they stay resident in VMEM across the k sweep (the Pallas
revisiting idiom — the TPU analogue of keeping the accumulator in registers).

Tiling: BN x BK = 256 x 128 output tile; the full d (contraction) dimension is
kept in VMEM per block — for tf-idf (d = 2048 f32) the x tile is 2 MiB and the
center tile 1 MiB, comfortably inside a v5e core's VMEM. Inputs are padded to
tile multiples by the wrapper; padded CENTER columns are masked with -inf in
the kernel (padded doc rows are sliced off by the wrapper).

Tie semantics match ref.assign_argmax (first max wins): within a tile
jnp.argmax takes the first; across tiles the update is strict (>), so earlier
(lower-index) tiles win ties.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import dot_precision

NEG = float(jnp.finfo(jnp.float32).min)

BN = 256  # doc rows per tile (8-sublane multiple)
BK = 128  # center columns per tile (lane width)


def _kernel(x_ref, c_ref, idx_ref, sim_ref, *, k_real: int, bk: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        idx_ref[...] = jnp.full_like(idx_ref, -1)
        sim_ref[...] = jnp.full_like(sim_ref, NEG)

    x = x_ref[...]  # (BN, d)
    c = c_ref[...]  # (BK, d)
    sims = jax.lax.dot_general(
        x,
        c,
        (((1,), (1,)), ((), ())),  # contract on d: (BN, d) x (BK, d) -> (BN, BK)
        preferred_element_type=jnp.float32,
        precision=dot_precision(x.dtype),
    )
    # mask padded center columns (global col id >= k_real)
    col = j * bk + jax.lax.broadcasted_iota(jnp.int32, sims.shape, 1)
    sims = jnp.where(col < k_real, sims, NEG)

    local_sim = jnp.max(sims, axis=1, keepdims=True)  # (BN, 1)
    local_idx = (
        jnp.argmax(sims, axis=1).astype(jnp.int32)[:, None] + j * bk
    )  # (BN, 1)

    best_sim = sim_ref[...]
    better = local_sim > best_sim  # strict: earlier tiles win ties
    sim_ref[...] = jnp.where(better, local_sim, best_sim)
    idx_ref[...] = jnp.where(better, local_idx, idx_ref[...])


_MIB = 1024 * 1024
# Mosaic grants a kernel 16 MiB of scoped VMEM unless asked for more; a v5e
# TensorCore has 128 MiB. Kernels whose blocks outgrow the default ask for
# what they need, with headroom, up to this cap.
_VMEM_CAP = 100 * _MIB


def vmem_params(need: int) -> pltpu.CompilerParams:
    """Compiler params granting ``need`` bytes of scoped VMEM plus 4 MiB of
    headroom — never below Mosaic's 16 MiB default, never above the cap."""
    limit = min(max(need + 4 * _MIB, 16 * _MIB), _VMEM_CAP)
    return pltpu.CompilerParams(vmem_limit_bytes=int(limit))


def split_bytes(*operands: tuple[int, int]) -> int:
    """VMEM a HIGHEST-precision f32 dot adds: Mosaic splits each f32 operand
    of ``(elements, itemsize)`` into bf16 parts (about twice its bytes)."""
    return sum(2 * n * 4 for n, isz in operands if isz == 4)


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    pad = (-x.shape[axis]) % mult
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=("interpret", "bn", "bk"))
def assign_argmax_pallas(
    x: jax.Array,
    centers: jax.Array,
    *,
    interpret: bool = False,
    bn: int = BN,
    bk: int = BK,
) -> tuple[jax.Array, jax.Array]:
    """(n, d), (k, d) -> ((n,) int32 argmax, (n,) f32 max similarity)."""
    n, d = x.shape
    k = centers.shape[0]
    bn = min(bn, max(8, n))
    bk = min(bk, max(8, k))

    xp = _pad_to(_pad_to(x, 0, bn), 1, 128 if d >= 128 else 8)
    cp = _pad_to(_pad_to(centers, 0, bk), 1, 128 if d >= 128 else 8)
    np_, dp = xp.shape
    kp = cp.shape[0]
    grid = (np_ // bn, kp // bk)

    idx, sim = pl.pallas_call(
        functools.partial(_kernel, k_real=k, bk=bk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, dp), lambda i, j: (i, 0)),
            pl.BlockSpec((bk, dp), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((np_, 1), jnp.int32),
            jax.ShapeDtypeStruct((np_, 1), jnp.float32),
        ],
        interpret=interpret,
    )(xp, cp)
    return idx[:n, 0], sim[:n, 0]
