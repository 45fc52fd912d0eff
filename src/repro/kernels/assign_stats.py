"""Fused assign+stats Pallas TPU kernel — map AND combine in ONE pass over x.

The paper's efficiency argument is combiner discipline: aggregate locally
before anything crosses the shuffle. The two-kernel pipeline
(assign_argmax then cluster_stats) violates that at the memory level — the
(n, d) document matrix is read from HBM twice per K-Means/BKC iteration.
This kernel reads each x tile ONCE: while the tile is VMEM-resident it is
used both to pick the nearest center (k sweep, revisited (max, argmax)
accumulator — same idiom as assign_argmax.py) and, on the final k step, to
scatter the tile into per-cluster accumulators via an in-VMEM one-hot matmul
(same idiom as the label_stats kernel below). Five results come out of one
HBM read:

  idx (n,), best_sim (n,), sums (k, d), counts (k,), min_sim (k,), sumsq (k,)

Grid: (n_tiles, k_tiles), k innermost.
  * idx/sim blocks are indexed by the n tile only -> resident across the k
    sweep (revisiting idiom).
  * sums/counts/min_sim/sumsq blocks have CONSTANT index maps -> resident in
    VMEM for the entire grid and written back once at the end.

d tiling (DESIGN.md §8): the (kp, d) f32 sums accumulator is capped at
ACC_BUDGET bytes of VMEM. When k*d fits (the paper's k <= ~1k, d = 2048
regime) the kernel is exactly the single-tile design above. Beyond the
budget, the wrapper narrows the in-kernel accumulator to the first BD_SUMS
feature columns (everything else — idx, best_sim, counts, min_sim, sumsq —
still comes from the single fused pass, which needs the full-d x tile for
the assignment matmul anyway) and builds the remaining sums columns with the
d-tiled ``label_stats`` kernel below, which streams (kp, BD) accumulator
blocks with an n-innermost grid. That tail re-reads n*(d - BD_SUMS) bytes of
x; the alternative — spilling the accumulator itself to HBM between n tiles
— would move 2 * n_tiles * k * d bytes, strictly worse whenever k > BN,
which is exactly the regime that busts the budget.

Row weights: the wrapper always materializes a (n, 1) f32 weight column
(ones when the caller passes none; zeros for rows it pads in). Inside the
kernel w scales the one-hot, so padding rows and weight-0 rows contribute
nothing to sums/counts/sumsq and are excluded from min_sim — this is what
lets the distributed path drop its separate ``x * w`` pass.

bf16: x and centers may be bf16 — the MXU matmuls and all accumulators run
f32 (``preferred_element_type``), so the HBM read of x is 2x cheaper at the
same accumulation precision.

Tie semantics match ref.assign_argmax (first max wins): within a tile
jnp.argmax takes the first; across k tiles the update is strict (>).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Shared with the standalone assign kernel: same tiling, same tie semantics.
from repro.kernels import ref
from repro.kernels.assign_argmax import (
    BK,
    BN,
    NEG,
    _pad_to,
    split_bytes,
    vmem_params,
)
from repro.kernels.ref import BIG, BIG_I, PRECISION, PRUNE_MARGIN, dot_precision

BD = 512  # feature columns per label_stats accumulator tile
# VMEM cap for the fused kernel's resident (kp, d) f32 sums accumulator; the
# old implicit ceiling was one tile of k~1k x d=2048 (8 MiB, DESIGN.md §6).
ACC_BUDGET = 8 * 1024 * 1024


def _fused_vmem(
    *, bn: int, bk: int, dp: int, isz: int, kp: int, acc_cols: int,
    extra: int = 0,
):
    """Scoped-VMEM request for one fused-kernel call, from its block shapes.

    Every pipelined block is double-buffered — the resident (kp, acc_cols)
    sums accumulator included. The combine step keeps an f32 copy of the x
    tile and (kp, bn) one-hot temporaries next to them, and both dots split
    their f32 operands for HIGHEST precision.
    """
    blocks = 2 * (bn * dp * isz + bk * dp * isz + kp * acc_cols * 4)
    temps = bn * dp * 4 + 3 * kp * bn * 4 + 2 * bn * bk * 4
    split = split_bytes((bn * dp, isz), (bk * dp, isz)) + split_bytes(
        (kp * bn, 4), (bn * acc_cols, 4)
    )
    return vmem_params(blocks + temps + split + extra)


def _kernel(
    x_ref,
    c_ref,
    w_ref,
    idx_ref,
    sim_ref,
    sums_ref,
    counts_ref,
    min_ref,
    sq_ref,
    *,
    k_real: int,
    bk: int,
    nk: int,
):
    i = pl.program_id(0)  # n tile
    j = pl.program_id(1)  # k tile (innermost)

    @pl.when(j == 0)
    def _init_rows():
        idx_ref[...] = jnp.full_like(idx_ref, -1)
        sim_ref[...] = jnp.full_like(sim_ref, NEG)

    @pl.when(jnp.logical_and(i == 0, j == 0))
    def _init_accumulators():
        sums_ref[...] = jnp.zeros_like(sums_ref)
        counts_ref[...] = jnp.zeros_like(counts_ref)
        min_ref[...] = jnp.full_like(min_ref, BIG)
        sq_ref[...] = jnp.zeros_like(sq_ref)

    x = x_ref[...]  # (BN, d) — full contraction dim, resident for the k sweep
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

    # After the last k tile the assignment for this n tile is final and x is
    # STILL in VMEM: fold it into the cluster accumulators (the combiner) so
    # the tile never has to be re-read from HBM.
    @pl.when(j == nk - 1)
    def _combine():
        idx = idx_ref[...]  # (BN, 1) final assignment
        sim = sim_ref[...]  # (BN, 1) final best similarity
        wv = w_ref[...]  # (BN, 1) row weights (0 for padding)
        kp = sums_ref.shape[0]
        bn_ = idx.shape[0]

        bins = jax.lax.broadcasted_iota(jnp.int32, (kp, bn_), 0)
        hot = bins == idx[:, 0][None, :]  # (kp, BN) membership, in VMEM only
        wrow = wv[:, 0][None, :]  # (1, BN)
        hot_w = jnp.where(hot, wrow, 0.0).astype(jnp.float32)

        xf = x.astype(jnp.float32)
        sums_ref[...] += jax.lax.dot_general(
            hot_w,
            xf[:, : sums_ref.shape[1]],  # accumulator may cover a d prefix
            (((1,), (0,)), ((), ())),  # (kp, BN) @ (BN, bd_sums)
            preferred_element_type=jnp.float32,
            precision=PRECISION,
        )
        counts_ref[...] += jnp.sum(hot_w, axis=1, keepdims=True)
        rowsq = jnp.sum(xf * xf, axis=1)  # (BN,)
        sq_ref[...] += jnp.sum(hot_w * rowsq[None, :], axis=1, keepdims=True)
        member = jnp.where(
            jnp.logical_and(hot, wrow > 0), sim[:, 0][None, :], BIG
        )
        min_ref[...] = jnp.minimum(
            min_ref[...], jnp.min(member, axis=1, keepdims=True)
        )


@functools.partial(jax.jit, static_argnames=("interpret", "bn", "bk", "bd"))
def assign_stats_pallas(
    x: jax.Array,
    centers: jax.Array,
    w: jax.Array | None = None,
    *,
    interpret: bool = False,
    bn: int = BN,
    bk: int = BK,
    bd: int | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """(n, d), (k, d)[, (n,)] -> (idx, best_sim, sums, counts, min_sim, sumsq).

    Contract identical to ref.assign_stats; single HBM read of x while the
    (kp, d) accumulator fits ACC_BUDGET. Beyond that the sums tail streams
    through the d-tiled label_stats kernel (see module docstring). ``bd``
    overrides the in-kernel accumulator width (tests force the split path).
    """
    n, d = x.shape
    k = centers.shape[0]
    bn = min(bn, max(8, n))
    bk = min(bk, max(8, k))
    dmult = 128 if d >= 128 else 8

    xp = _pad_to(_pad_to(x, 0, bn), 1, dmult)
    cp = _pad_to(_pad_to(centers, 0, bk), 1, dmult)
    wv = jnp.ones((n,), jnp.float32) if w is None else w.astype(jnp.float32)
    wp = _pad_to(wv[:, None], 0, bn)  # padded rows get weight 0
    np_, dp = xp.shape
    kp_c = cp.shape[0]
    kp = k + ((-k) % 8)  # sublane-align the accumulator bin dimension
    grid = (np_ // bn, kp_c // bk)

    if bd is None:
        bd = ACC_BUDGET // (kp * 4)
    bd_sums = min(dp, max(dmult, (bd // dmult) * dmult))

    idx, sim, sums, counts, min_sim, sumsq = pl.pallas_call(
        functools.partial(_kernel, k_real=k, bk=bk, nk=grid[1]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, dp), lambda i, j: (i, 0)),
            pl.BlockSpec((bk, dp), lambda i, j: (j, 0)),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((kp, bd_sums), lambda i, j: (0, 0)),
            pl.BlockSpec((kp, 1), lambda i, j: (0, 0)),
            pl.BlockSpec((kp, 1), lambda i, j: (0, 0)),
            pl.BlockSpec((kp, 1), lambda i, j: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((np_, 1), jnp.int32),
            jax.ShapeDtypeStruct((np_, 1), jnp.float32),
            jax.ShapeDtypeStruct((kp, bd_sums), jnp.float32),
            jax.ShapeDtypeStruct((kp, 1), jnp.float32),
            jax.ShapeDtypeStruct((kp, 1), jnp.float32),
            jax.ShapeDtypeStruct((kp, 1), jnp.float32),
        ],
        compiler_params=_fused_vmem(
            bn=bn, bk=bk, dp=dp, isz=xp.dtype.itemsize, kp=kp,
            acc_cols=bd_sums,
        ),
        interpret=interpret,
    )(xp, cp, wp)
    idx_n = idx[:n, 0]
    if bd_sums < d:
        tail, _ = label_stats_pallas(
            x[:, bd_sums:], idx_n, k, wv, interpret=interpret, bn=bn
        )
        full_sums = jnp.concatenate([sums[:k, :bd_sums], tail], axis=1)
    else:
        full_sums = sums[:k, :d]
    return (
        idx_n,
        sim[:n, 0],
        full_sums,
        counts[:k, 0],
        min_sim[:k, 0],
        sumsq[:k, 0],
    )


# ------------------------------------------------------- bounded (pruned)
#
# Bound-pruned variant of the fused kernel (DESIGN.md §13). Two pruning
# levels, both exact:
#
#   * Row level (Elkan/Hamerly): rows whose deflated carry proves the winner
#     unchanged arrive PRE-ASSIGNED (act = 0, idx/sim initialized from the
#     carry); their similarity lanes are masked to NEG so they never update.
#     When EVERY row of an n-block is settled, whole center slabs are skipped
#     via @pl.when — this is where the O(n·k·d) actually disappears.
#   * Slab level (two-level index): centers arrive PERMUTED so that similar
#     centers (ops.build_center_index's √k Lloyd groups) share a BK slab.
#     Each slab carries a cone bound: with r its unit representative,
#     s = x·r and t = √(‖x‖² − s²), every member c (decomposed c = a·r + c⊥)
#     satisfies x·c = a·s + x·c⊥ ≤ max(a⁺s, a⁻s) + b·t, where a⁺/a⁻ are the
#     max/min member component along r and b the max ‖c⊥‖. A slab whose ub
#     is below every active row's running best (minus the f32 margin) cannot
#     hold a winner OR a tie, so it is skipped — computing only the (BN, d)
#     × (d, 1) rep dot instead of the (BN, d) × (d, BK) slab matmul.
#
# Exactness bookkeeping: labels are original center ids (the perm rides in
# as an int32 column and updates are (sim desc, orig id asc) lexicographic,
# reproducing the flat sweep's ties-to-lowest-index bit-for-bit). The hi
# bound out is max(tracked second-best among computed slabs, ub of skipped
# slabs) — a valid upper bound on every non-winner similarity.


def _bounded_kernel(
    x_ref,
    c_ref,
    w_ref,
    act_ref,
    rsq_ref,
    idx0_ref,
    sim0_ref,
    perm_ref,
    rep_ref,
    cone_ref,
    idx_ref,
    sim_ref,
    sec_ref,
    sums_ref,
    counts_ref,
    min_ref,
    sq_ref,
    *,
    ns: int,
    margin: float,
):
    i = pl.program_id(0)  # n tile
    j = pl.program_id(1)  # center SLAB (innermost)

    @pl.when(j == 0)
    def _init_rows():
        # pruned rows start at their carried (idx, sim) and are final;
        # active rows start unassigned (-1, NEG)
        idx_ref[...] = idx0_ref[...]
        sim_ref[...] = sim0_ref[...]
        sec_ref[...] = jnp.full_like(sec_ref, NEG)

    @pl.when(jnp.logical_and(i == 0, j == 0))
    def _init_accumulators():
        sums_ref[...] = jnp.zeros_like(sums_ref)
        counts_ref[...] = jnp.zeros_like(counts_ref)
        min_ref[...] = jnp.full_like(min_ref, BIG)
        sq_ref[...] = jnp.zeros_like(sq_ref)

    x = x_ref[...]  # (BN, d)
    act = act_ref[...] > 0  # (BN, 1) row still needs the sweep
    rsq = rsq_ref[...]  # (BN, 1) ‖x‖²
    rep = rep_ref[...]  # (8, d): row 0 the slab representative, rest zero
    s = jax.lax.dot_general(
        x.astype(jnp.float32), rep, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=PRECISION,
    )[:, 0:1]  # (BN, 1)
    t = jnp.sqrt(jnp.maximum(rsq - s * s, 0.0))
    ub = (
        jnp.maximum(cone_ref[0, j] * s, cone_ref[1, j] * s)
        + cone_ref[2, j] * t
    )  # (BN, 1) cone bound on any member similarity

    cur = sim_ref[...]  # running best only grows, so the skip test is safe
    need = jnp.any(jnp.logical_and(act, ub >= cur - margin))

    @pl.when(need)
    def _sweep():
        c = c_ref[...]  # (BK, d) permuted centers
        sims = jax.lax.dot_general(
            x, c, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=dot_precision(x.dtype),
        )  # (BN, BK)
        orig = perm_ref[...]  # (1, BK) original ids; -1 = pad
        valid = jnp.logical_and(orig >= 0, act)
        sims = jnp.where(valid, sims, NEG)
        lmax = jnp.max(sims, axis=1, keepdims=True)
        cand = sims == lmax
        orig_b = jnp.broadcast_to(orig, sims.shape)
        lorig = jnp.min(
            jnp.where(cand, orig_b, BIG_I), axis=1, keepdims=True
        )  # lowest ORIGINAL id among slab ties
        winner = jnp.logical_and(cand, orig_b == lorig)
        lsec = jnp.max(jnp.where(winner, NEG, sims), axis=1, keepdims=True)

        has = lmax > NEG  # fully-masked rows (settled/pad) update nothing
        best = sim_ref[...]
        bidx = idx_ref[...]
        better = jnp.logical_and(
            has,
            jnp.logical_or(
                lmax > best, jnp.logical_and(lmax == best, lorig < bidx)
            ),
        )
        sim_ref[...] = jnp.where(better, lmax, best)
        idx_ref[...] = jnp.where(better, lorig, bidx)
        # top-2 value fold: second' = max(second, slab second, min(best, slab max))
        sec_ref[...] = jnp.maximum(
            jnp.maximum(sec_ref[...], jnp.where(has, lsec, NEG)),
            jnp.minimum(best, jnp.where(has, lmax, NEG)),
        )

    @pl.when(jnp.logical_not(need))
    def _skip():
        # the slab was not searched: its cone bound caps every member, and it
        # cannot hold the winner (ub < running best), so it belongs in hi
        sec_ref[...] = jnp.maximum(sec_ref[...], jnp.where(act, ub, NEG))

    @pl.when(j == ns - 1)
    def _combine():
        idx = idx_ref[...]  # (BN, 1) final assignment (original ids; -1 pad)
        sim = sim_ref[...]
        wv = w_ref[...]
        kp = sums_ref.shape[0]
        bn_ = idx.shape[0]

        bins = jax.lax.broadcasted_iota(jnp.int32, (kp, bn_), 0)
        hot = bins == idx[:, 0][None, :]  # idx -1 matches no bin
        wrow = wv[:, 0][None, :]
        hot_w = jnp.where(hot, wrow, 0.0).astype(jnp.float32)

        xf = x.astype(jnp.float32)
        sums_ref[...] += jax.lax.dot_general(
            hot_w,
            xf[:, : sums_ref.shape[1]],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=PRECISION,
        )
        counts_ref[...] += jnp.sum(hot_w, axis=1, keepdims=True)
        rowsq = jnp.sum(xf * xf, axis=1)
        sq_ref[...] += jnp.sum(hot_w * rowsq[None, :], axis=1, keepdims=True)
        member = jnp.where(
            jnp.logical_and(hot, wrow > 0), sim[:, 0][None, :], BIG
        )
        min_ref[...] = jnp.minimum(
            min_ref[...], jnp.min(member, axis=1, keepdims=True)
        )


@functools.partial(
    jax.jit, static_argnames=("interpret", "bn", "bk", "bd", "margin")
)
def assign_stats_bounded_pallas(
    x: jax.Array,
    centers: jax.Array,
    prev_idx: jax.Array,
    lo: jax.Array,
    hi: jax.Array,
    drift: jax.Array,
    w: jax.Array | None = None,
    *,
    perm: jax.Array | None = None,
    margin: float = PRUNE_MARGIN,
    interpret: bool = False,
    bn: int = BN,
    bk: int = BK,
    bd: int | None = None,
):
    """Bound-pruned fused pass; contract identical to ref.assign_stats_bounded.

    ``perm`` is a (k,) slab-ordering permutation (ops.build_center_index);
    None falls back to the identity order (cone bounds still skip slabs, just
    less often). Returns the 10-tuple (idx, best_sim, sums, counts, min_sim,
    sumsq, idx, lo_out, hi_out, pruned) with labels in ORIGINAL center ids.
    """
    n, d = x.shape
    k = centers.shape[0]
    bn = min(bn, max(8, n))
    bk = min(bk, max(8, k))
    dmult = 128 if d >= 128 else 8

    if perm is None:
        perm = jnp.arange(k, dtype=jnp.int32)
    cperm = centers[perm]
    xp = _pad_to(_pad_to(x, 0, bn), 1, dmult)
    cp = _pad_to(_pad_to(cperm, 0, bk), 1, dmult)
    permp = _pad_to(perm.astype(jnp.int32)[None, :] + 1, 1, bk) - 1  # pad -> -1
    wv = jnp.ones((n,), jnp.float32) if w is None else w.astype(jnp.float32)
    wp = _pad_to(wv[:, None], 0, bn)
    np_, dp = xp.shape
    kp_c = cp.shape[0]
    ns = kp_c // bk  # number of center slabs
    kp = k + ((-k) % 8)
    grid = (np_ // bn, ns)

    # ---- row-level bound prep (XLA side: O(n·d), dwarfed by the sweep)
    xf = x.astype(jnp.float32)
    rowsq = jnp.einsum("nd,nd->n", xf, xf, precision=PRECISION)
    rownorm = jnp.sqrt(rowsq)
    ok, pidx, lo_adj, hi_adj = ref.deflate_bounds(
        prev_idx, lo, hi, rownorm, drift
    )
    pruned = jnp.logical_and(ok, lo_adj > hi_adj + margin)
    sim_prev = jnp.einsum(
        "nd,nd->n", xf, centers[pidx].astype(jnp.float32), precision=PRECISION
    )  # settled rows' final similarity, without the k sweep
    act = jnp.where(pruned, 0.0, 1.0).astype(jnp.float32)
    idx0 = jnp.where(pruned, pidx, -1).astype(jnp.int32)
    sim0 = jnp.where(pruned, sim_prev, NEG).astype(jnp.float32)
    actp = _pad_to(act[:, None], 0, bn)  # pad rows act=0: never force a sweep
    rsqp = _pad_to(rowsq[:, None], 0, bn)
    idx0p = _pad_to(idx0[:, None] + 1, 0, bn) - 1  # pad -> -1 (no stats bin)
    sim0p = _pad_to(sim0[:, None], 0, bn)

    # ---- slab cone bounds (XLA side: O(k·d))
    c3 = cp.reshape(ns, bk, dp).astype(jnp.float32)
    m3 = permp.reshape(ns, bk) >= 0
    cnt = jnp.sum(m3, axis=1).astype(jnp.float32)  # (ns,)
    mean = jnp.sum(c3 * m3[..., None], axis=1) / jnp.maximum(cnt, 1.0)[:, None]
    mnorm = jnp.sqrt(jnp.sum(mean * mean, axis=1, keepdims=True))
    rep = mean / jnp.maximum(mnorm, 1e-12)  # (ns, dp); empty slab -> 0
    a = jnp.einsum("sbd,sd->sb", c3, rep, precision=PRECISION)
    csq = jnp.sum(c3 * c3, axis=2)
    bperp = jnp.sqrt(jnp.maximum(csq - a * a, 0.0))
    nonempty = cnt > 0
    a_pos = jnp.where(
        nonempty, jnp.max(jnp.where(m3, a, NEG), axis=1), 0.0
    )
    a_neg = jnp.where(
        nonempty, jnp.min(jnp.where(m3, a, BIG), axis=1), 0.0
    )
    b_max = jnp.where(
        nonempty, jnp.max(jnp.where(m3, bperp, 0.0), axis=1), 0.0
    )
    # Mosaic tiles a VMEM block in (8, 128): each slab's representative rides
    # as row 0 of its own zero-padded 8-row block, and the three per-slab
    # cone scalars live whole in SMEM, read by slab index.
    rep8 = jnp.zeros((ns, 8, dp), jnp.float32).at[:, 0].set(rep)
    rep8 = rep8.reshape(ns * 8, dp)
    cone = jnp.stack([a_pos, a_neg, b_max])  # (3, ns)

    if bd is None:
        bd = ACC_BUDGET // (kp * 4)
    bd_sums = min(dp, max(dmult, (bd // dmult) * dmult))

    idx, sim, sec, sums, counts, min_sim, sumsq = pl.pallas_call(
        functools.partial(_bounded_kernel, ns=ns, margin=margin),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, dp), lambda i, j: (i, 0)),
            pl.BlockSpec((bk, dp), lambda i, j: (j, 0)),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, bk), lambda i, j: (0, j)),
            pl.BlockSpec((8, dp), lambda i, j: (j, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((kp, bd_sums), lambda i, j: (0, 0)),
            pl.BlockSpec((kp, 1), lambda i, j: (0, 0)),
            pl.BlockSpec((kp, 1), lambda i, j: (0, 0)),
            pl.BlockSpec((kp, 1), lambda i, j: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((np_, 1), jnp.int32),
            jax.ShapeDtypeStruct((np_, 1), jnp.float32),
            jax.ShapeDtypeStruct((np_, 1), jnp.float32),
            jax.ShapeDtypeStruct((kp, bd_sums), jnp.float32),
            jax.ShapeDtypeStruct((kp, 1), jnp.float32),
            jax.ShapeDtypeStruct((kp, 1), jnp.float32),
            jax.ShapeDtypeStruct((kp, 1), jnp.float32),
        ],
        compiler_params=_fused_vmem(
            bn=bn, bk=bk, dp=dp, isz=xp.dtype.itemsize, kp=kp,
            acc_cols=bd_sums,
            # the slab's (8, d) representative block, and the cone dot's f32
            # copy of the x tile with its precision split
            extra=2 * 8 * dp * 4 + bn * dp * 4 + split_bytes((bn * dp, 4)),
        ),
        interpret=interpret,
    )(xp, cp, wp, actp, rsqp, idx0p, sim0p, permp, rep8, cone)
    idx_n = idx[:n, 0]
    sim_n = sim[:n, 0]
    if bd_sums < d:
        tail, _ = label_stats_pallas(
            x[:, bd_sums:], idx_n, k, wv, interpret=interpret, bn=bn
        )
        full_sums = jnp.concatenate([sums[:k, :bd_sums], tail], axis=1)
    else:
        full_sums = sums[:k, :d]
    lo_out = sim_n
    hi_out = jnp.where(pruned, hi_adj, sec[:n, 0])
    return (
        idx_n,
        sim_n,
        full_sums,
        counts[:k, 0],
        min_sim[:k, 0],
        sumsq[:k, 0],
        idx_n,
        lo_out,
        hi_out,
        pruned,
    )


# ------------------------------------------------------------- label stats


def _label_stats_kernel(idx_ref, w_ref, x_ref, sums_ref, counts_ref):
    i = pl.program_id(0)  # d tile
    j = pl.program_id(1)  # n tile (innermost)

    @pl.when(j == 0)
    def _init_sums():
        sums_ref[...] = jnp.zeros_like(sums_ref)

    @pl.when(jnp.logical_and(i == 0, j == 0))
    def _init_counts():
        counts_ref[...] = jnp.zeros_like(counts_ref)

    idx = idx_ref[...]  # (BN, 1) int32
    wv = w_ref[...]  # (BN, 1) f32 (0 for padding / excluded rows)
    x = x_ref[...]  # (BN, BD)
    kp = sums_ref.shape[0]

    bins = jax.lax.broadcasted_iota(jnp.int32, (kp, idx.shape[0]), 0)
    hot = bins == idx[:, 0][None, :]  # oob labels (e.g. -1) match no bin
    hot_w = jnp.where(hot, wv[:, 0][None, :], 0.0).astype(jnp.float32)

    sums_ref[...] += jax.lax.dot_general(
        hot_w,
        x.astype(jnp.float32),
        (((1,), (0,)), ((), ())),  # (kp, BN) @ (BN, BD)
        preferred_element_type=jnp.float32,
        precision=PRECISION,
    )

    @pl.when(i == 0)
    def _counts():
        counts_ref[...] += jnp.sum(hot_w, axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("k", "interpret", "bn", "bd"))
def label_stats_pallas(
    x: jax.Array,
    idx: jax.Array,
    k: int,
    w: jax.Array | None = None,
    *,
    interpret: bool = False,
    bn: int = BN,
    bd: int = BD,
) -> tuple[jax.Array, jax.Array]:
    """(n, d), (n,)[, (n,)] -> ((k, d) weighted sums, (k,) weight totals).

    The d-tiled accumulator grid: (d_tiles, n_tiles), n innermost, so each
    (kp, BD) sums block stays VMEM-resident for one full document sweep and
    k*d is bounded per-tile, not in total. Weights subsume row-padding
    masking (padded rows carry weight 0); out-of-range labels fall into no
    bin, matching ref.label_stats.
    """
    n, d = x.shape
    bn = min(bn, max(8, n))
    kp = k + ((-k) % 8)  # sublane-align the bin dimension
    dmult = 128 if d >= 128 else 8

    wv = jnp.ones((n,), jnp.float32) if w is None else w.astype(jnp.float32)
    xp = _pad_to(_pad_to(x, 0, bn), 1, dmult)  # lane-align d like the siblings
    # block width: lane-aligned, inside the VMEM budget, at most the padded d
    bd_cap = max(dmult, (ACC_BUDGET // (kp * 4) // dmult) * dmult)
    bd = min(max(dmult, (bd // dmult) * dmult), bd_cap, xp.shape[1])
    xp = _pad_to(xp, 1, bd)  # grid-divisible; zero columns contribute nothing
    idxp = _pad_to(idx.astype(jnp.int32)[:, None] + 1, 0, bn) - 1  # pad -> -1
    wp = _pad_to(wv[:, None], 0, bn)
    np_, dp = xp.shape
    grid = (dp // bd, np_ // bn)

    sums, counts = pl.pallas_call(
        _label_stats_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, 1), lambda i, j: (j, 0)),
            pl.BlockSpec((bn, 1), lambda i, j: (j, 0)),
            pl.BlockSpec((bn, bd), lambda i, j: (j, i)),
        ],
        out_specs=[
            pl.BlockSpec((kp, bd), lambda i, j: (0, i)),
            pl.BlockSpec((kp, 1), lambda i, j: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((kp, dp), jnp.float32),
            jax.ShapeDtypeStruct((kp, 1), jnp.float32),
        ],
        interpret=interpret,
    )(idxp, wp, xp)
    return sums[:k, :d], counts[:k, 0]
