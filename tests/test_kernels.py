"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps + hypothesis.

Every kernel runs in interpret mode (kernel body executed on CPU); the oracle
is repro.kernels.ref. Sweeps deliberately include sizes that don't divide the
block shapes (padding paths) and degenerate sizes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # offline container: skip property-based tests only
    from hypothesis_stub import given, settings, st

from repro.kernels import ops, ref
from repro.kernels.assign_argmax import assign_argmax_pallas
from repro.kernels.assign_stats import (
    ACC_BUDGET,
    assign_stats_pallas,
    label_stats_pallas,
)
from repro.kernels.best_edge import best_edge_pallas
from repro.kernels.component_reduce import component_best_edge_pallas
from repro.kernels.sim_best_edge import sim_best_edge_pallas

DTYPES = [jnp.float32, jnp.bfloat16]


def _rand(rng, shape, dtype):
    return jnp.asarray(rng.normal(size=shape).astype(np.float32)).astype(dtype)


# ------------------------------------------------------------ assign_argmax


@pytest.mark.parametrize("n,k,d", [(7, 3, 5), (64, 16, 32), (300, 17, 70),
                                   (513, 129, 130), (1024, 256, 128)])
@pytest.mark.parametrize("dtype", DTYPES)
def test_assign_argmax_sweep(rng, n, k, d, dtype):
    x = _rand(rng, (n, d), dtype)
    c = _rand(rng, (k, d), dtype)
    ri, rs = ref.assign_argmax(x, c)
    pi, ps = assign_argmax_pallas(x, c, interpret=True)
    np.testing.assert_array_equal(np.asarray(ri), np.asarray(pi))
    np.testing.assert_allclose(np.asarray(rs), np.asarray(ps), rtol=2e-2, atol=2e-2)


def test_assign_argmax_tie_breaks_lowest_index():
    # identical centers -> every doc must pick index 0
    x = jnp.ones((9, 4), jnp.float32)
    c = jnp.ones((5, 4), jnp.float32)
    pi, _ = assign_argmax_pallas(x, c, interpret=True)
    assert (np.asarray(pi) == 0).all()


def test_assign_argmax_tie_across_tiles(rng):
    # duplicate best center in tile 0 and tile 1 (bk=8): lowest index wins
    c = _rand(rng, (20, 16), jnp.float32)
    c = c.at[13].set(c[2])
    x = c[2][None, :] * jnp.ones((5, 1))
    pi, _ = assign_argmax_pallas(x, c, interpret=True, bk=8)
    assert (np.asarray(pi) == 2).all()


# ------------------------------------------------------------ assign_stats


def _assert_stats_close(got, want, *, exact=False):
    """Compare (idx, best_sim, sums, counts, min_sim, sumsq) tuples."""
    np.testing.assert_array_equal(np.asarray(want[0]), np.asarray(got[0]))
    np.testing.assert_array_equal(np.asarray(want[3]), np.asarray(got[3]))
    if exact:
        for a, b in zip(want[1:], got[1:]):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        return
    for a, b in zip(
        (want[1], want[2], want[4], want[5]), (got[1], got[2], got[4], got[5])
    ):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=2e-2, atol=1e-1,
        )


@pytest.mark.parametrize("n,k,d", [(7, 3, 5), (64, 16, 32), (300, 17, 70),
                                   (513, 129, 130)])
@pytest.mark.parametrize("dtype", DTYPES)
def test_assign_stats_sweep(rng, n, k, d, dtype):
    x = _rand(rng, (n, d), dtype)
    c = _rand(rng, (k, d), dtype)
    want = ref.assign_stats(x, c)
    got = assign_stats_pallas(x, c, interpret=True)
    _assert_stats_close(got, want)


def test_assign_stats_exact_integer_data(rng):
    """Integer-valued f32 inputs: every sum is exactly representable, so the
    fused kernel must match the oracle BIT-FOR-BIT in interpret mode."""
    x = jnp.asarray(rng.integers(-8, 9, size=(300, 70)).astype(np.float32))
    c = jnp.asarray(rng.integers(-8, 9, size=(17, 70)).astype(np.float32))
    want = ref.assign_stats(x, c)
    got = assign_stats_pallas(x, c, interpret=True)
    _assert_stats_close(got, want, exact=True)
    # and the scatter-based XLA production path agrees bit-for-bit too
    _assert_stats_close(ref.assign_stats_scatter(x, c), want, exact=True)


def test_assign_stats_tie_breaks_match_assign_argmax(rng):
    # duplicate best center in k-tile 0 and k-tile 1 (bk=8): first max wins,
    # exactly like assign_argmax
    c = _rand(rng, (20, 16), jnp.float32)
    c = c.at[13].set(c[2])
    x = c[2][None, :] * jnp.ones((5, 1))
    ai, _ = assign_argmax_pallas(x, c, interpret=True, bk=8)
    si, _, _, counts, _, _ = assign_stats_pallas(x, c, interpret=True, bk=8)
    np.testing.assert_array_equal(np.asarray(ai), np.asarray(si))
    assert (np.asarray(si) == 2).all()
    assert float(counts[2]) == 5.0 and float(counts[13]) == 0.0


def test_assign_stats_empty_clusters(rng):
    # positive rows + one dominant positive center: everything lands in
    # cluster 0, clusters 1-4 must have zero stats and BIG min_sim
    x = jnp.abs(_rand(rng, (10, 8), jnp.float32)) + 0.1
    c = jnp.concatenate(
        [jnp.full((1, 8), 100.0), jnp.full((4, 8), -100.0)]
    )
    idx, _, sums, counts, min_sim, sumsq = assign_stats_pallas(x, c, interpret=True)
    assert (np.asarray(idx) == 0).all()
    assert float(counts[0]) == 10.0 and (np.asarray(counts[1:]) == 0).all()
    assert (np.abs(np.asarray(sums[1:])) < 1e-6).all()
    assert (np.asarray(sumsq[1:]) == 0).all()
    assert (np.asarray(min_sim[1:]) == ref.BIG).all()
    assert float(min_sim[0]) < ref.BIG


def test_assign_stats_weights_exclude_rows(rng):
    """Weight-0 rows must not contribute to any statistic (the distributed
    padding-row contract)."""
    n, k, d = 40, 5, 12
    x = _rand(rng, (n, d), jnp.float32)
    c = _rand(rng, (k, d), jnp.float32)
    w = jnp.asarray((rng.random(n) > 0.3).astype(np.float32))
    keep = np.asarray(w) > 0
    want = ref.assign_stats(x[keep], c)
    for impl_out in (
        assign_stats_pallas(x, c, w, interpret=True),
        ref.assign_stats_scatter(x, c, w),
    ):
        np.testing.assert_allclose(
            np.asarray(want[2]), np.asarray(impl_out[2]), rtol=1e-5, atol=1e-5
        )
        np.testing.assert_array_equal(np.asarray(want[3]), np.asarray(impl_out[3]))
        np.testing.assert_allclose(
            np.asarray(want[4]), np.asarray(impl_out[4]), rtol=1e-6
        )


def test_assign_stats_chunked_equals_oneshot_bitforbit(rng):
    """The streaming wrapper must equal the one-shot path bit-for-bit
    (integer-valued data makes every accumulation order exact)."""
    x = jnp.asarray(rng.integers(-8, 9, size=(1000, 33)).astype(np.float32))
    c = jnp.asarray(rng.integers(-8, 9, size=(11, 33)).astype(np.float32))
    w = jnp.asarray((rng.random(1000) > 0.1).astype(np.float32))
    for impl in ("xla", "pallas_interpret"):
        for wa in (None, w):
            one = ops.assign_stats(x, c, wa, impl=impl)
            for chunk in (256, 250):  # divides n / does not divide n
                chk = ops.assign_stats_chunked(x, c, wa, chunk=chunk, impl=impl)
                for a, b, name in zip(one, chk, one._fields):
                    np.testing.assert_array_equal(
                        np.asarray(a), np.asarray(b), err_msg=f"{impl}:{name}"
                    )


def test_assign_stats_scatter_matches_oracle(rng):
    x = _rand(rng, (200, 40), jnp.float32)
    c = _rand(rng, (9, 40), jnp.float32)
    _assert_stats_close(ref.assign_stats_scatter(x, c), ref.assign_stats(x, c))


def test_ops_assign_stats_dispatch(rng):
    x = _rand(rng, (100, 33), jnp.float32)
    c = _rand(rng, (9, 33), jnp.float32)
    s1 = ops.assign_stats(x, c, impl="xla")
    s2 = ops.assign_stats(x, c, impl="pallas_interpret")
    np.testing.assert_array_equal(np.asarray(s1.idx), np.asarray(s2.idx))
    np.testing.assert_array_equal(np.asarray(s1.counts), np.asarray(s2.counts))
    np.testing.assert_allclose(
        np.asarray(s1.sums), np.asarray(s2.sums), rtol=1e-4, atol=1e-4
    )


# ------------------------------------------------------------ best_edge


@pytest.mark.parametrize("r,c,labels", [(6, 6, 2), (90, 121, 5), (256, 256, 9),
                                        (33, 257, 4)])
def test_best_edge_sweep(rng, r, c, labels):
    sim = _rand(rng, (r, c), jnp.float32)
    lr = jnp.asarray(rng.integers(0, labels, size=r).astype(np.int32))
    lc = jnp.asarray(rng.integers(0, labels, size=c).astype(np.int32))
    rj, rs_ = ref.best_edge(sim, lr, lc)
    pj, ps = best_edge_pallas(sim, lr, lc, interpret=True)
    np.testing.assert_array_equal(np.asarray(rj), np.asarray(pj))
    np.testing.assert_allclose(np.asarray(rs_), np.asarray(ps), rtol=1e-6)


def test_best_edge_all_same_component(rng):
    sim = _rand(rng, (12, 12), jnp.float32)
    lab = jnp.zeros((12,), jnp.int32)
    pj, ps = best_edge_pallas(sim, lab, lab, interpret=True)
    assert (np.asarray(pj) == -1).all()
    assert (np.asarray(ps) == float(jnp.finfo(jnp.float32).min)).all()


# ------------------------------------------------------------ sim_best_edge


@pytest.mark.parametrize("r,c,labels", [(6, 6, 2), (90, 121, 5), (256, 256, 9),
                                        (33, 257, 4), (300, 70, 3)])
@pytest.mark.parametrize("dtype", DTYPES)
def test_sim_best_edge_sweep(rng, r, c, labels, dtype):
    """Fused sim+edge kernel vs oracle, including non-divisible tile shapes."""
    xr = _rand(rng, (r, 40), dtype)
    xc = _rand(rng, (c, 40), dtype)
    lr = jnp.asarray(rng.integers(0, labels, size=r).astype(np.int32))
    lc = jnp.asarray(rng.integers(0, labels, size=c).astype(np.int32))
    rj, rs_ = ref.sim_best_edge(xr, xc, lr, lc)
    pj, ps = sim_best_edge_pallas(xr, xc, lr, lc, interpret=True)
    np.testing.assert_array_equal(np.asarray(rj), np.asarray(pj))
    np.testing.assert_allclose(np.asarray(rs_), np.asarray(ps), rtol=2e-2, atol=2e-2)


def test_sim_best_edge_exact_integer_data(rng):
    """Integer-valued f32 inputs: similarities are exactly representable, so
    the kernel, the oracle, and the chunked XLA path must agree bit-for-bit."""
    xr = jnp.asarray(rng.integers(-6, 7, size=(130, 48)).astype(np.float32))
    xc = jnp.asarray(rng.integers(-6, 7, size=(97, 48)).astype(np.float32))
    lr = jnp.asarray(rng.integers(0, 5, size=130).astype(np.int32))
    lc = jnp.asarray(rng.integers(0, 5, size=97).astype(np.int32))
    rj, rs_ = ref.sim_best_edge(xr, xc, lr, lc)
    pj, ps = sim_best_edge_pallas(xr, xc, lr, lc, interpret=True)
    np.testing.assert_array_equal(np.asarray(rj), np.asarray(pj))
    np.testing.assert_array_equal(np.asarray(rs_), np.asarray(ps))
    for block in (32, 50):  # divides r / does not divide r
        cj, cs = ops.sim_best_edge(xr, xc, lr, lc, impl="xla", block=block)
        np.testing.assert_array_equal(np.asarray(rj), np.asarray(cj))
        np.testing.assert_array_equal(np.asarray(rs_), np.asarray(cs))


def test_sim_best_edge_tie_across_tiles(rng):
    """A duplicate best column in tile 0 and tile 1 (bc=8): lowest col wins,
    in the kernel and in the chunked XLA path alike."""
    xc = _rand(rng, (20, 16), jnp.float32)
    xc = xc.at[13].set(xc[2])
    xr = xc[2][None, :] * jnp.ones((5, 1))
    lr = jnp.zeros((5,), jnp.int32)
    lc = jnp.ones((20,), jnp.int32)  # all cols cross-component
    pj, _ = sim_best_edge_pallas(xr, xc, lr, lc, interpret=True, bc=8)
    assert (np.asarray(pj) == 2).all()
    cj, _ = ops.sim_best_edge(xr, xc, lr, lc, impl="xla", block=2)
    assert (np.asarray(cj) == 2).all()


def test_sim_best_edge_all_same_component(rng):
    xs = _rand(rng, (12, 8), jnp.float32)
    lab = jnp.zeros((12,), jnp.int32)
    pj, ps = sim_best_edge_pallas(xs, xs, lab, lab, interpret=True)
    assert (np.asarray(pj) == -1).all()
    assert (np.asarray(ps) == float(jnp.finfo(jnp.float32).min)).all()


def test_sim_best_edge_self_column_excluded_by_labels(rng):
    """A point's own column is same-component, so the fused path never
    proposes a self-edge even though the diagonal similarity is maximal."""
    xs = _rand(rng, (40, 16), jnp.float32)
    from repro.common import l2_normalize

    xs = l2_normalize(xs)
    lab = jnp.arange(40, dtype=jnp.int32)  # all singletons
    pj, _ = sim_best_edge_pallas(xs, xs, lab, lab, interpret=True)
    assert (np.asarray(pj) != np.arange(40)).all()


def test_best_edge_negative_row_labels_propose_nothing(rng):
    """Pad rows (label -1) are masked out of the map itself: (-1, f32.min)
    on every implementation, even though -1 != every column label."""
    neg = float(jnp.finfo(jnp.float32).min)
    xr = _rand(rng, (30, 16), jnp.float32)
    xc = _rand(rng, (25, 16), jnp.float32)
    lr = jnp.asarray(rng.integers(0, 4, size=30).astype(np.int32))
    lr = lr.at[::3].set(-1)
    lc = jnp.asarray(rng.integers(0, 4, size=25).astype(np.int32))
    for bj, bs in (
        ref.sim_best_edge(xr, xc, lr, lc),
        sim_best_edge_pallas(xr, xc, lr, lc, interpret=True),
        ops.sim_best_edge(xr, xc, lr, lc, impl="xla", block=8),
        ref.best_edge(xr @ xc.T, lr, lc),
        best_edge_pallas(xr @ xc.T, lr, lc, interpret=True),
    ):
        assert (np.asarray(bj)[::3] == -1).all()
        assert (np.asarray(bs)[::3] == neg).all()
        assert (np.asarray(bj)[1::3] >= 0).all()  # real rows still propose


# ------------------------------------------------------- d-tiled sim_best_edge


def test_sim_best_edge_forced_d_tiling_bitexact(rng):
    """bd override forces the d grid dimension at small sizes: the scratch
    accumulator path must equal the single-d-tile path and the oracle
    bit-for-bit on integer data."""
    xr = jnp.asarray(rng.integers(-6, 7, size=(130, 300)).astype(np.float32))
    xc = jnp.asarray(rng.integers(-6, 7, size=(97, 300)).astype(np.float32))
    lr = jnp.asarray(rng.integers(0, 5, size=130).astype(np.int32))
    lc = jnp.asarray(rng.integers(0, 5, size=97).astype(np.int32))
    rj, rs_ = ref.sim_best_edge(xr, xc, lr, lc)
    one_j, one_s = sim_best_edge_pallas(xr, xc, lr, lc, interpret=True)
    for bd in (128, 256):  # 300 pads to 384 -> 3 / 2 d steps
        pj, ps = sim_best_edge_pallas(xr, xc, lr, lc, interpret=True, bd=bd)
        np.testing.assert_array_equal(np.asarray(rj), np.asarray(pj))
        np.testing.assert_array_equal(np.asarray(rs_), np.asarray(ps))
        np.testing.assert_array_equal(np.asarray(one_j), np.asarray(pj))
        np.testing.assert_array_equal(np.asarray(one_s), np.asarray(ps))


def test_sim_best_edge_d_beyond_vmem_ceiling(rng):
    """d = 16384 (2x the old ~8k f32 ceiling): the default wrapper must
    engage the d grid dimension and stay bit-exact vs the oracle on integer
    data."""
    from repro.kernels.sim_best_edge import BD

    d = 16384
    assert d > 2 * BD, "test must exceed the single-tile contraction width"
    xr = jnp.asarray(rng.integers(-3, 4, size=(48, d)).astype(np.float32))
    lr = jnp.asarray(rng.integers(0, 4, size=48).astype(np.int32))
    rj, rs_ = ref.sim_best_edge(xr, xr, lr, lr)
    pj, ps = sim_best_edge_pallas(xr, xr, lr, lr, interpret=True)
    np.testing.assert_array_equal(np.asarray(rj), np.asarray(pj))
    np.testing.assert_array_equal(np.asarray(rs_), np.asarray(ps))


# ------------------------------------------------------ component pre-reduce


@pytest.mark.parametrize("r,c", [(7, 3), (64, 64), (130, 9), (513, 40),
                                 (300, 700)])
def test_component_best_edge_sweep(rng, r, c):
    """Segmented pre-reduce vs the lexsort oracle, pallas AND xla paths —
    including NEG no-edge rows, duplicate weights, out-of-range (pad) comp
    ids, and c > r (more segments than candidates)."""
    neg = float(jnp.finfo(jnp.float32).min)
    w = jnp.asarray(rng.normal(size=r).astype(np.float32))
    w = w.at[::5].set(neg)  # rows with no cross-component edge
    if r > 10:
        w = w.at[3].set(w[8])  # duplicate weight: row id must tie-break
    col = jnp.asarray(rng.integers(-1, 64, size=r).astype(np.int32))
    rows = jnp.asarray(rng.permutation(2 * r)[:r].astype(np.int32))
    comp = jnp.asarray(rng.integers(0, c + 1, size=r).astype(np.int32))
    want = ref.component_best_edge(w, col, rows, comp, c)
    got_p = component_best_edge_pallas(w, col, rows, comp, c, interpret=True)
    got_x = ops.component_best_edge(w, col, rows, comp, c, impl="xla")
    for a, b, bx, name in zip(want, got_p, got_x, ("w", "row", "col")):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"pallas:{name}")
        np.testing.assert_array_equal(np.asarray(a), np.asarray(bx),
                                      err_msg=f"xla:{name}")


def test_component_best_edge_empty_and_pad_segments(rng):
    """Empty segments carry the reduce identities (f32.min, BIG_I, -1) so
    the cross-shard 'component' fold treats them as perfect losers; pad rows
    tagged comp == c contribute to no segment."""
    neg = float(jnp.finfo(jnp.float32).min)
    w = jnp.asarray([1.0, 2.0, 3.0, 9.0], jnp.float32)
    col = jnp.asarray([5, 6, 7, 8], jnp.int32)
    rows = jnp.asarray([0, 1, 2, 3], jnp.int32)
    comp = jnp.asarray([0, 0, 2, 4], jnp.int32)  # comp 1, 3 empty; 4 == c pad
    for bw, brow, bcol in (
        ref.component_best_edge(w, col, rows, comp, 4),
        component_best_edge_pallas(w, col, rows, comp, 4, interpret=True),
        ops.component_best_edge(w, col, rows, comp, 4, impl="xla"),
    ):
        np.testing.assert_array_equal(
            np.asarray(bw), np.asarray([2.0, neg, 3.0, neg], np.float32))
        np.testing.assert_array_equal(
            np.asarray(brow), np.asarray([1, ref.BIG_I, 2, ref.BIG_I]))
        np.testing.assert_array_equal(np.asarray(bcol),
                                      np.asarray([6, -1, 7, -1]))


def test_component_best_edge_lexicographic_tie(rng):
    """Equal weights inside a segment: the LOWEST global row id wins, across
    tile boundaries too (bn=8 forces multiple row tiles)."""
    r = 40
    w = jnp.full((r,), 0.5, jnp.float32)
    col = jnp.arange(r, dtype=jnp.int32) + 100
    rows = jnp.asarray((np.arange(r)[::-1]).astype(np.int32))  # descending
    comp = jnp.zeros((r,), jnp.int32)
    for bw, brow, bcol in (
        ref.component_best_edge(w, col, rows, comp, 1),
        component_best_edge_pallas(w, col, rows, comp, 1, interpret=True,
                                   bn=8),
        ops.component_best_edge(w, col, rows, comp, 1, impl="xla"),
    ):
        assert float(bw[0]) == 0.5
        assert int(brow[0]) == 0  # lowest row id (held by the LAST position)
        assert int(bcol[0]) == 100 + r - 1


# ------------------------------------------------------------ label_stats


@pytest.mark.parametrize("n,k,d", [(5, 2, 3), (64, 8, 16), (333, 17, 70),
                                   (400, 100, 257)])
def test_label_stats_sweep(rng, n, k, d):
    x = _rand(rng, (n, d), jnp.float32)
    idx = jnp.asarray(rng.integers(0, k, size=n).astype(np.int32))
    w = jnp.asarray((rng.random(n) > 0.3).astype(np.float32))
    for wa in (None, w):
        rs_, rc = ref.label_stats(x, idx, k, wa)
        ss_, sc = ref.label_stats_scatter(x, idx, k, wa)
        ps_, pc = label_stats_pallas(x, idx, k, wa, interpret=True)
        np.testing.assert_allclose(np.asarray(rs_), np.asarray(ps_),
                                   rtol=2e-2, atol=1e-1)
        np.testing.assert_allclose(np.asarray(rs_), np.asarray(ss_),
                                   rtol=2e-2, atol=1e-1)
        np.testing.assert_array_equal(np.asarray(rc), np.asarray(pc))
        np.testing.assert_array_equal(np.asarray(rc), np.asarray(sc))


def test_label_stats_drops_out_of_range_labels(rng):
    """-1 padding labels (the distributed sample-HAC pad contract) must fall
    into no bin on every implementation."""
    x = jnp.asarray(rng.integers(-4, 5, size=(50, 12)).astype(np.float32))
    idx = jnp.asarray(rng.integers(-1, 4, size=50).astype(np.int32))
    want = ref.label_stats(x, idx, 4)
    for got in (
        ref.label_stats_scatter(x, idx, 4),
        label_stats_pallas(x, idx, 4, interpret=True),
        ops.label_stats(x, idx, 4, impl="xla"),
    ):
        np.testing.assert_array_equal(np.asarray(want[0]), np.asarray(got[0]))
        np.testing.assert_array_equal(np.asarray(want[1]), np.asarray(got[1]))
    keep = np.asarray(idx) >= 0
    np.testing.assert_array_equal(
        np.asarray(want[0]),
        np.asarray(ref.label_stats(x[keep], idx[keep], 4)[0]),
    )


def test_label_stats_matches_cluster_stats_oracle(rng):
    """Unweighted label_stats == the retired cluster_stats combiner (whose
    one-hot oracle survives in ref as the ground truth)."""
    x = _rand(rng, (200, 33), jnp.float32)
    idx = jnp.asarray(rng.integers(0, 9, size=200).astype(np.int32))
    cs_, cc = ref.cluster_stats(x, idx, 9)
    ls_, lc = label_stats_pallas(x, idx, 9, interpret=True)
    np.testing.assert_allclose(np.asarray(cs_), np.asarray(ls_),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(cc), np.asarray(lc))


# ------------------------------------------------------------ d-tiled fused


def test_assign_stats_forced_d_split_bitexact(rng):
    """bd override forces the accumulator split at small sizes: the head
    kernel + label_stats tail must equal the single-tile path bit-for-bit on
    integer data."""
    x = jnp.asarray(rng.integers(-8, 9, size=(300, 300)).astype(np.float32))
    c = jnp.asarray(rng.integers(-8, 9, size=(17, 300)).astype(np.float32))
    want = assign_stats_pallas(x, c, interpret=True)  # fits in one tile
    got = assign_stats_pallas(x, c, interpret=True, bd=128)
    for a, b, name in zip(want, got, ops.AssignStats._fields):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=name)


def test_assign_stats_d_tiled_beyond_vmem_ceiling(rng):
    """k*d = 2048x4096 (4x the ACC_BUDGET ceiling): the auto d-split must
    engage and stay bit-exact against the oracle on integer data."""
    n, k, d = 96, 2048, 4096
    assert k * d * 4 > ACC_BUDGET, "test must exceed the single-tile budget"
    x = jnp.asarray(rng.integers(-4, 5, size=(n, d)).astype(np.float32))
    c = jnp.asarray(rng.integers(-4, 5, size=(k, d)).astype(np.float32))
    want = ref.assign_stats(x, c)
    got = assign_stats_pallas(x, c, interpret=True)
    for a, b, name in zip(want, got, ops.AssignStats._fields):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=name)
    # and the scatter-based XLA production path agrees bit-for-bit too
    gsc = ref.assign_stats_scatter(x, c)
    for a, b, name in zip(want, gsc, ops.AssignStats._fields):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=name)


def test_assign_stats_chunked_equals_oneshot_d_tiled(rng):
    """Chunked-vs-oneshot bit parity THROUGH the d-tiled accumulator path
    (k*d beyond the single-tile budget), weighted and unweighted."""
    n, k, d = 600, 2048, 4096
    x = jnp.asarray(rng.integers(-3, 4, size=(n, d)).astype(np.float32))
    c = jnp.asarray(rng.integers(-3, 4, size=(k, d)).astype(np.float32))
    w = jnp.asarray((rng.random(n) > 0.1).astype(np.float32))
    for wa in (None, w):
        one = ops.assign_stats(x, c, wa, impl="pallas_interpret")
        chk = ops.assign_stats_chunked(
            x, c, wa, chunk=250, impl="pallas_interpret"  # does not divide n
        )
        for a, b, name in zip(one, chk, one._fields):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b), err_msg=name
            )


# ------------------------------------------------------------ ops dispatch


def test_ops_dispatch_xla_equals_interpret(rng):
    x = _rand(rng, (100, 33), jnp.float32)
    c = _rand(rng, (9, 33), jnp.float32)
    for impl in ("xla", "pallas_interpret"):
        i, s = ops.assign_argmax(x, c, impl=impl)
        assert i.shape == (100,) and s.shape == (100,)
    i1, _ = ops.assign_argmax(x, c, impl="xla")
    i2, _ = ops.assign_argmax(x, c, impl="pallas_interpret")
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))


# ------------------------------------------------------------ hypothesis


@settings(max_examples=25, deadline=None)
@given(
    n=st.integers(1, 120), k=st.integers(1, 40), d=st.integers(1, 80),
    seed=st.integers(0, 2**31 - 1),
)
def test_assign_argmax_property(n, k, d, seed):
    r = np.random.default_rng(seed)
    x = jnp.asarray(r.normal(size=(n, d)).astype(np.float32))
    c = jnp.asarray(r.normal(size=(k, d)).astype(np.float32))
    ri, rs = ref.assign_argmax(x, c)
    pi, ps = assign_argmax_pallas(x, c, interpret=True)
    np.testing.assert_array_equal(np.asarray(ri), np.asarray(pi))
    np.testing.assert_allclose(np.asarray(rs), np.asarray(ps), rtol=1e-4, atol=1e-4)


@settings(max_examples=25, deadline=None)
@given(
    n=st.integers(1, 120), k=st.integers(1, 30), d=st.integers(1, 50),
    seed=st.integers(0, 2**31 - 1),
)
def test_label_stats_property(n, k, d, seed):
    r = np.random.default_rng(seed)
    x = jnp.asarray(r.normal(size=(n, d)).astype(np.float32))
    idx = jnp.asarray(r.integers(0, k, size=n).astype(np.int32))
    rs_, rc = ref.label_stats(x, idx, k)
    ps_, pc = label_stats_pallas(x, idx, k, interpret=True)
    np.testing.assert_allclose(np.asarray(rs_), np.asarray(ps_), rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(rc), np.asarray(pc))


@settings(max_examples=25, deadline=None)
@given(
    r=st.integers(1, 200), c=st.integers(1, 40),
    seed=st.integers(0, 2**31 - 1),
)
def test_component_best_edge_property(r, c, seed):
    rr = np.random.default_rng(seed)
    w = jnp.asarray(rr.normal(size=r).astype(np.float32))
    col = jnp.asarray(rr.integers(-1, 64, size=r).astype(np.int32))
    rows = jnp.asarray(rr.permutation(2 * r)[:r].astype(np.int32))
    comp = jnp.asarray(rr.integers(0, c + 1, size=r).astype(np.int32))
    want = ref.component_best_edge(w, col, rows, comp, c)
    got = component_best_edge_pallas(w, col, rows, comp, c, interpret=True)
    gxla = ops.component_best_edge(w, col, rows, comp, c, impl="xla")
    for a, b, bx, name in zip(want, got, gxla, ("w", "row", "col")):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"pallas:{name}")
        np.testing.assert_array_equal(np.asarray(a), np.asarray(bx),
                                      err_msg=f"xla:{name}")


@settings(max_examples=25, deadline=None)
@given(
    n=st.integers(1, 120), k=st.integers(1, 40), d=st.integers(1, 80),
    seed=st.integers(0, 2**31 - 1),
)
def test_assign_stats_property(n, k, d, seed):
    r = np.random.default_rng(seed)
    x = jnp.asarray(r.normal(size=(n, d)).astype(np.float32))
    c = jnp.asarray(r.normal(size=(k, d)).astype(np.float32))
    want = ref.assign_stats(x, c)
    got = assign_stats_pallas(x, c, interpret=True)
    np.testing.assert_array_equal(np.asarray(want[0]), np.asarray(got[0]))
    np.testing.assert_array_equal(np.asarray(want[3]), np.asarray(got[3]))
    for a, b in zip(want[1:], got[1:]):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4
        )


@settings(max_examples=25, deadline=None)
@given(
    r=st.integers(1, 100), c=st.integers(1, 100), d=st.integers(1, 60),
    labels=st.integers(1, 8), seed=st.integers(0, 2**31 - 1),
)
def test_sim_best_edge_property(r, c, d, labels, seed):
    rr = np.random.default_rng(seed)
    xr = jnp.asarray(rr.normal(size=(r, d)).astype(np.float32))
    xc = jnp.asarray(rr.normal(size=(c, d)).astype(np.float32))
    lr = jnp.asarray(rr.integers(0, labels, size=r).astype(np.int32))
    lc = jnp.asarray(rr.integers(0, labels, size=c).astype(np.int32))
    rj, rs_ = ref.sim_best_edge(xr, xc, lr, lc)
    pj, ps = sim_best_edge_pallas(xr, xc, lr, lc, interpret=True)
    np.testing.assert_array_equal(np.asarray(rj), np.asarray(pj))
    np.testing.assert_allclose(np.asarray(rs_), np.asarray(ps),
                               rtol=1e-4, atol=1e-4)
