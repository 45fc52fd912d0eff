"""Core clustering algorithms: K-Means, BKC, Buckshot, HAC, components.

Validates the paper's algorithmic claims at unit scale:
  * K-Means monotonically improves the cosine objective and converges.
  * BKC produces exactly k clusters with RSS close to K-Means (paper: 5-8%).
  * Buckshot RSS within a few % of K-Means (paper: 3.5-5.5%).
  * single-link HAC (Prim MST + cut) == naive O(s^3) agglomerative oracle.
  * Borůvka MST == Prim MST labels, single-device and for every k.
  * label-propagation connected components == union-find oracle (hypothesis).
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

from repro.common import l2_normalize
from repro.core import (
    bkc,
    bkc_fit,
    buckshot,
    kmeans,
    kmeans_fit,
    kmeans_step,
    metrics,
)
from repro.core.connected_components import (
    compact_labels,
    label_components,
    label_components_np,
    num_components,
)
from repro.core.hac import mst_prim, single_link_labels
from repro.core.kmeans import init_random_centers
from repro.core.microcluster import build_microclusters, merge_stats, pair_similarity
from repro.core.bkc import join_to_groups
from repro.core import sampling
# imported via distrib.hac_parallel on purpose: the machinery moved to
# core.hac and this validates the backward-compat re-export
from repro.distrib.hac_parallel import boruvka_mst, single_link_labels_boruvka

KEY = jax.random.PRNGKey(0)


# ------------------------------------------------------------------ K-Means


def test_kmeans_objective_monotone(blob_data):
    x, _, k = blob_data
    centers = init_random_centers(KEY, x, k)
    prev_obj = np.inf
    for _ in range(6):
        new_centers, idx, best_sim, _, _ = kmeans_step(x, centers, k)
        obj = float(metrics.cosine_objective(best_sim))
        assert obj <= prev_obj + 1e-4, "cosine objective must not increase"
        prev_obj = obj
        centers = new_centers


def test_kmeans_converges_and_labels_separable(blob_data):
    x, labels, k = blob_data
    res = kmeans(x, k, KEY, max_iters=20)
    assert int(res.iterations) < 20, "should converge before max_iters"
    pur = float(metrics.purity(res.assignment, jnp.asarray(labels), k, k))
    assert pur > 0.7  # random init -> local optima; benchmarks do the strict claim
    assert float(res.rss) > 0


def test_kmeans_respects_given_init(blob_data):
    x, _, k = blob_data
    init = init_random_centers(KEY, x, k)
    r1 = kmeans_fit(x, init, k, max_iters=5)
    r2 = kmeans_fit(x, init, k, max_iters=5)
    assert float(r1.rss) == float(r2.rss), "deterministic given same init"


def test_kmeans_empty_cluster_keeps_center(blob_data):
    x, _, k = blob_data
    # a center at the antipode of the data gets no members and must survive
    far = -x[0][None, :]
    init = jnp.concatenate([init_random_centers(KEY, x, k - 1), far])
    res = kmeans_fit(x, init, k, max_iters=3)
    assert bool(jnp.all(jnp.isfinite(res.centers)))


# ------------------------------------------------------------------ microclusters


def test_microcluster_cf_additivity(blob_data):
    x, _, _ = blob_data
    big_k = 16
    centers = l2_normalize(x[:big_k])
    full, _, _ = build_microclusters(x, centers, big_k)
    a, _, _ = build_microclusters(x[:600], centers, big_k)
    b, _, _ = build_microclusters(x[600:], centers, big_k)
    merged = merge_stats(a, b)
    np.testing.assert_allclose(np.asarray(full.n), np.asarray(merged.n))
    np.testing.assert_allclose(
        np.asarray(full.cf1), np.asarray(merged.cf1), rtol=1e-5, atol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(full.min_sim), np.asarray(merged.min_sim), rtol=1e-6
    )


def test_microcluster_min_sim_is_min(blob_data):
    x, _, _ = blob_data
    big_k = 8
    centers = l2_normalize(x[:big_k])
    mc, idx, best_sim = build_microclusters(x, centers, big_k)
    idx_np, sim_np = np.asarray(idx), np.asarray(best_sim)
    for c in range(big_k):
        mask = idx_np == c
        if mask.any():
            assert abs(float(mc.min_sim[c]) - sim_np[mask].min()) < 1e-6


def test_pair_similarity_matches_paper_formula(blob_data):
    x, _, _ = blob_data
    big_k = 8
    centers = l2_normalize(x[:big_k])
    mc, _, _ = build_microclusters(x, centers, big_k)
    pair, escape = pair_similarity(mc)
    cos = np.asarray(centers @ centers.T)
    mins = np.asarray(mc.min_sim)
    want = np.maximum(cos - mins[:, None] - mins[None, :], 0.0)
    np.fill_diagonal(want, 0.0)
    np.testing.assert_allclose(np.asarray(pair), want, rtol=1e-5, atol=1e-6)
    # escape clause: sim == 0 but cos >= min(min_i, min_j)
    esc = np.asarray(escape)
    onsite = (want == 0.0) & (cos >= np.minimum(mins[:, None], mins[None, :]))
    np.fill_diagonal(onsite, False)
    assert (esc == onsite).all()


# ------------------------------------------------------------------ BKC


def test_bkc_produces_k_clusters(blob_data):
    x, _, k = blob_data
    res = bkc(x, 64, k, KEY)
    groups = np.asarray(res.group_of_mc)
    assert groups.min() >= 0 and groups.max() < k
    assert len(np.unique(np.asarray(res.assignment))) <= k


def test_bkc_rss_close_to_kmeans(blob_data):
    """Paper Tables 1-3: BKC RSS within 5-8% of converged K-Means (we allow
    15% at this tiny scale; the benchmark reproduces the paper's setting)."""
    x, _, k = blob_data
    km = kmeans(x, k, KEY, max_iters=8)
    bk = bkc(x, 64, k, KEY)
    assert float(bk.rss) < float(km.rss) * 1.15


def test_bkc_deterministic_given_centers(blob_data):
    x, _, k = blob_data
    big_k = 32
    centers = l2_normalize(x[jax.random.choice(KEY, x.shape[0], (big_k,), replace=False)])
    r1 = bkc_fit(x, centers, big_k, k)
    r2 = bkc_fit(x, centers, big_k, k)
    np.testing.assert_array_equal(np.asarray(r1.assignment), np.asarray(r2.assignment))


def test_join_to_groups_exactly_k(blob_data):
    x, _, _ = blob_data
    for k in (2, 5, 11):
        big_k = 48
        centers = l2_normalize(
            x[jax.random.choice(KEY, x.shape[0], (big_k,), replace=False)]
        )
        mc, _, _ = build_microclusters(x, centers, big_k)
        group, thr = join_to_groups(mc, k)
        g = np.asarray(group)
        assert g.min() >= 0 and g.max() < k
        assert len(np.unique(g)) == k
        assert np.isfinite(float(thr))


# ------------------------------------------------------------------ Buckshot


def test_buckshot_sample_size_default():
    assert sampling.buckshot_sample_size(20_000, 50) == 1000
    assert sampling.buckshot_sample_size(20_000, 200) == 2000


def test_buckshot_rss_close_to_kmeans(blob_data):
    x, _, k = blob_data
    km = kmeans(x, k, KEY, max_iters=8)
    bs = buckshot(x, k, KEY, kmeans_iters=3)
    assert float(bs.kmeans.rss) < float(km.rss) * 1.10
    assert int(bs.kmeans.iterations) <= 3, "phase 2 must stay at 2-3 iterations"


def test_buckshot_sample_is_subset(blob_data):
    x, _, k = blob_data
    bs = buckshot(x, k, KEY)
    idx = np.asarray(bs.sample_idx)
    assert len(np.unique(idx)) == len(idx), "sample without replacement"
    assert idx.min() >= 0 and idx.max() < x.shape[0]


def test_buckshot_hac_switch_boruvka_equals_prim(blob_data):
    """Default matrix-free phase 1 == the dense Prim oracle path: same sample
    labels, same initial centers, same final result."""
    from repro.core import buckshot_fit, buckshot_phase1
    from repro.core.sampling import sample_indices

    x, _, k = blob_data
    sidx = sample_indices(KEY, x.shape[0], 200)
    lb, cb = buckshot_phase1(x, sidx, k)  # default hac="boruvka"
    lp, cp = buckshot_phase1(x, sidx, k, hac="prim")
    np.testing.assert_array_equal(np.asarray(lb), np.asarray(lp))
    np.testing.assert_allclose(np.asarray(cb), np.asarray(cp), rtol=1e-5,
                               atol=1e-6)
    rb = buckshot_fit(x, sidx, k, hac="boruvka")
    rp = buckshot_fit(x, sidx, k, hac="prim")
    np.testing.assert_allclose(
        float(rb.kmeans.rss), float(rp.kmeans.rss), rtol=1e-5
    )
    with pytest.raises(ValueError):
        buckshot_phase1(x, sidx, k, hac="nope")


# ------------------------------------------------------------------ HAC


def _naive_single_link(sim: np.ndarray, k: int) -> np.ndarray:
    """O(s^3) agglomerative single-link oracle."""
    s = sim.shape[0]
    sim = sim.copy().astype(np.float64)
    np.fill_diagonal(sim, -np.inf)
    labels = list(range(s))
    active = set(range(s))
    cluster_sim = sim.copy()
    while len(active) > k:
        best, bi, bj = -np.inf, -1, -1
        act = sorted(active)
        for i in act:
            for j in act:
                if i < j and cluster_sim[i, j] > best:
                    best, bi, bj = cluster_sim[i, j], i, j
        # merge bj into bi (single link: max similarity)
        for t in act:
            if t != bi and t != bj:
                m = max(cluster_sim[bi, t], cluster_sim[bj, t])
                cluster_sim[bi, t] = cluster_sim[t, bi] = m
        active.discard(bj)
        labels = [bi if l == bj else l for l in labels]
    # canonicalize: dense by first occurrence of min-id root
    roots = {}
    out = np.empty(s, np.int32)
    # resolve chains
    def find(a):
        while labels[a] != a:
            a = labels[a]
        return a
    for i in range(s):
        r = find(i)
        roots.setdefault(r, len(roots))
    for i in range(s):
        out[i] = roots[find(i)]
    return out


def _partition_equal(a: np.ndarray, b: np.ndarray) -> bool:
    """Same partition up to label permutation."""
    pa = {}
    pb = {}
    for i, (x, y) in enumerate(zip(a, b)):
        pa.setdefault(x, set()).add(i)
        pb.setdefault(y, set()).add(i)
    return sorted(map(frozenset, pa.values()), key=min) == sorted(
        map(frozenset, pb.values()), key=min
    )


@pytest.mark.parametrize("s,k", [(20, 3), (50, 7), (81, 12)])
def test_single_link_matches_naive_oracle(rng, s, k):
    x = l2_normalize(jnp.asarray(rng.normal(size=(s, 16)).astype(np.float32)))
    sim = np.asarray(x @ x.T)
    got = np.asarray(single_link_labels(jnp.asarray(sim), k))
    want = _naive_single_link(sim, k)
    assert _partition_equal(got, want)
    assert len(np.unique(got)) == k


def test_mst_prim_total_weight_is_max(rng):
    """Prim MST weight must beat 200 random spanning trees."""
    s = 40
    x = l2_normalize(jnp.asarray(rng.normal(size=(s, 8)).astype(np.float32)))
    sim = np.asarray(x @ x.T)
    _, _, ew = mst_prim(jnp.asarray(sim))
    w_prim = float(np.asarray(ew).sum())
    r = np.random.default_rng(5)
    for _ in range(200):
        perm = r.permutation(s)
        w = sum(sim[perm[i], perm[i + 1]] for i in range(s - 1))
        assert w_prim >= w - 1e-5


@pytest.mark.parametrize("s,k", [(64, 5), (200, 12), (150, 1), (512, 20),
                                 (700, 3)])
def test_boruvka_equals_prim(rng, s, k):
    """Matrix-free Borůvka == dense Prim labels at growing s."""
    xs = l2_normalize(jnp.asarray(rng.normal(size=(s, 24)).astype(np.float32)))
    ref_labels = np.asarray(single_link_labels(xs @ xs.T, k))
    got = np.asarray(single_link_labels_boruvka(xs, k))
    assert (ref_labels == got).all()


def test_boruvka_row_chunking_is_transparent(rng):
    """The chunked candidate sweep (block < s) must not change the forest."""
    from repro.core.hac import boruvka_mst as core_boruvka, cut_mst_edges

    s, k = 512, 9
    xs = l2_normalize(jnp.asarray(rng.normal(size=(s, 16)).astype(np.float32)))
    want = np.asarray(single_link_labels(xs @ xs.T, k))
    edges = core_boruvka(xs, block=100)  # forces the scan path, non-divisible
    got = np.asarray(cut_mst_edges(edges, s, k))
    assert (want == got).all()


def _cut_full_width(edges, n, k):
    """The cut as it labelled every edge slot: mask by rank, then propagate
    over all L slots. The reference for the kept-edges-only cut."""
    from repro.core.hac import components_from_edges

    neg = float(jnp.finfo(jnp.float32).min)
    w = jnp.where(edges.valid, edges.w, neg)
    order = jnp.argsort(-w)
    rank = jnp.argsort(order)
    keep = jnp.logical_and(edges.valid, rank < (n - k))
    labels = components_from_edges(n, edges.u, edges.v, keep)
    is_root = labels == jnp.arange(n, dtype=labels.dtype)
    return (jnp.cumsum(is_root.astype(jnp.int32)) - 1)[labels]


def _cut_edges(layout, s):
    """An edge set of each layout the cut is handed, over s points."""
    from repro.core.hac import MSTEdges, boruvka_mst as core_boruvka

    r = np.random.default_rng(s)
    x = r.normal(size=(s, 12)).astype(np.float32)
    if layout == "tied":  # duplicate rows: many edges of weight exactly 1
        x = np.repeat(x[: s // 3 + 1], 3, axis=0)[:s]
    xs = l2_normalize(jnp.asarray(x))
    if layout == "compact":
        from repro.distrib.hac_parallel import boruvka_mst_distributed
        from repro.distrib.sharding import make_flat_mesh

        edges = boruvka_mst_distributed(
            make_flat_mesh(1), ("data",), xs, merge="comp", compact=True,
            prewarm=False)
        assert edges.u.shape[0] < 3 * s  # one slot per component per round
        return edges
    edges = core_boruvka(xs)
    if layout == "forest":  # drop every third tree edge: a forest
        valid = np.asarray(edges.valid).copy()
        valid[np.flatnonzero(valid)[::3]] = False
        edges = MSTEdges(edges.u, edges.v, edges.w, jnp.asarray(valid))
    return edges


@pytest.mark.parametrize("k", ["1", "2", "n-1", "n"])
@pytest.mark.parametrize("layout", ["rounds", "compact", "tied", "forest"])
def test_cut_over_kept_edges_matches_full_width(layout, k):
    """Labelling only the n-k strongest slots gives the labels of masking
    every slot of the edge history by rank — on Borůvka's (rounds·s) slots,
    the compact component layout, tied weights and a forest."""
    from repro.core.hac import cut_mst_edges

    s = 97
    k = {"1": 1, "2": 2, "n-1": s - 1, "n": s}[k]
    edges = _cut_edges(layout, s)
    valid = int(np.asarray(edges.valid).sum())
    assert valid == (s - 1 - len(range(0, s - 1, 3)) if layout == "forest"
                     else s - 1)
    want = np.asarray(_cut_full_width(edges, s, k))
    got = np.asarray(cut_mst_edges(edges, s, k))
    np.testing.assert_array_equal(got, want)


def test_cut_loop_runs_over_kept_edges_only():
    """The cut's label-propagation loop carries n-k edge slots, never the
    L = rounds·s slots of Borůvka's edge history."""
    from repro.core.hac import MSTEdges, _rounds_for, cut_mst_edges

    s, k = 1000, 20
    big = _rounds_for(s) * s
    edges = MSTEdges(
        jax.ShapeDtypeStruct((big,), jnp.int32),
        jax.ShapeDtypeStruct((big,), jnp.int32),
        jax.ShapeDtypeStruct((big,), jnp.float32),
        jax.ShapeDtypeStruct((big,), jnp.bool_),
    )
    jaxpr = jax.make_jaxpr(lambda e: cut_mst_edges(e, s, k))(edges)

    def whiles(jx):
        for eqn in jx.eqns:
            if eqn.primitive.name == "while":
                yield eqn
            for p in eqn.params.values():
                for sub in p if isinstance(p, (tuple, list)) else (p,):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        yield from whiles(sub)

    loops = list(whiles(jaxpr.jaxpr))
    assert len(loops) == 1
    lengths = [v.aval.shape[0] for v in loops[0].invars if v.aval.shape]
    assert big not in lengths
    assert lengths.count(s - k) == 3  # u, v and the kept mask
    assert set(lengths) == {s, s - k}


def test_boruvka_emits_spanning_forest(rng):
    s = 128
    xs = l2_normalize(jnp.asarray(rng.normal(size=(s, 8)).astype(np.float32)))
    edges = boruvka_mst(xs)
    assert int(np.asarray(edges.valid).sum()) == s - 1
    # same total weight as Prim
    _, _, ew = mst_prim(xs @ xs.T)
    w_prim = float(np.asarray(ew).sum())
    w_boru = float(np.asarray(edges.w)[np.asarray(edges.valid)].sum())
    assert abs(w_prim - w_boru) < 1e-3


# ------------------------------------------------------------------ components


@settings(max_examples=40, deadline=None)
@given(m=st.integers(1, 40), p=st.floats(0.0, 0.5), seed=st.integers(0, 2**31 - 1))
def test_label_components_matches_union_find(m, p, seed):
    r = np.random.default_rng(seed)
    adj = r.random((m, m)) < p
    adj = np.triu(adj, 1)
    adj = adj | adj.T
    got = np.asarray(label_components(jnp.asarray(adj)))
    want = label_components_np(adj)
    assert (got == want).all()
    # num_components consistent
    assert int(num_components(jnp.asarray(got))) == len(np.unique(want))
    # compact labels are dense 0..G-1
    dense = np.asarray(compact_labels(jnp.asarray(got)))
    assert set(np.unique(dense)) == set(range(len(np.unique(want))))


# ------------------------------------------------------------------ metrics


def test_rss_decomposition_matches_explicit(blob_data):
    x, _, k = blob_data
    res = kmeans(x, k, KEY)
    idx = np.asarray(res.assignment)
    xn = np.asarray(x)
    explicit = 0.0
    for c in range(k):
        mask = idx == c
        if mask.any():
            mu = xn[mask].mean(0)
            explicit += ((xn[mask] - mu) ** 2).sum()
    assert abs(float(res.rss) - explicit) / explicit < 1e-4


def test_purity_perfect_and_bounds(blob_data):
    x, labels, k = blob_data
    lab = jnp.asarray(labels)
    assert float(metrics.purity(lab, lab, k, k)) == 1.0
    assert float(metrics.nmi(lab, lab, k, k)) > 0.999


@settings(max_examples=30, deadline=None)
@given(n=st.integers(2, 200), k=st.integers(1, 10), seed=st.integers(0, 2**31 - 1))
def test_metrics_bounds_property(n, k, seed):
    r = np.random.default_rng(seed)
    pred = jnp.asarray(r.integers(0, k, n).astype(np.int32))
    true = jnp.asarray(r.integers(0, k, n).astype(np.int32))
    p = float(metrics.purity(pred, true, k, k))
    m = float(metrics.nmi(pred, true, k, k))
    assert 0.0 <= p <= 1.0 + 1e-6
    assert -1e-6 <= m <= 1.0 + 1e-6
    # permutation invariance of purity
    perm = r.permutation(k)
    pred2 = jnp.asarray(perm[np.asarray(pred)].astype(np.int32))
    assert abs(float(metrics.purity(pred2, true, k, k)) - p) < 1e-6
