"""Out-of-core streaming parity (DESIGN.md §10).

The streaming paths must reproduce the resident oracles:
  - streaming two-pass tf-idf is BIT-EXACT vs one-shot ``tfidf.tfidf``
    (df and n are integer-valued, pass 2 is elementwise per chunk);
  - the streaming stats fold is BIT-EXACT under re-chunking on integer-valued
    data (the repo's accumulation-order convention, cf. test_kernels);
  - streaming K-Means/BKC/Buckshot ASSIGNMENTS are identical to the resident
    paths on the same synth corpus, with centers/RSS at f32-ulp tolerance
    (two different XLA programs may fuse the f32 center update differently);
  - the reservoir sample equals the direct global top-s oracle exactly.

Multi-device variants run in subprocesses (the main pytest process keeps one
device), mirroring test_distributed.py.
"""

from __future__ import annotations

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common import l2_normalize
from repro.core.bkc import bkc_fit, bkc_fit_stream
from repro.core.buckshot import buckshot_fit, buckshot_stream
from repro.core.kmeans import (
    init_random_centers,
    kmeans_fit,
    kmeans_fit_stream,
)
from repro.core.sampling import reservoir_sample_stream
from repro.kernels import ops
from repro.text import synth, tfidf
from repro.text.stream import CorpusStream

ENV = dict(
    os.environ,
    XLA_FLAGS="--xla_force_host_platform_device_count=4",
    PYTHONPATH="src",
    JAX_PLATFORMS="cpu",
)


def _run(code: str, timeout: int = 600) -> str:
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=timeout, env=ENV,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


@pytest.fixture(scope="module")
def corpus():
    c = synth.make_corpus(800, vocab=256, n_topics=8, seed=3)
    x = tfidf.tfidf(jnp.asarray(c.counts))
    return c, x


def _x_stream(chunk=128):
    st, _ = synth.stream_corpus(800, vocab=256, n_topics=8, seed=3, chunk=chunk)
    return tfidf.tfidf_stream(st)


# ------------------------------------------------------------------ stream


def test_stream_chunks_fixed_shape_and_reassemble(corpus):
    c, _ = corpus
    st, labels = synth.stream_corpus(
        800, vocab=256, n_topics=8, seed=3, chunk=96
    )
    np.testing.assert_array_equal(labels, c.labels)
    total_w = 0.0
    for ch in st.chunks():
        assert ch.x.shape == (96, 256) and ch.w.shape == (96,)
        assert ((ch.w == 0) | (ch.w == 1)).all()
        assert (ch.x[ch.w == 0] == 0).all()  # padding rows are all-zero
        total_w += float(ch.w.sum())
    assert total_w == 800
    np.testing.assert_array_equal(st.materialize(), c.counts)


def test_stream_synth_bit_identical_any_chunk(corpus):
    c, _ = corpus
    for chunk in (800, 127, 1024):
        st, _ = synth.stream_corpus(
            800, vocab=256, n_topics=8, seed=3, chunk=chunk
        )
        np.testing.assert_array_equal(st.materialize(), c.counts)


def test_stream_from_array_one_chunk_wrapper(corpus):
    c, _ = corpus
    st = CorpusStream.from_array(c.counts)
    assert st.n_chunks == 1 and st.chunk == 800
    np.testing.assert_array_equal(st.materialize(), c.counts)


# (n, chunk): n a multiple of chunk, n < chunk, a short tail, one chunk
FROM_ARRAY_CASES = [(512, 128), (100, 128), (600, 128), (600, None)]


def _from_array(n, chunk):
    x = np.random.default_rng(n).random((n, 16), np.float32)
    return x, CorpusStream.from_array(x, chunk=chunk)


@pytest.mark.parametrize("n,chunk", FROM_ARRAY_CASES)
def test_stream_from_array_chunks_match_per_pass_padding(n, chunk):
    """Every pass yields the chunks that padding each block in that pass
    would give, bit for bit: rows, weights and start."""
    from repro.text.stream import _pad_block

    x, st = _from_array(n, chunk)
    c = n if chunk is None else chunk
    assert (st.n, st.dim, st.chunk) == (n, 16, c)
    for _ in range(3):
        got = list(st.chunks())
        assert [ch.start for ch in got] == list(range(0, n, c))
        for ch in got:
            want_x, want_w = _pad_block(x[ch.start : ch.start + c], c)
            for a, b in ((ch.x, want_x), (ch.w, want_w)):
                assert a.dtype == b.dtype and a.shape == b.shape
                assert a.tobytes() == b.tobytes()


@pytest.mark.parametrize("n,chunk", FROM_ARRAY_CASES)
def test_stream_from_array_tail_built_once_and_read_only(n, chunk):
    _, st = _from_array(n, chunk)
    tails = [list(st.chunks())[-1] for _ in range(3)]
    for ch in tails[1:]:
        assert ch.x is tails[0].x and ch.w is tails[0].w
    assert not tails[0].x.flags.writeable and not tails[0].w.flags.writeable
    with pytest.raises(ValueError, match="read-only"):
        tails[0].x[0, 0] = 1.0


@pytest.mark.parametrize("n,chunk", FROM_ARRAY_CASES)
def test_stream_from_array_fault_on_tail_leaves_next_pass_clean(n, chunk):
    from repro.testing import faults
    from repro.text.stream import run_pass

    x, st = _from_array(n, chunk)
    last = st.n_chunks - 1

    def tail_rows(acc, ch, ci):
        return np.asarray(ch.x).copy() if ci == last else acc

    with faults.inject(f"nan@c{last}") as plan:
        bad = run_pass(st, tail_rows, None)
        assert plan.fired("nan") == 1
    assert np.isnan(bad[0]).all()
    clean = run_pass(st, tail_rows, None)
    start = last * st.chunk
    np.testing.assert_array_equal(clean[: n - start], x[start:])
    assert not clean[n - start :].any()


def test_stream_from_blocks_enforces_contract():
    """A short mid-stream block or a row-count mismatch must raise, not
    silently pad the middle of the logical row order."""

    def bad_mid(blocks):
        st = CorpusStream.from_blocks(
            lambda: iter(blocks), n=sum(b.shape[0] for b in blocks),
            dim=4, chunk=8,
        )
        with pytest.raises(ValueError):
            st.materialize()

    z = lambda r: np.zeros((r, 4), np.float32)
    bad_mid([z(3), z(8)])  # short block before the final one
    bad_mid([z(12)])  # block exceeds chunk
    st = CorpusStream.from_blocks(lambda: iter([z(8), z(3)]), n=20, dim=4, chunk=8)
    with pytest.raises(ValueError, match="declared n"):
        st.materialize()
    # the legal shape: full blocks then one short tail
    ok = CorpusStream.from_blocks(lambda: iter([z(8), z(3)]), n=11, dim=4, chunk=8)
    assert ok.materialize().shape == (11, 4)


def test_concat_streams_bit_identical_to_single_stream():
    """Re-chunked concatenation == one stream over the concatenated rows:
    same blocks, same padding, same start offsets — so every downstream fold
    (df, reservoir, K-Means) matches that oracle bit-for-bit. Also the
    re-iterability contract: a second pass re-opens every source."""
    from repro.text.stream import concat_streams

    rng = np.random.default_rng(11)
    rows = rng.random((57, 6)).astype(np.float32)
    # three sources with different chunk sizes, each with a padded tail
    parts = [
        CorpusStream.from_array(rows[:20], chunk=7),
        CorpusStream.from_array(rows[20:23], chunk=9),
        CorpusStream.from_array(rows[23:], chunk=13),
    ]
    cat = concat_streams(*parts, chunk=10)
    oracle = CorpusStream.from_array(rows, chunk=10)
    assert cat.n == oracle.n and cat.n_chunks == oracle.n_chunks
    for _pass in range(2):  # re-iterable
        got, want = list(cat.chunks()), list(oracle.chunks())
        assert len(got) == len(want)
        for g, o in zip(got, want):
            np.testing.assert_array_equal(g.x, o.x)
            np.testing.assert_array_equal(g.w, o.w)
            assert g.start == o.start


def test_concat_streams_rejects_dim_mismatch_and_empty():
    from repro.text.stream import concat_streams

    a = CorpusStream.from_array(np.zeros((4, 3), np.float32))
    b = CorpusStream.from_array(np.zeros((4, 5), np.float32))
    with pytest.raises(ValueError, match="dim"):
        concat_streams(a, b)
    with pytest.raises(ValueError):
        concat_streams()
    # the .concat sugar keeps the receiver's chunk size
    c = a.concat(CorpusStream.from_array(np.zeros((2, 3), np.float32)))
    assert c.n == 6 and c.chunk == a.chunk


def test_stream_reiterable(corpus):
    """Two passes over the same stream see identical chunks (the two-pass
    tf-idf / multi-iteration K-Means contract)."""
    st, _ = synth.stream_corpus(800, vocab=256, n_topics=8, seed=3, chunk=256)
    a = st.materialize()
    b = st.materialize()
    np.testing.assert_array_equal(a, b)


# ----------------------------------------------------------------- executor


def _collect_pass(stream, **kw):
    from repro.text.stream import run_pass

    return run_pass(
        stream, lambda acc, ch, ci: acc + [(ci, np.asarray(ch.x).copy())], [],
        **kw,
    )


def test_executor_contract_violations_surface_through_prefetch():
    """from_blocks contract checks raise on the CONSUMER thread even though
    the prefetcher produces chunks on a background thread."""
    z = lambda r: np.zeros((r, 4), np.float32)

    short_mid = CorpusStream.from_blocks(
        lambda: iter([z(3), z(8)]), n=11, dim=4, chunk=8
    )
    with pytest.raises(ValueError, match="short block"):
        _collect_pass(short_mid, prefetch=2)

    mismatch = CorpusStream.from_blocks(
        lambda: iter([z(8), z(3)]), n=20, dim=4, chunk=8
    )
    with pytest.raises(ValueError, match="declared n"):
        _collect_pass(mismatch, prefetch=2)


def test_executor_empty_stream():
    """An n = 0 stream yields no chunks: run_pass returns the initial carry,
    materialize is (0, dim), and df_stream refuses it."""
    st = CorpusStream.from_blocks(lambda: iter([]), n=0, dim=4, chunk=8)
    assert st.n_chunks == 0
    assert _collect_pass(st, prefetch=2) == []
    assert st.materialize().shape == (0, 4)
    with pytest.raises(ValueError, match="empty stream"):
        tfidf.df_stream(st)


def test_executor_map_reiteration_fresh_passes():
    """A mapped stream re-iterates under the prefetcher: every pass is a
    fresh generator (no iterator exhaustion), chunks bit-identical."""
    st, _ = synth.stream_corpus(500, vocab=64, n_topics=4, seed=1, chunk=96)
    mapped = st.map(lambda x, w: jnp.asarray(x) * 2.0)
    a = _collect_pass(mapped, prefetch=2)
    b = _collect_pass(mapped, prefetch=2)
    assert len(a) == len(b) == mapped.n_chunks
    for (ci_a, x_a), (ci_b, x_b) in zip(a, b):
        assert ci_a == ci_b
        np.testing.assert_array_equal(x_a, x_b)
    np.testing.assert_array_equal(
        np.concatenate([x for _, x in a])[:500], mapped.materialize()
    )


def test_executor_prefetch_on_off_chunks_identical():
    """Prefetch changes WHO computes a chunk, never the chunk: same order,
    same values, with a depth larger than the chunk count too."""
    st, _ = synth.stream_corpus(500, vocab=64, n_topics=4, seed=1, chunk=96)
    off = _collect_pass(st, prefetch=0)
    for depth in (1, 2, 16):
        on = _collect_pass(st, prefetch=depth)
        assert [ci for ci, _ in on] == [ci for ci, _ in off]
        for (_, x_on), (_, x_off) in zip(on, off):
            np.testing.assert_array_equal(x_on, x_off)


def test_executor_close_stops_abandoned_producer():
    """A fold that raises mid-pass must not leave the producer thread
    spinning (run_pass closes the prefetcher on any exit)."""
    import threading

    from repro.text.stream import run_pass

    st, _ = synth.stream_corpus(500, vocab=64, n_topics=4, seed=1, chunk=96)

    def boom(acc, ch, ci):
        raise RuntimeError("abandon pass")

    before = threading.active_count()
    with pytest.raises(RuntimeError, match="abandon pass"):
        run_pass(st, boom, None, prefetch=2)
    # run_pass's finally-close joins the producer thread before re-raising
    assert threading.active_count() <= before


def test_prefetch_parity_env_switch(corpus, monkeypatch):
    """Streaming K-Means/BKC/Buckshot are bit-identical with prefetch on vs
    off (REPRO_STREAM_PREFETCH env switch), single device, non-chunk-multiple
    n (800 % 96 != 0)."""
    results = {}
    for mode in ("0", "2"):
        monkeypatch.setenv("REPRO_STREAM_PREFETCH", mode)
        xs = _x_stream(chunk=96)
        init = init_random_centers(jax.random.PRNGKey(0), xs.materialize(), 8)
        km = kmeans_fit_stream(xs, init, 8, max_iters=4)
        bk = bkc_fit_stream(xs, l2_normalize(xs.materialize()[:32]), 32, 8)
        bs = buckshot_stream(xs, 8, jax.random.PRNGKey(0), kmeans_iters=2)
        results[mode] = (km, bk, bs)
    km0, bk0, bs0 = results["0"]
    km1, bk1, bs1 = results["2"]
    np.testing.assert_array_equal(km0.assignment, km1.assignment)
    np.testing.assert_array_equal(np.asarray(km0.centers), np.asarray(km1.centers))
    np.testing.assert_array_equal(bk0.assignment, bk1.assignment)
    np.testing.assert_array_equal(
        np.asarray(bk0.group_of_mc), np.asarray(bk1.group_of_mc)
    )
    np.testing.assert_array_equal(bs0.kmeans.assignment, bs1.kmeans.assignment)
    np.testing.assert_array_equal(
        np.asarray(bs0.sample_idx), np.asarray(bs1.sample_idx)
    )
    np.testing.assert_array_equal(
        np.asarray(bs0.init_centers), np.asarray(bs1.init_centers)
    )


# ------------------------------------------------------------------ tf-idf


def test_tfidf_stream_bit_exact(corpus):
    c, x = corpus
    for chunk in (128, 250, 800):
        st, _ = synth.stream_corpus(
            800, vocab=256, n_topics=8, seed=3, chunk=chunk
        )
        got = tfidf.tfidf_stream(st).materialize()
        np.testing.assert_array_equal(got, np.asarray(x))


def test_df_stream_matches_resident(corpus):
    c, _ = corpus
    st, _ = synth.stream_corpus(800, vocab=256, n_topics=8, seed=3, chunk=200)
    df, n = tfidf.df_stream(st)
    np.testing.assert_array_equal(
        np.asarray(df), np.asarray(tfidf.document_frequency(jnp.asarray(c.counts)))
    )
    assert float(n) == 800.0


# ------------------------------------------------------------------ fold


def test_stream_stats_fold_bitexact_integer_data():
    """Chunked streaming fold == one-shot fused stats, bit for bit (integer
    data makes every accumulation order exact; includes a non-divisible
    chunk so the padded tail is exercised)."""
    from repro.core.kmeans import _stream_pass

    rng = np.random.default_rng(0)
    x = rng.integers(-8, 9, size=(1000, 33)).astype(np.float32)
    c = jnp.asarray(rng.integers(-8, 9, size=(11, 33)).astype(np.float32))
    one = ops.assign_stats(jnp.asarray(x), c)
    for chunk in (256, 250, 1000):
        st = CorpusStream.from_array(x, chunk=chunk)
        out = _stream_pass(st, c, 11, "xla", collect=True)
        (sums, counts, min_sim, sumsq), idx, sim = out.stats, out.idx, out.best_sim
        np.testing.assert_array_equal(np.asarray(one.sums), np.asarray(sums))
        np.testing.assert_array_equal(np.asarray(one.counts), np.asarray(counts))
        np.testing.assert_array_equal(np.asarray(one.min_sim), np.asarray(min_sim))
        np.testing.assert_array_equal(np.asarray(one.sumsq), np.asarray(sumsq))
        np.testing.assert_array_equal(np.asarray(one.idx), idx)
        np.testing.assert_array_equal(np.asarray(one.best_sim), sim)


# ------------------------------------------------------------------ k-means


def test_kmeans_stream_matches_resident(corpus):
    c, x = corpus
    init = init_random_centers(jax.random.PRNGKey(0), x, 8)
    res = kmeans_fit(x, init, 8, max_iters=8)
    sres = kmeans_fit_stream(_x_stream(), init, 8, max_iters=8)
    assert int(res.iterations) == int(sres.iterations)
    np.testing.assert_array_equal(np.asarray(res.assignment), sres.assignment)
    np.testing.assert_allclose(
        np.asarray(res.centers), np.asarray(sres.centers), rtol=1e-5, atol=1e-7
    )
    np.testing.assert_allclose(float(res.rss), float(sres.rss), rtol=1e-5)
    np.testing.assert_allclose(
        float(res.objective), float(sres.objective), rtol=1e-5
    )


def test_kmeans_stream_one_chunk_is_resident(corpus):
    """The resident path is the one-chunk specialization of the stream."""
    c, x = corpus
    init = init_random_centers(jax.random.PRNGKey(0), x, 8)
    res = kmeans_fit(x, init, 8, max_iters=8)
    sres = kmeans_fit_stream(
        CorpusStream.from_array(np.asarray(x)), init, 8, max_iters=8
    )
    np.testing.assert_array_equal(np.asarray(res.assignment), sres.assignment)
    np.testing.assert_allclose(
        np.asarray(res.centers), np.asarray(sres.centers), rtol=1e-6
    )


# ------------------------------------------------------------------ bkc


def test_bkc_stream_matches_resident(corpus):
    c, x = corpus
    cidx = jax.random.choice(
        jax.random.PRNGKey(0), x.shape[0], shape=(64,), replace=False
    )
    centers0 = l2_normalize(x[cidx])
    res = bkc_fit(x, centers0, 64, 8)
    sres = bkc_fit_stream(_x_stream(), centers0, 64, 8)
    np.testing.assert_array_equal(np.asarray(res.assignment), sres.assignment)
    np.testing.assert_array_equal(
        np.asarray(res.group_of_mc), np.asarray(sres.group_of_mc)
    )
    np.testing.assert_allclose(float(res.rss), float(sres.rss), rtol=1e-5)
    np.testing.assert_allclose(
        float(res.threshold), float(sres.threshold), rtol=1e-5, atol=1e-12
    )


# ------------------------------------------------------------------ sampling


def test_reservoir_equals_global_top_s_oracle(corpus):
    """Running top-s over chunks == direct top-s of ALL per-row scores, and
    the returned rows are exactly the corpus rows at those indices."""
    c, x = corpus
    key = jax.random.PRNGKey(7)
    xs = _x_stream(chunk=96)
    rows, gidx = reservoir_sample_stream(xs, 50, key)
    scores = []
    for ci, ch in enumerate(xs.chunks()):
        u = np.asarray(jax.random.uniform(jax.random.fold_in(key, ci), (96,)))
        scores.append(np.where(np.asarray(ch.w) > 0, u, -1.0))
    want = np.argsort(-np.concatenate(scores)[:800])[:50]
    np.testing.assert_array_equal(np.sort(gidx), np.sort(want))
    np.testing.assert_array_equal(np.asarray(rows), np.asarray(x)[gidx])


def test_reservoir_rejects_oversample():
    st = CorpusStream.from_array(np.zeros((10, 4), np.float32))
    with pytest.raises(ValueError):
        reservoir_sample_stream(st, 11, jax.random.PRNGKey(0))


def test_reservoir_s_equals_n_returns_exactly_the_real_rows():
    """The s == n edge: pad rows score -1.0 (strictly below any real [0, 1)
    draw) and the carry filler -2.0 loses to both, so the sample is exactly
    the n real rows — no pad leak, even with a heavily padded tail chunk."""
    rng = np.random.default_rng(5)
    x = rng.random((13, 4)).astype(np.float32)  # 13 rows, chunk 8 -> 3 pads
    st = CorpusStream.from_array(x, chunk=8)
    rows, gidx = reservoir_sample_stream(st, 13, jax.random.PRNGKey(2))
    np.testing.assert_array_equal(np.sort(gidx), np.arange(13))
    np.testing.assert_array_equal(np.asarray(rows)[np.argsort(gidx)], x)


# ------------------------------------------------------------------ buckshot


def test_buckshot_stream_matches_resident_fit(corpus):
    """Streaming Buckshot == resident buckshot_fit handed the SAME sample
    (the reservoir indices), end to end: phase-1 labels bit-equal,
    assignments identical."""
    c, x = corpus
    bs = buckshot_stream(_x_stream(), 8, jax.random.PRNGKey(0), kmeans_iters=3)
    res = buckshot_fit(x, jnp.asarray(bs.sample_idx), 8, kmeans_iters=3)
    np.testing.assert_array_equal(
        np.asarray(res.sample_labels), np.asarray(bs.sample_labels)
    )
    np.testing.assert_array_equal(
        np.asarray(res.kmeans.assignment), bs.kmeans.assignment
    )
    np.testing.assert_allclose(
        np.asarray(res.init_centers), np.asarray(bs.init_centers),
        rtol=1e-5, atol=1e-7,
    )
    np.testing.assert_allclose(
        float(res.kmeans.rss), float(bs.kmeans.rss), rtol=1e-5
    )


# ------------------------------------------------------------- multi-device


def test_fold_job_matches_resident_job_4dev():
    """Engine fold mode: chunked fold + ONE collective == resident make_job
    on the concatenated data (sum/min/max and per-chunk shard passthrough)."""
    _run("""
    import numpy as np, jax, jax.numpy as jnp
    from repro.distrib.engine import make_fold_job, make_job
    from repro.distrib.sharding import make_flat_mesh, shard_rows

    mesh = make_flat_mesh(4)
    x = jnp.arange(64, dtype=jnp.float32).reshape(64, 1) - 17.0

    def mc(data, bcast):
        v = data["x"]
        return {"sum": jnp.sum(v), "min": jnp.min(v), "max": jnp.max(v),
                "rows": v * 2.0}

    kinds = {"sum": "sum", "min": "min", "max": "max", "rows": "shard"}
    res = make_job(mesh, ("data",), mc, kinds)(
        {"x": shard_rows(mesh, ("data",), x)}, {})
    fold = make_fold_job(mesh, ("data",), mc, kinds)
    carry, rows = None, []
    for start in range(0, 64, 16):
        chunk = shard_rows(mesh, ("data",), x[start:start + 16])
        carry, so = fold.step(carry, {"x": chunk}, {})
        rows.append(np.asarray(so["rows"]))
    out = fold.finalize(carry)
    assert float(out["sum"]) == float(res["sum"])
    assert float(out["min"]) == float(res["min"])
    assert float(out["max"]) == float(res["max"])
    assert out["rows"] is None
    np.testing.assert_array_equal(np.concatenate(rows), np.asarray(res["rows"]))
    print("FOLD OK")
    """)


def test_fold_job_prefix_subtree_kinds_4dev():
    """A fold kind may cover a whole out SUBTREE (the engine's pytree-prefix
    contract, same as make_job): the carry/merge/finalize must tree_map."""
    _run("""
    import numpy as np, jax.numpy as jnp
    from repro.distrib.engine import make_fold_job
    from repro.distrib.sharding import make_flat_mesh, shard_rows

    mesh = make_flat_mesh(4)
    x = jnp.arange(32, dtype=jnp.float32).reshape(32, 1)

    def mc(data, bcast):
        v = data["x"]
        return {"stats": {"a": jnp.sum(v), "b": jnp.sum(v * v)}}

    fold = make_fold_job(mesh, ("data",), mc, {"stats": "sum"})
    carry = None
    for start in range(0, 32, 8):
        chunk = shard_rows(mesh, ("data",), x[start:start + 8])
        carry, _ = fold.step(carry, {"x": chunk}, {})
    out = fold.finalize(carry)
    assert float(out["stats"]["a"]) == float(x.sum())
    assert float(out["stats"]["b"]) == float((x * x).sum())
    print("PREFIX FOLD OK")
    """)


def test_fold_job_rejects_gather_kind():
    from repro.distrib.engine import make_fold_job
    from repro.distrib.sharding import make_flat_mesh

    with pytest.raises(ValueError, match="fold mode"):
        make_fold_job(
            make_flat_mesh(1), ("data",), lambda d, b: d, {"x": "gather"}
        )


def test_distributed_streaming_tfidf_bit_exact_4dev():
    _run("""
    import numpy as np, jax.numpy as jnp
    from repro.distrib.sharding import make_flat_mesh
    from repro.text import synth, tfidf

    mesh = make_flat_mesh(4)
    c = synth.make_corpus(203, vocab=64, n_topics=4, seed=2)  # non-divisible n
    local = np.asarray(tfidf.tfidf(jnp.asarray(c.counts)))
    st, _ = synth.stream_corpus(203, vocab=64, n_topics=4, seed=2, chunk=40)
    got = tfidf.tfidf_distributed_stream(mesh, ("data",), st).materialize()
    np.testing.assert_array_equal(got, local)
    print("TFIDF STREAM OK")
    """)


def test_distributed_streaming_bkc_matches_resident_4dev():
    _run("""
    import numpy as np, jax, jax.numpy as jnp
    from repro.common import l2_normalize
    from repro.distrib.cluster import bkc_distributed, bkc_distributed_stream
    from repro.distrib.sharding import (
        make_flat_mesh, pad_rows_to_multiple, shard_rows)
    from repro.text import synth, tfidf

    mesh = make_flat_mesh(4)
    c = synth.make_corpus(400, vocab=128, n_topics=6, seed=4)
    x = tfidf.tfidf(jnp.asarray(c.counts))
    cidx = jax.random.choice(
        jax.random.PRNGKey(2), x.shape[0], shape=(32,), replace=False)
    init = l2_normalize(x[cidx])

    xp, w = pad_rows_to_multiple(x, 4)
    res = bkc_distributed(
        mesh, ("data",), shard_rows(mesh, ("data",), xp),
        shard_rows(mesh, ("data",), w), init, 32, 6)

    st, _ = synth.stream_corpus(400, vocab=128, n_topics=6, seed=4, chunk=80)
    sres = bkc_distributed_stream(
        mesh, ("data",), tfidf.tfidf_stream(st), init, 32, 6)
    np.testing.assert_array_equal(
        np.asarray(res.assignment)[:400], sres.assignment)
    np.testing.assert_allclose(
        np.asarray(res.centers), np.asarray(sres.centers),
        rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(float(res.rss), float(sres.rss), rtol=1e-5)
    print("BKC STREAM OK")
    """)


def test_fold_job_topk_kind_4dev():
    """Engine fold-mode 'topk': per-shard running top-s + gather-finalize ==
    direct global top-s of every candidate ever emitted."""
    _run("""
    import numpy as np, jax, jax.numpy as jnp
    from repro.distrib.engine import make_fold_job
    from repro.distrib.sharding import make_flat_mesh, shard_rows

    mesh = make_flat_mesh(4)
    s = 6
    rng = np.random.default_rng(0)
    scores = rng.permutation(160).astype(np.float32)  # distinct -> unique top
    payload = np.arange(160, dtype=np.int32) * 10

    def mc(data, bcast):
        top, pos = jax.lax.top_k(data["score"], s)
        return {"best": {"score": top, "tag": data["tag"][pos]}}

    fold = make_fold_job(mesh, ("data",), mc, {"best": "topk"})
    carry = None
    for start in range(0, 160, 40):
        data = {
            "score": shard_rows(mesh, ("data",), jnp.asarray(scores[start:start + 40])),
            "tag": shard_rows(mesh, ("data",), jnp.asarray(payload[start:start + 40])),
        }
        carry, _ = fold.step(carry, data, {})
    out = fold.finalize(carry)["best"]
    want = np.argsort(-scores)[:s]
    np.testing.assert_array_equal(np.asarray(out["score"]), scores[want])
    np.testing.assert_array_equal(np.asarray(out["tag"]), payload[want])
    print("TOPK FOLD OK")
    """)


def test_fold_job_topk_requires_score_leaf():
    from repro.distrib.engine import _check_topk

    with pytest.raises(ValueError, match="score"):
        _check_topk({"gidx": None})
    with pytest.raises(ValueError, match="score"):
        _check_topk(np.zeros((3,)))


def test_distributed_streaming_reservoir_matches_oracle_4dev():
    """Sharded streaming reservoir == host-replayed global top-s of the same
    per-(chunk, shard) uniforms, rows == the corpus rows at those indices
    (non-shard-multiple n: the padded tail never samples)."""
    _run("""
    import numpy as np, jax, jax.numpy as jnp
    from repro.distrib.cluster import reservoir_sample_distributed_stream
    from repro.distrib.sharding import make_flat_mesh
    from repro.text import synth, tfidf

    mesh = make_flat_mesh(4)
    n, chunk, s = 403, 80, 48
    key = jax.random.PRNGKey(3)
    c = synth.make_corpus(n, vocab=128, n_topics=6, seed=4)
    x = np.asarray(tfidf.tfidf(jnp.asarray(c.counts)))

    st, _ = synth.stream_corpus(n, vocab=128, n_topics=6, seed=4, chunk=chunk)
    xs = tfidf.tfidf_stream(st)
    rows, gidx = reservoir_sample_distributed_stream(mesh, ("data",), xs, s, key)

    # oracle: replay every shard's per-chunk uniforms on the host
    chunk_local = chunk // 4
    n_chunks = -(-n // chunk)
    full = np.full(n_chunks * chunk, -1.0, np.float32)
    for ci in range(n_chunks):
        ck = jax.random.fold_in(key, ci)
        for p in range(4):
            u = np.asarray(jax.random.uniform(
                jax.random.fold_in(ck, p), (chunk_local,)))
            lo = ci * chunk + p * chunk_local
            full[lo:lo + chunk_local] = u
    full[n:] = -1.0  # chunk-padding rows carry w == 0
    want = np.argsort(-full)[:s]
    np.testing.assert_array_equal(np.asarray(gidx), want)
    np.testing.assert_allclose(np.asarray(rows), x[gidx], rtol=1e-6, atol=1e-7)
    print("DIST RESERVOIR OK")
    """)


def test_distributed_sample_rows_no_pad_leak_4dev():
    """Regression: ``sample_rows_distributed`` used to score pad rows by a
    mask MULTIPLY (exactly 0.0, tied with real rows drawing 0.0) and had no
    oversample guard, so s > real rows silently returned zero pad rows as
    sample members. Pads now score -1 and s == n_real returns exactly the
    real rows; s > n_real raises."""
    _run("""
    import numpy as np, jax, jax.numpy as jnp, pytest
    from repro.distrib.cluster import sample_rows_distributed
    from repro.distrib.sharding import (
        make_flat_mesh, pad_rows_to_multiple, shard_rows)

    mesh = make_flat_mesh(4)
    rng = np.random.default_rng(8)
    x = rng.random((10, 5)).astype(np.float32)  # pads to 12 rows: 2 pad rows
    xp, w = pad_rows_to_multiple(jnp.asarray(x), 4)
    xs = shard_rows(mesh, ("data",), xp)
    ws = shard_rows(mesh, ("data",), w)

    rows = sample_rows_distributed(mesh, ("data",), xs, ws, 10,
                                   jax.random.PRNGKey(1))
    got = np.asarray(rows)
    # every real row sampled exactly once, zero pad rows
    order = np.lexsort(got.T)
    want = np.lexsort(x.T)
    np.testing.assert_array_equal(got[order], x[want])

    try:
        sample_rows_distributed(mesh, ("data",), xs, ws, 11,
                                jax.random.PRNGKey(1))
    except ValueError as e:
        assert "without" in str(e)
    else:
        raise AssertionError("oversample did not raise")
    print("SAMPLE ROWS OK")
    """)


def test_buckshot_distributed_stream_matches_resident_4dev():
    """End-to-end distributed streaming Buckshot == resident
    buckshot_distributed handed the SAME sample rows, on a non-shard-multiple
    n: assignments identical, centers/RSS at f32-ulp."""
    _run("""
    import numpy as np, jax, jax.numpy as jnp
    from repro.distrib.cluster import (
        buckshot_distributed, buckshot_distributed_stream,
        reservoir_sample_distributed_stream)
    from repro.distrib.sharding import (
        make_flat_mesh, pad_rows_to_multiple, shard_rows)
    from repro.text import synth, tfidf

    mesh = make_flat_mesh(4)
    n, chunk, k, s = 403, 80, 6, 48
    key = jax.random.PRNGKey(3)
    c = synth.make_corpus(n, vocab=128, n_topics=6, seed=4)
    x = tfidf.tfidf(jnp.asarray(c.counts))

    st, _ = synth.stream_corpus(n, vocab=128, n_topics=6, seed=4, chunk=chunk)
    xs = tfidf.tfidf_stream(st)
    sres = buckshot_distributed_stream(
        mesh, ("data",), xs, k, key, sample_size=s, kmeans_iters=3)

    # the internal sampler is deterministic in (key, chunk): re-drawing it
    # yields the sample the streaming driver used
    rows, gidx = reservoir_sample_distributed_stream(mesh, ("data",), xs, s, key)
    xp, w = pad_rows_to_multiple(x, 4)
    res = buckshot_distributed(
        mesh, ("data",), shard_rows(mesh, ("data",), xp),
        shard_rows(mesh, ("data",), w), k, key,
        sample_size=s, sample_rows=rows, kmeans_iters=3)

    np.testing.assert_array_equal(np.asarray(res.assignment)[:n], sres.assignment)
    np.testing.assert_allclose(
        np.asarray(res.centers), np.asarray(sres.centers), rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(float(res.rss), float(sres.rss), rtol=1e-5)
    print("BUCKSHOT DIST STREAM OK")
    """)


def test_distributed_prefetch_parity_4dev():
    """Streaming distributed K-Means and Buckshot: prefetch on vs off is
    bit-identical on the mesh (the executor only moves chunk generation to a
    background thread)."""
    _run("""
    import os
    import numpy as np, jax, jax.numpy as jnp
    from repro.core.kmeans import init_random_centers
    from repro.distrib.cluster import (
        buckshot_distributed_stream, kmeans_distributed_stream)
    from repro.distrib.sharding import make_flat_mesh
    from repro.text import synth, tfidf

    mesh = make_flat_mesh(4)
    n, chunk, k = 403, 80, 6
    key = jax.random.PRNGKey(1)

    def build():
        st, _ = synth.stream_corpus(
            n, vocab=128, n_topics=6, seed=4, chunk=chunk)
        return tfidf.tfidf_stream(st)

    init = init_random_centers(
        key, jnp.asarray(build().materialize()), k)
    got = {}
    for mode in ("0", "2"):
        os.environ["REPRO_STREAM_PREFETCH"] = mode
        km = kmeans_distributed_stream(
            mesh, ("data",), build(), init, k, max_iters=4)
        bs = buckshot_distributed_stream(
            mesh, ("data",), build(), k, key, sample_size=48, kmeans_iters=2)
        got[mode] = (km, bs)
    km0, bs0 = got["0"]; km1, bs1 = got["2"]
    np.testing.assert_array_equal(km0.assignment, km1.assignment)
    np.testing.assert_array_equal(
        np.asarray(km0.centers), np.asarray(km1.centers))
    assert km0.iterations == km1.iterations
    np.testing.assert_array_equal(bs0.assignment, bs1.assignment)
    np.testing.assert_array_equal(
        np.asarray(bs0.centers), np.asarray(bs1.centers))
    print("DIST PREFETCH PARITY OK")
    """)


def test_distributed_streaming_kmeans_matches_resident_4dev():
    _run("""
    import numpy as np, jax, jax.numpy as jnp
    from repro.core.kmeans import init_random_centers
    from repro.distrib.cluster import (
        kmeans_distributed, kmeans_distributed_stream)
    from repro.distrib.sharding import (
        make_flat_mesh, pad_rows_to_multiple, shard_rows)
    from repro.text import synth, tfidf

    mesh = make_flat_mesh(4)
    c = synth.make_corpus(400, vocab=128, n_topics=6, seed=4)
    x = tfidf.tfidf(jnp.asarray(c.counts))
    init = init_random_centers(jax.random.PRNGKey(1), x, 6)

    xp, w = pad_rows_to_multiple(x, 4)
    res = kmeans_distributed(
        mesh, ("data",), shard_rows(mesh, ("data",), xp),
        shard_rows(mesh, ("data",), w), init, 6, max_iters=5)

    st, _ = synth.stream_corpus(400, vocab=128, n_topics=6, seed=4, chunk=80)
    sres = kmeans_distributed_stream(
        mesh, ("data",), tfidf.tfidf_stream(st), init, 6, max_iters=5)
    assert res.iterations == sres.iterations
    np.testing.assert_array_equal(
        np.asarray(res.assignment)[:400], sres.assignment)
    np.testing.assert_allclose(
        np.asarray(res.centers), np.asarray(sres.centers),
        rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(float(res.rss), float(sres.rss), rtol=1e-5)
    print("KMEANS STREAM OK")
    """)
