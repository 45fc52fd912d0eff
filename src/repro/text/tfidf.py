"""TF-IDF weighting in the vector space model (paper §2: 'most of them are
based on the vector space model representation with tf-idf weights').

Single-device entry point plus the distributed document-frequency job: df is a
per-shard partial sum psum'd across the data axes (another instance of the
combiner discipline — the reduce payload is (d,) not (n,d)).

Streaming (out-of-core) form is TWO passes over a ``text/stream.CorpusStream``:
pass 1 folds (df, n) over chunks — locally on one device, or through the
engine's fold job on a mesh (one psum for the whole pass) — and pass 2 is a
lazily-mapped stream that rescales + L2-normalizes each chunk on device as it
arrives. df and n are integer-valued, so the chunked f32 fold is EXACT and the
streamed rows are bit-identical to the resident ``tfidf``."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.common import l2_normalize
from repro.distrib.engine import make_fold_job, make_job


@jax.jit
def tf_weight(counts: jax.Array) -> jax.Array:
    """Sub-linear tf: 1 + log(tf) for tf > 0 (Manning et al. [28])."""
    return jnp.where(counts > 0, 1.0 + jnp.log(jnp.maximum(counts, 1.0)), 0.0)


@jax.jit
def idf_weight(df: jax.Array, n_docs: jax.Array | float) -> jax.Array:
    """Smoothed idf: log(n / (1 + df))."""
    return jnp.log(jnp.asarray(n_docs, jnp.float32) / (1.0 + df))


@jax.jit
def document_frequency(counts: jax.Array) -> jax.Array:
    return jnp.sum((counts > 0).astype(jnp.float32), axis=0)


@jax.jit
def tfidf(counts: jax.Array) -> jax.Array:
    """counts (n,d) -> L2-normalized tf-idf vectors (n,d) f32.

    n == 0 is rejected up front (a shape, so checked at trace time): idf
    would silently be log(0/...) = -inf for every term, and downstream
    clustering would ingest an empty matrix as if it were data. An all-zero
    ROW (an empty document) is fine — it stays the zero vector through the
    zero-safe L2 normalize."""
    if counts.shape[0] == 0:
        raise ValueError("tfidf: empty collection (n == 0 documents)")
    df = document_frequency(counts)
    x = tf_weight(counts) * idf_weight(df, counts.shape[0])
    x = jnp.maximum(x, 0.0)  # idf can go negative for terms in >n/e docs
    return l2_normalize(x)


def _df_map(data, bcast):
    """Shared map+combine for the (df, n) job: per-shard weighted presence."""
    del bcast
    c, ws = data["counts"], data["w"]
    present = (c > 0).astype(jnp.float32) * ws[:, None]
    return {"df": jnp.sum(present, axis=0), "n": jnp.sum(ws)}


@jax.jit
def _rescale(c, df, n):
    x = tf_weight(c) * idf_weight(df, n)
    return l2_normalize(jnp.maximum(x, 0.0))


def tfidf_distributed(
    mesh: Mesh,
    axes: tuple[str, ...],
    counts: jax.Array,
    w: jax.Array,
) -> jax.Array:
    """Distributed tf-idf: one MapReduce job for (df, n), then a local rescale.

    counts rows sharded over `axes`; padding rows have w == 0."""
    job = make_job(mesh, axes, _df_map, {"df": "sum", "n": "sum"}, name="tfidf_df")
    stats = job({"counts": counts, "w": w}, {})
    return _rescale(counts, stats["df"], stats["n"])


# ------------------------------------------------------------------ streaming


def df_stream(stream, *, checkpoint=None, guard=None) -> tuple[jax.Array, jax.Array]:
    """Pass 1 over a count-chunk stream: fold (df (d,), n) — exact, since
    both are integer-valued however the chunks split the rows. Driven by the
    shared streaming executor, so chunk generation overlaps the fold.
    Checkpoints under pass id ``tfidf/df``; guard='finite' attributes the
    first non-finite accumulator to its chunk."""
    from repro.text.stream import run_pass, to_device

    if stream.n == 0:
        raise ValueError("df_stream: empty stream (n == 0 documents)")

    def fold(carry, ch, ci):
        x, w = to_device(ch)
        part = _df_map({"counts": x, "w": w}, ())
        df, n = carry
        return df + part["df"], n + part["n"]

    return run_pass(
        stream,
        fold,
        (jnp.zeros((stream.dim,), jnp.float32), jnp.float32(0.0)),
        pass_id="tfidf/df",
        checkpoint=checkpoint,
        guard=guard,
    )


def tfidf_stream(stream, *, checkpoint=None, guard=None):
    """Streaming two-pass tf-idf: (df, n) fold, then a lazily-mapped stream
    whose chunks are rescaled + L2-normalized on device on arrival.

    Bit-exact vs resident ``tfidf``: pass 1 folds integers, pass 2 applies
    the identical elementwise rescale per chunk. Peak residency O(chunk·d)."""
    df, n = df_stream(stream, checkpoint=checkpoint, guard=guard)
    return stream.map(lambda c, w: _rescale(jnp.asarray(c), df, n))


def df_fold_distributed(mesh, axes, stream) -> dict:
    """Distributed pass 1: the engine fold job — every chunk is mapped and
    combined per shard, ONE psum closes the pass (not one per chunk)."""
    from repro.distrib.sharding import check_stream_shardable, shard_rows
    from repro.text.stream import run_pass, to_device

    check_stream_shardable(stream, mesh, axes)
    job = make_fold_job(
        mesh, axes, _df_map, {"df": "sum", "n": "sum"}, name="tfidf_df_fold"
    )

    def fold(carry, ch, ci):
        x, w = to_device(ch)
        data = {
            "counts": shard_rows(mesh, axes, x),
            "w": shard_rows(mesh, axes, w),
        }
        carry, _ = job.step(carry, data, {})
        return carry

    return job.finalize(run_pass(stream, fold, None))


def tfidf_distributed_stream(mesh, axes, stream):
    """Streaming distributed tf-idf: fold-job pass 1, per-chunk rescale pass 2.

    Returns a mapped stream; consumers (distrib.cluster streaming jobs) shard
    each rescaled chunk onto the mesh as it arrives."""
    stats = df_fold_distributed(mesh, axes, stream)
    df, n = stats["df"], stats["n"]
    return stream.map(lambda c, w: _rescale(jnp.asarray(c), df, n))
