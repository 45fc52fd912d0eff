"""Sampling utilities (paper: mappers assign random keys; one reducer extracts).

Single-device: ``jax.random.choice`` without replacement.
Distributed (see distrib/engine.py usage): each shard draws iid uniforms per doc,
takes its local top-s, and a global top-s over the gathered candidates yields an
exact uniform sample without replacement (global top-s is a subset of the union
of local top-s sets).
Streaming (``reservoir_sample_stream``): the same top-s trick as a RUNNING fold
over corpus chunks — top-s is a monoid (top_s(A ∪ B) = top_s(top_s(A) ∪
top_s(B))), so carrying the s best (score, index, row) triples across chunks
computes the exact global top-s, i.e. an exact uniform s-sample without
replacement, with O(s·d + chunk·d) residency and one pass (DESIGN.md §10).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation


@functools.partial(jax.jit, static_argnames=("n", "s"))
def sample_indices(key: jax.Array, n: int, s: int) -> jax.Array:
    """s distinct indices uniform over [0, n)."""
    return jax.random.choice(key, n, shape=(s,), replace=False)


@functools.partial(jax.jit, static_argnames=("s",))
def local_top_s(key: jax.Array, n_local: int, s: int) -> tuple[jax.Array, jax.Array]:
    """Per-shard step of distributed sampling: (scores, local indices) of top-s."""
    u = jax.random.uniform(key, (n_local,))
    scores, idx = jax.lax.top_k(u, min(s, n_local))
    return scores, idx.astype(jnp.int32)


def buckshot_sample_size(n: int, k: int) -> int:
    """Paper's sample size s = sqrt(k * n)."""
    import math

    return max(k, int(math.ceil(math.sqrt(float(k) * float(n)))))


# ---------------------------------------------------------------- streaming


@functools.partial(jax.jit, static_argnames=("s",))
def merge_top_s(
    carry: tuple[jax.Array, jax.Array, jax.Array],
    scores: jax.Array,
    gidx: jax.Array,
    rows: jax.Array,
    s: int,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One step of the running top-s reservoir: fold a chunk's candidates in.

    carry = (scores (s,), gidx (s,), rows (s, d)); the chunk contributes
    per-row scores (pad rows ≤ -1, so they lose to every real uniform in
    [0, 1)). Top-s of the (s + chunk) union is the exact top-s of everything
    seen — ``local_top_s``'s per-shard trick turned into a chunk monoid.
    """
    c_scores, c_gidx, c_rows = carry
    all_scores = jnp.concatenate([c_scores, scores])
    all_gidx = jnp.concatenate([c_gidx, gidx.astype(jnp.int32)])
    all_rows = jnp.concatenate([c_rows, rows])
    top, pos = jax.lax.top_k(all_scores, s)
    return top, all_gidx[pos], all_rows[pos]


@functools.partial(jax.jit, static_argnames=("chunk",))
def _chunk_scores(key: jax.Array, w: jax.Array, start, chunk: int):
    u = jax.random.uniform(key, (chunk,))
    scores = jnp.where(w > 0, u, -1.0)  # padding loses every comparison
    gidx = start + jnp.arange(chunk, dtype=jnp.int32)
    return scores, gidx


def reservoir_sample_stream(
    stream, s: int, key: jax.Array, *, checkpoint=None, guard=None
) -> tuple[jax.Array, np.ndarray]:
    """Exact uniform s-sample (without replacement) of a chunk stream's real
    rows, in ONE pass with O(s·d) carry: rows never revisit the stream.

    Per-chunk uniforms are keyed by fold_in(key, chunk_index), so the sample
    is deterministic in (key, chunk size) — which is also what makes the pass
    checkpointable: a restored carry replays the identical per-chunk scores
    for the remaining chunks. The snapshot meta binds the rng key's content,
    so a snapshot folded under a different key never resumes this pass.

    The ``s == stream.n`` edge returns exactly the real rows: pad rows score
    -1.0, STRICTLY below any real row's [0, 1) draw (never tied — a mask
    multiply would score pads 0.0, interleaved with real rows drawing 0.0),
    and the carry's -2.0 filler loses to both, so neither can displace a
    real row from the top-s. ``s > stream.n`` is rejected up front.
    Returns (rows (s, d) device, global indices (s,) np.int32, sorted by
    descending score — a uniformly shuffled order).
    """
    from repro.text.stream import run_pass, to_device  # lazy: keeps layering acyclic

    if s > stream.n:
        raise ValueError(f"sample size {s} exceeds stream rows {stream.n}")

    meta = None
    if checkpoint is not None:
        from repro.resilience import array_token

        meta = {"key": array_token(jax.random.key_data(key)), "s": s}
        done = checkpoint.load_result("reservoir", meta=meta)
        if done is not None:
            return jnp.asarray(done["rows"]), np.asarray(done["gidx"])

    def fold(carry, ch, ci):
        x, w = to_device(ch)
        scores, gidx = _chunk_scores(
            jax.random.fold_in(key, ci), w, jnp.int32(ch.start), stream.chunk,
        )
        return merge_top_s(carry, scores, gidx, x, s)

    _, gidx, rows = run_pass(
        stream,
        fold,
        (
            jnp.full((s,), -2.0, jnp.float32),  # below even the pad sentinel
            jnp.full((s,), -1, jnp.int32),
            jnp.zeros((s, stream.dim), jnp.float32),
        ),
        pass_id="reservoir",
        checkpoint=checkpoint,
        guard=guard,
        meta=meta,
    )
    with TraceAnnotation("stream.sync"):
        gidx = np.asarray(gidx)
    if checkpoint is not None:
        checkpoint.save_result(
            "reservoir", {"rows": np.asarray(rows), "gidx": gidx}, meta=meta
        )
    return rows, gidx
