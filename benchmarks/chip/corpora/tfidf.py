"""A tf-idf corpus, made on the device from a seed: the generator that a
configuration names as ``"generator": "tfidf"`` in its ``corpus`` block.

The distribution is that of the program's host generator (``text/synth``):
each of ``topics`` topics is a Dirichlet(``sharpness``) distribution over
the hashed vocabulary, every document mixes its topic with a shared
Dirichlet(1) background at weight ``background``, its length is
Poisson(``doc_len``) clipped below at 16, and its tokens are drawn from
that mixture. The counts become sub-linear tf times smoothed idf,
``log(n / (1 + df))``, floored at 0 and L2-normalized per row, as in
``text/tfidf``.

Everything runs in one jitted program: tokens are drawn by
inverse CDF in row blocks, counted by compares on the vector unit (no
scatter, no gather) and written into the tf matrix in place, so the host
never holds the corpus and the generator costs one program load.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

MIN_LEN = 16


def _block_rows(n: int, cap: int = 8192) -> int:
    """The largest divisor of ``n`` not above ``cap``: blocks tile the rows."""
    for b in range(min(cap, n), 0, -1):
        if n % b == 0:
            return b
    return 1


def _mixture_cdf(key, d: int, topics: int, sharpness: float, background: float):
    kt, kb = jax.random.split(key)
    topic_w = jax.random.dirichlet(kt, jnp.full((d,), sharpness), (topics,))
    back = jax.random.dirichlet(kb, jnp.ones((d,)))
    mix = (1.0 - background) * topic_w + background * back[None, :]
    cdf = jnp.cumsum(mix, axis=1)
    return cdf / cdf[:, -1:]


def _tf(key, cdf, n: int, doc_len: float, lmax: int):
    """(n, d) sub-linear tf rows and their (d,) document frequency."""
    topics, d = cdf.shape
    block = _block_rows(n)

    iota = jnp.arange(d, dtype=jnp.int32)[None, :]

    def body(i, carry):
        tf, df = carry
        kl, kn, kt = jax.random.split(jax.random.fold_in(key, i), 3)
        labels = jax.random.randint(kl, (block,), 0, topics)
        lengths = jnp.clip(
            jax.random.poisson(kn, doc_len, (block,)), MIN_LEN, lmax
        )
        u = jax.random.uniform(kt, (block, lmax))
        rows_cdf = cdf[labels]

        def token(t, counts):
            # inverse CDF: the token is the number of CDF entries at or
            # below u; compares and sums on the vector unit, no gather
            ut = jax.lax.dynamic_slice_in_dim(u, t, 1, axis=1)
            tok = jnp.minimum(jnp.sum((rows_cdf <= ut).astype(jnp.int32), axis=1), d - 1)
            hit = (iota == tok[:, None]) & (t < lengths)[:, None]
            return counts + hit.astype(jnp.float32)

        counts = jax.lax.fori_loop(0, lmax, token, jnp.zeros((block, d), jnp.float32))
        blk = jnp.where(counts > 0, 1.0 + jnp.log(jnp.maximum(counts, 1.0)), 0.0)
        df = df + jnp.sum((counts > 0).astype(jnp.float32), axis=0)
        tf = jax.lax.dynamic_update_slice(tf, blk, (i * block, 0))
        return tf, df

    init = (jnp.zeros((n, d), jnp.float32), jnp.zeros((d,), jnp.float32))
    return jax.lax.fori_loop(0, n // block, body, init)


def _finish(tf, df, n_total: int):
    idf = jnp.log(jnp.float32(n_total) / (1.0 + df))
    x = jnp.maximum(tf * idf[None, :], 0.0)
    norm = jnp.sqrt(jnp.sum(x * x, axis=1, keepdims=True))
    return x / jnp.maximum(norm, 1e-12)


def lmax_for(doc_len: float) -> int:
    """A token budget per row that a Poisson(doc_len) length exceeds with
    probability below 1e-15."""
    return int(math.ceil(doc_len + 10.0 * math.sqrt(doc_len)))


def make(key, corpus: dict, n: int, d: int):
    """(n, d) f32 tf-idf rows on the default device. ``corpus`` holds
    topics, doc_len, sharpness and background."""
    kmix, krows = jax.random.split(key)
    doc_len = float(corpus["doc_len"])
    lmax = lmax_for(doc_len)
    cdf = jax.jit(
        _mixture_cdf, static_argnums=(1, 2, 3, 4)
    )(kmix, d, int(corpus["topics"]), float(corpus["sharpness"]),
      float(corpus["background"]))

    @jax.jit
    def one(krows, cdf):
        tf, df = _tf(jax.random.fold_in(krows, 0), cdf, n, doc_len, lmax)
        return _finish(tf, df, n)

    return one(krows, cdf)
