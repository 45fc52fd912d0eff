"""Faults planted under the timed path, to show that ``correct`` catches
them. The benchmark's own runs never plant one.

``plant(entry, name)`` replaces one function of the program before the
cell's job is traced, so the job that the window drives runs broken:

- ``step_unchanged``: a K-Means step returns its centers unchanged;
- ``tile_unchanged``: the step leaves the centers of the last, partial
  tile of 128 clusters unchanged (400 = 3 x 128 + 16): a fault confined to
  a few clusters;
- ``half_batch``: half of the rows get weight 0, so the statistics are the
  mean over the rest;
- ``answer_altered``: row 0's assignment is moved to the next center;
- ``labels_altered``: one sample row's single-link label is moved.
"""

from __future__ import annotations

import importlib

TILE = 128


def _step_unchanged():
    km = importlib.import_module("repro.core.kmeans")
    step = km.kmeans_step

    def kmeans_step(x, centers, k, **kw):
        out = step(x, centers, k, **kw)
        return (centers,) + tuple(out[1:])

    km.kmeans_step = kmeans_step


def _tile_unchanged():
    km = importlib.import_module("repro.core.kmeans")
    step = km.kmeans_step

    def kmeans_step(x, centers, k, **kw):
        new, *rest = step(x, centers, k, **kw)
        last = (k // TILE) * TILE
        return (new.at[last:].set(centers[last:]), *rest)

    km.kmeans_step = kmeans_step


def _half_batch():
    import jax.numpy as jnp

    ops = importlib.import_module("repro.kernels.ops")
    assign_stats = ops.assign_stats

    def half(x, centers, w=None, **kw):
        n = x.shape[0]
        keep = (jnp.arange(n) < n // 2).astype(jnp.float32)
        return assign_stats(x, centers, keep if w is None else w * keep, **kw)

    ops.assign_stats = half


def _answer_altered():
    ops = importlib.import_module("repro.kernels.ops")
    assign_stats = ops.assign_stats

    def altered(x, centers, w=None, **kw):
        st = assign_stats(x, centers, w, **kw)
        return st._replace(idx=st.idx.at[0].set((st.idx[0] + 1) % centers.shape[0]))

    ops.assign_stats = altered


def _labels_altered():
    import jax.numpy as jnp

    bs = importlib.import_module("repro.core.buckshot")
    single_link = bs.single_link_labels_boruvka

    def altered(xs, k, **kw):
        lab = single_link(xs, k, **kw)
        return lab.at[1].set(jnp.where(lab[1] != lab[0], lab[0], (lab[0] + 1) % k))

    bs.single_link_labels_boruvka = altered


def _stream_fold(change):
    km = importlib.import_module("repro.core.kmeans")
    fold = km._stream_fold_chunk

    def broken(carry, x, w, centers, **kw):
        return change(fold, carry, x, w, centers, **kw)

    km._stream_fold_chunk = broken


def _stream_step_unchanged():
    # the statistics never grow, so every center keeps its old value
    _stream_fold(lambda f, carry, x, w, c, **kw: (carry, f(carry, x, w, c, **kw)[1]))


def _stream_half_batch():
    import jax.numpy as jnp

    _stream_fold(lambda f, carry, x, w, c, **kw: f(
        carry, x, w * (jnp.arange(x.shape[0]) < x.shape[0] // 2), c, **kw))


def _stream_answer_altered():
    def change(f, carry, x, w, c, **kw):
        new, (idx, sim, obj) = f(carry, x, w, c, **kw)
        return new, (idx.at[0].set((idx[0] + 1) % c.shape[0]), sim, obj)

    _stream_fold(change)


FAULTS = {
    "kmeans": {"step_unchanged": _step_unchanged, "tile_unchanged": _tile_unchanged,
               "half_batch": _half_batch, "answer_altered": _answer_altered},
    # tile_unchanged is no fault in a Buckshot cell: single link leaves the
    # clusters of the last tile with no rows, so their centers never move
    "buckshot": {"step_unchanged": _step_unchanged, "half_batch": _half_batch,
                 "answer_altered": _answer_altered, "labels_altered": _labels_altered},
    "buckshot_stream": {"step_unchanged": _stream_step_unchanged,
                        "half_batch": _stream_half_batch,
                        "answer_altered": _stream_answer_altered,
                        "labels_altered": _labels_altered},
}


def plant(entry: str, name: str) -> None:
    FAULTS[entry][name]()
