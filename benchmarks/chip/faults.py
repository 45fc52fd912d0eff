"""Faults planted under the timed path, to show that ``correct`` catches
them. The benchmark's own runs never plant one.

Each entry's file (``entries/<entry>.py``) lists in ``FAULTS`` the faults
that are valid for its jobs, each with its planter. ``plant(entry, name)``
calls that planter, which replaces one function of the program before the
cell's job is traced, so the job that the window drives runs broken. The
planters that several entries share are kept here:

- ``step_unchanged``: a K-Means step returns its centers unchanged;
- ``half_batch``: half of the rows get weight 0, so the statistics are the
  mean over the rest;
- ``answer_altered``: row 0's assignment is moved to the next center;
- ``labels_altered``: one sample row's single-link label is moved.
"""

from __future__ import annotations

import importlib


def step_unchanged():
    km = importlib.import_module("repro.core.kmeans")
    step = km.kmeans_step

    def kmeans_step(x, centers, k, **kw):
        out = step(x, centers, k, **kw)
        return (centers,) + tuple(out[1:])

    km.kmeans_step = kmeans_step


def half_batch():
    import jax.numpy as jnp

    ops = importlib.import_module("repro.kernels.ops")
    assign_stats = ops.assign_stats

    def half(x, centers, w=None, **kw):
        n = x.shape[0]
        keep = (jnp.arange(n) < n // 2).astype(jnp.float32)
        return assign_stats(x, centers, keep if w is None else w * keep, **kw)

    ops.assign_stats = half


def answer_altered():
    ops = importlib.import_module("repro.kernels.ops")
    assign_stats = ops.assign_stats

    def altered(x, centers, w=None, **kw):
        st = assign_stats(x, centers, w, **kw)
        return st._replace(idx=st.idx.at[0].set((st.idx[0] + 1) % centers.shape[0]))

    ops.assign_stats = altered


def labels_altered():
    import jax.numpy as jnp

    bs = importlib.import_module("repro.core.buckshot")
    single_link = bs.single_link_labels_boruvka

    def altered(xs, k, **kw):
        lab = single_link(xs, k, **kw)
        return lab.at[1].set(jnp.where(lab[1] != lab[0], lab[0], (lab[0] + 1) % k))

    bs.single_link_labels_boruvka = altered


def plant(entry: str, name: str) -> None:
    importlib.import_module(f"entries.{entry}").FAULTS[name]()
