"""Streamed Buckshot jobs: ``repro.core.buckshot_stream`` over
``CorpusStream.from_array`` of the corpus in host RAM, in chunks of
``chunk`` rows."""

import importlib

import faults

HOST_CORPUS = True


def make(cell, x_host, impl, devices):
    import jax

    from repro.core import buckshot_stream
    from repro.text.stream import CorpusStream

    k, t = cell.cfg["k"], cell.traffic
    stream = CorpusStream.from_array(x_host, chunk=t["chunk"])

    def run(key):
        r = buckshot_stream(stream, k, key, kmeans_iters=t["iters"], **impl)
        km = jax.block_until_ready(r.kmeans)
        return {
            "centers": km.centers, "assignment": km.assignment, "rss": km.rss,
            "best_sim": km.best_sim, "sample_idx": r.sample_idx, "sample_labels": r.sample_labels,
            "init_centers": r.init_centers,
        }

    return run


# The stream path folds chunk by chunk through _stream_fold_chunk, not
# kmeans_step and one assign_stats call: its faults break the fold.


def _stream_fold(change):
    km = importlib.import_module("repro.core.kmeans")
    fold = km._stream_fold_chunk

    def broken(carry, x, w, centers, **kw):
        return change(fold, carry, x, w, centers, **kw)

    km._stream_fold_chunk = broken


def step_unchanged():
    # the statistics never grow, so every center keeps its old value
    _stream_fold(lambda f, carry, x, w, c, **kw: (carry, f(carry, x, w, c, **kw)[1]))


def half_batch():
    import jax.numpy as jnp

    _stream_fold(lambda f, carry, x, w, c, **kw: f(
        carry, x, w * (jnp.arange(x.shape[0]) < x.shape[0] // 2), c, **kw))


def answer_altered():
    def change(f, carry, x, w, c, **kw):
        new, (idx, sim, obj) = f(carry, x, w, c, **kw)
        return new, (idx.at[0].set((idx[0] + 1) % c.shape[0]), sim, obj)

    _stream_fold(change)


FAULTS = {"step_unchanged": step_unchanged, "half_batch": half_batch,
          "answer_altered": answer_altered, "labels_altered": faults.labels_altered}
