"""K-Means jobs on the resident corpus: ``repro.core.kmeans`` under one
``jax.jit``, every job running its ``iters`` iterations."""

import importlib

import faults

HOST_CORPUS = False


def make(cell, x, impl, devices):
    import jax

    from repro.core import kmeans

    k, t = cell.cfg["k"], cell.traffic
    # tol 0: every job runs its max_iters iterations unless it converges exactly
    fn = jax.jit(lambda x, key: kmeans(x, k, key, max_iters=t["iters"], tol=0.0, **impl))

    def run(key):
        r = jax.block_until_ready(fn(x, key))
        return {"centers": r.centers, "assignment": r.assignment, "rss": r.rss,
                "best_sim": r.best_sim}

    return run


TILE = 128


def tile_unchanged():
    """The step leaves the centers of the last, partial tile of 128
    clusters unchanged (400 = 3 x 128 + 16): a fault confined to a few
    clusters. No fault in a Buckshot job, where single link leaves the
    clusters of the last tile with no rows, so their centers never move."""
    km = importlib.import_module("repro.core.kmeans")
    step = km.kmeans_step

    def kmeans_step(x, centers, k, **kw):
        new, *rest = step(x, centers, k, **kw)
        last = (k // TILE) * TILE
        return (new.at[last:].set(centers[last:]), *rest)

    km.kmeans_step = kmeans_step


FAULTS = {"step_unchanged": faults.step_unchanged, "tile_unchanged": tile_unchanged,
          "half_batch": faults.half_batch, "answer_altered": faults.answer_altered}
