"""Buckshot jobs on the resident corpus: ``repro.core.buckshot`` under one
``jax.jit``."""

import faults

HOST_CORPUS = False


def make(cell, x, impl, devices):
    import jax

    from repro.core import buckshot

    k, t = cell.cfg["k"], cell.traffic
    fn = jax.jit(lambda x, key: buckshot(x, k, key, kmeans_iters=t["iters"], **impl))

    def run(key):
        r = jax.block_until_ready(fn(x, key))
        return {
            "centers": r.kmeans.centers, "assignment": r.kmeans.assignment,
            "rss": r.kmeans.rss, "best_sim": r.kmeans.best_sim, "sample_idx": r.sample_idx,
            "sample_labels": r.sample_labels, "init_centers": r.init_centers,
        }

    return run


FAULTS = {"step_unchanged": faults.step_unchanged, "half_batch": faults.half_batch,
          "answer_altered": faults.answer_altered, "labels_altered": faults.labels_altered}
