"""Buckshot jobs: a sample of s rows drawn from the job's key by the
traffic's ``sample`` rule, single link over it cut at k clusters, the unit
means of those clusters as initial centers, then spherical K-Means for
``iters`` iterations.

The numbers of phase 1, before those of ``reference.kmeans_numbers``:

- ``sample_mismatch``: sampled rows that differ from the reference's draw.
  Exact.
- ``cut_gap``: the largest similarity between two sample rows that the
  program put in different clusters, less the weight of the strongest
  spanning-tree edge that single-link cuts. Single-link at k clusters is
  the k-partition of least such similarity, so a correct cut reads 0, a
  near tie reads rounding, and a wrong cut reads a gap.
- ``cut_margin`` (printed, not compared): the weakest kept tree edge less
  the strongest cut one, how near the cut is to a tie.
- ``init_center_gap``: largest coordinate gap between the program's initial
  centers and the unit means of its own sample clusters.
"""

import jax.numpy as jnp
import numpy as np

import reference as ref


def run(x, key, p, *, lower=False):
    k = p["k"]
    idx = ref.draw_sample(key, p)
    xs = ref.l2n(x[idx])
    labels = ref.single_link(xs, k=k, lower=lower)
    init = ref.label_centers(xs, labels, k=k, lower=lower)
    return {"sample_idx": idx, "sample_labels": labels, "init_centers": init,
            **ref.kmeans_job(x, init, p, lower=lower)}


def numbers(x, key, out, p) -> dict[str, float]:
    k = p["k"]
    got: dict[str, float] = {}
    idx = ref.draw_sample(key, p)
    xs = ref.l2n(x[idx])
    got["sample_mismatch"] = float(
        np.sum(np.asarray(out["sample_idx"]) != np.asarray(idx))
    )
    lp = jnp.asarray(np.asarray(out["sample_labels"], np.int32))
    labels, cut_gap, margin = ref.phase1_check(xs, lp, k=k)
    got["cut_gap"] = float(cut_gap)
    got["cut_margin"] = float(margin)
    own = ref.label_centers(xs, lp, k=k)
    got["init_center_gap"] = float(
        np.max(np.abs(np.asarray(out["init_centers"]) - np.asarray(own)))
    )
    init = ref.label_centers(xs, labels, k=k)
    return {**got, **ref.kmeans_numbers(x, init, out, p)}
