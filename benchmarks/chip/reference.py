"""The plain reference and the comparison that decides ``correct``.

Spherical K-Means, single-link HAC (Prim's maximum spanning tree, cut at k
clusters) and the samples, written in straightforward ``jax.numpy``. Each
kind of job that a traffic file names is a file ``kinds/<kind>.py`` built
from these pieces: ``run(x, key, p, *, lower=False)`` does the whole job
and ``numbers(x, key, out, p)`` compares a job's answers. Nothing here or
there imports the program or takes anything it made: the data are the
benchmark's own (``corpora/``), samples and initial centers are drawn from
the job's key by the same published rules, and the program's outputs enter
only as the answers being checked.

Every matrix product runs in float32 at ``Precision.HIGHEST``, the precision
the configurations state. ``lower=True`` runs the same product as three
bfloat16 passes (hi·hi + hi·lo + lo·hi, float32 accumulation), the TPU's
``BF16_BF16_F32_X3`` algorithm: that is the control, the reference put in
the program's place one precision step down.

The numbers of a job's final K-Means answers (a traffic file gives a limit
to each one its cell compares; the others are printed beside them; a kind
adds its own):

- ``assign_gap``: over every row, how far the similarity of the center the
  program assigned lies below the best similarity to the program's own final
  centers. A sound run reads rounding; an assignment made at a lower
  precision reads the near ties it got wrong.
- ``rss_gap``: relative gap between the program's RSS and the RSS of the
  program's own assignment recomputed here: the reduced statistics.
- ``center_gap``: the median over centers of the largest coordinate gap
  between the program's final centers and the reference's, which replays
  the whole job from the same key. The median, because a near tie that the
  two sides break differently moves a few centers and not the rest.
- ``center_gap_agreed``: the same gap at its worst, over the centers whose
  clusters hold the same rows in the program's final assignment and the
  reference's. A near tie broken differently leaves its two clusters out.
- ``center_gap_p99``: the 99th percentile of the same gap over all
  centers: after many iterations near ties move some tens of centers in a
  sound run, and a fault that breaks a few tiles of centers moves more.
- ``clusters_disagree`` (printed, not compared): how many clusters the two
  final assignments fill with different rows.
- ``sim_gap`` (where the program returns each row's best similarity): the
  largest gap between that similarity and the one recomputed here for the
  center the program assigned. It reads the precision of the assignment
  product itself, near ties or not.
"""

from __future__ import annotations

import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
f32 = jnp.float32


def matmul(a, b, lower: bool = False):
    """``a @ b`` in float32: at HIGHEST, or as three bf16 passes. A TPU runs
    the three passes itself when asked for the algorithm by name, with no
    bf16 copies of the operands in memory (``Precision.HIGH`` gave one pass
    there); the CPU computes that algorithm exactly, so the passes are
    written out elsewhere."""
    if not lower:
        return jnp.matmul(a, b, precision=HIGHEST, preferred_element_type=f32)
    if jax.default_backend() == "tpu":
        return jnp.matmul(
            a, b, precision=jax.lax.DotAlgorithmPreset.BF16_BF16_F32_X3,
            preferred_element_type=f32,
        )

    def split(m):
        hi = m.astype(jnp.bfloat16)
        return hi, (m - hi.astype(f32)).astype(jnp.bfloat16)

    ah, al = split(a)
    bh, bl = split(b)

    def mm(p, q):
        return jnp.matmul(p, q, preferred_element_type=f32)

    return mm(ah, bh) + (mm(ah, bl) + mm(al, bh))


def l2n(x):
    return x / jnp.maximum(jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True)), 1e-12)


def assign(x, c, lower=False):
    sim = matmul(x, c.T, lower)
    return jnp.argmax(sim, axis=1).astype(jnp.int32), jnp.max(sim, axis=1)


def cluster_sums(x, labels, k, lower=False):
    hot = jax.nn.one_hot(labels, k, dtype=f32)
    return matmul(hot.T, x, lower), jnp.sum(hot, axis=0)


def rss_of(x, sums, counts):
    return jnp.sum(x * x) - jnp.sum(
        jnp.sum(sums * sums, axis=1) / jnp.maximum(counts, 1.0)
    )


@functools.partial(jax.jit, static_argnames=("k", "max_iters", "lower"))
def kmeans_fit(x, init, *, k, max_iters, lower=False):
    """Spherical K-Means from ``init``: iterate while a center still moves
    and fewer than ``max_iters`` iterations ran, then assign once more.
    Returns (centers, assignment, best similarity, rss)."""

    def cond(st):
        c, prev, it = st
        moved = jnp.max(jnp.sum((c - prev) ** 2, axis=1))
        return jnp.logical_and(it < max_iters, moved > 0.0)

    def body(st):
        c, _, it = st
        idx, _ = assign(x, c, lower)
        sums, counts = cluster_sums(x, idx, k, lower)
        means = sums / jnp.maximum(counts, 1.0)[:, None]
        return jnp.where(counts[:, None] > 0, l2n(means), c), c, it + 1

    c, _, _ = jax.lax.while_loop(cond, body, (init, init + 10.0, jnp.int32(0)))
    idx, best = assign(x, c, lower)
    sums, counts = cluster_sums(x, idx, k, lower)
    return c, idx, best, rss_of(x, sums, counts)


# ------------------------------------------------------------------ samples


@functools.partial(jax.jit, static_argnames=("n", "s"))
def sample_choice(key, *, n, s):
    """s distinct rows, uniform: ``jax.random.choice`` without replacement."""
    return jax.random.choice(key, n, shape=(s,), replace=False)


@functools.partial(jax.jit, static_argnames=("n", "s", "chunk"))
def sample_reservoir(key, *, n, s, chunk):
    """The top s of one uniform draw per row, drawn chunk by chunk from
    ``fold_in(key, chunk index)``; rows past n score -1."""
    n_chunks = -(-n // chunk)
    u = jax.vmap(lambda ci: jax.random.uniform(jax.random.fold_in(key, ci), (chunk,)))(
        jnp.arange(n_chunks)
    ).reshape(-1)
    u = jnp.where(jnp.arange(n_chunks * chunk) < n, u, -1.0)
    return jax.lax.top_k(u, s)[1].astype(jnp.int32)


def draw_sample(key, p):
    n, s = p["n"], p["s"]
    if p["sample"] == "choice":
        return sample_choice(key, n=n, s=s)
    if p["sample"] == "reservoir":
        return sample_reservoir(key, n=n, s=s, chunk=p["chunk"])
    raise ValueError(f"unknown sample rule {p['sample']!r}")


# ---------------------------------------------------------------- single link


def _prim(sim):
    """Prim's maximum spanning tree of a dense similarity matrix."""
    s = sim.shape[0]
    neg = -jnp.inf

    def body(i, c):
        in_tree, best, frm, eu, ev, ew = c
        cand = jnp.where(in_tree, neg, best)
        j = jnp.argmax(cand).astype(jnp.int32)
        eu, ev, ew = eu.at[i].set(frm[j]), ev.at[i].set(j), ew.at[i].set(cand[j])
        row = sim[j]
        better = row > best
        return (in_tree.at[j].set(True), jnp.where(better, row, best),
                jnp.where(better, j, frm), eu, ev, ew)

    init = (
        jnp.zeros((s,), bool).at[0].set(True),
        sim[0].at[0].set(neg),
        jnp.zeros((s,), jnp.int32),
        jnp.zeros((s - 1,), jnp.int32),
        jnp.zeros((s - 1,), jnp.int32),
        jnp.zeros((s - 1,), f32),
    )
    return jax.lax.fori_loop(0, s - 1, body, init)[3:]


def _components(s, eu, ev, keep):
    """Min-id labels of the graph of the kept edges."""

    def body(st):
        lab, _ = st
        m = jnp.where(keep, jnp.minimum(lab[eu], lab[ev]), s)
        new = lab.at[eu].min(m).at[ev].min(m)
        new = jnp.minimum(new, new[new])
        return new, jnp.any(new != lab)

    lab0 = jnp.arange(s, dtype=jnp.int32)
    return jax.lax.while_loop(lambda st: st[1], body, (lab0, jnp.bool_(True)))[0]


def _single_link(xs, k, lower):
    """Sample similarities, single-link labels at k clusters (dense ids in
    the order of each cluster's first row), and the weights of the weakest
    kept and the strongest cut tree edge."""
    s = xs.shape[0]
    sim = matmul(xs, xs.T, lower)
    eu, ev, ew = _prim(sim)
    order = jnp.argsort(-ew, stable=True)
    keep = jnp.zeros((s - 1,), bool).at[order[: s - k]].set(True)
    lab = _components(s, eu, ev, keep)
    dense = (jnp.cumsum((lab == jnp.arange(s)).astype(jnp.int32)) - 1)[lab]
    w = ew[order]
    return sim, dense, w[s - k - 1], w[s - k]


@functools.partial(jax.jit, static_argnames=("k", "lower"))
def single_link(xs, *, k, lower=False):
    return _single_link(xs, k, lower)[1]


@functools.partial(jax.jit, static_argnames=("k",))
def phase1_check(xs, labels_prog, *, k):
    """(reference labels, cut_gap, cut margin) for the program's labels."""
    sim, dense, w_keep, w_cut = _single_link(xs, k, False)
    differ = labels_prog[:, None] != labels_prog[None, :]
    m_inter = jnp.max(jnp.where(differ, sim, -jnp.inf))
    in_range = jnp.all((labels_prog >= 0) & (labels_prog < k))
    used = jnp.sum(jnp.bincount(jnp.clip(labels_prog, 0, k - 1), length=k) > 0)
    ok = jnp.logical_and(in_range, used == k)
    return dense, jnp.where(ok, m_inter - w_cut, jnp.inf), w_keep - w_cut


@functools.partial(jax.jit, static_argnames=("k", "lower"))
def label_centers(xs, labels, *, k, lower=False):
    sums, counts = cluster_sums(xs, labels, k, lower)
    return jnp.where(counts[:, None] > 0, l2n(sums), 0.0)


# ---------------------------------------------------------------- the jobs


def load_kind(kind: str):
    """The reference of one kind of job, ``kinds/<kind>.py``."""
    return importlib.import_module(f"kinds.{kind}")


def run_reference(kind, x, key, p, *, lower=False):
    """The whole job, done by the reference: the control when ``lower``."""
    return load_kind(kind).run(x, key, p, lower=lower)


def kmeans_job(x, init, p, *, lower=False):
    """K-Means from ``init`` for ``p["iters"]`` iterations, with the answers
    named as a job returns them."""
    c, a, best, rss = kmeans_fit(x, init, k=p["k"], max_iters=p["iters"], lower=lower)
    return {"centers": c, "assignment": a, "rss": rss, "best_sim": best}


@jax.jit
def _final_pass_numbers(x, centers, assignment, rss, best_sim):
    sim = matmul(x, centers.T)
    chosen = jnp.take_along_axis(sim, assignment[:, None], axis=1)[:, 0]
    gap = jnp.max(jnp.max(sim, axis=1) - chosen)
    sums, counts = cluster_sums(x, assignment, centers.shape[0])
    want = rss_of(x, sums, counts)
    return gap, jnp.abs(rss - want) / want, jnp.max(jnp.abs(best_sim - chosen))


@jax.jit
def _center_numbers(c_prog, c_ref, a_prog, a_ref):
    """Per-center gaps, and which clusters the two final assignments fill
    with different rows."""
    k = c_ref.shape[0]
    differ = a_prog != a_ref
    touched = (
        jnp.zeros((k,), bool)
        .at[jnp.where(differ, a_prog, k)].set(True, mode="drop")
        .at[jnp.where(differ, a_ref, k)].set(True, mode="drop")
    )
    per = jnp.max(jnp.abs(c_prog - c_ref), axis=1)
    return per, touched


def kmeans_numbers(x, init, out, p) -> dict[str, float]:
    """The numbers of a job's K-Means answers in ``out``, against the
    reference's K-Means from ``init``."""
    k = p["k"]
    got: dict[str, float] = {}
    c_ref, a_ref, _, _ = kmeans_fit(x, init, k=k, max_iters=p["iters"])
    c_prog = jnp.asarray(np.asarray(out["centers"]))
    assignment = jnp.asarray(np.asarray(out["assignment"]), jnp.int32)
    per, touched = (np.asarray(v) for v in _center_numbers(c_prog, c_ref, assignment, a_ref))
    got["center_gap"] = float(np.median(per))
    got["center_gap_agreed"] = float(np.max(per[~touched], initial=0.0))
    got["center_gap_p99"] = float(np.quantile(per, 0.99))
    got["clusters_disagree"] = float(np.sum(touched))
    gap, rss_gap, sim_gap = _final_pass_numbers(
        x, c_prog, assignment, jnp.asarray(np.asarray(out["rss"], np.float32)),
        jnp.asarray(np.asarray(out["best_sim"], np.float32)),
    )
    got["assign_gap"] = float(gap)
    got["rss_gap"] = float(rss_gap)
    got["sim_gap"] = float(sim_gap)
    return got


def numbers(kind, x, key, out, p) -> dict[str, float]:
    """The numbers of one job's answers ``out``."""
    return load_kind(kind).numbers(x, key, out, p)
