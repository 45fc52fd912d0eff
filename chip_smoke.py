#!/usr/bin/env python3
"""Run the clustering path once on a TPU, end to end, and check what it gives.

    python3 chip_smoke.py              # one chip: the paper's "1 GB" table shape
    python3 chip_smoke.py --chips 4    # four chips: distributed Buckshot, 2x2 mesh
    python3 chip_smoke.py --rehearse [--chips 4]   # CPU, tiny, never "ok"

One chip, at the shape of the paper's Tables 4 and 8 (n = 250,000 documents,
vocabulary d = 2048, k = 50 topics, seed 21; a 2.05 GB dense f32 tf-idf
matrix on the device): the corpus is generated once on the host, tf-idf runs
on the device, then one ``ops.assign_stats`` pass is checked against a
float64 numpy oracle, and resident K-Means, BKC and Buckshot and streaming
Buckshot each run with their default ``impl`` and again with
``impl="xla"`` on the same chip, which they must agree with.

Four chips: ``buckshot_distributed`` on a 2x2 pod mesh against its
single-device counterpart from the same sample rows. The distributed Borůvka
tree must equal the single-device one, and the rows of x must really be
sharded over the four devices.

Every phase prints one line; the last line is one JSON object,
``{"ok": true, "device": {...}}``, printed only when every check passed on a
TPU. Without a TPU the script exits non-zero. ``--rehearse`` runs the same
phases at a tiny size on the CPU with the Pallas kernels in interpret mode
(four virtual devices with ``--chips 4``); it never prints ``"ok": true``.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# (n, d, k, BigK, stream chunk): the paper's "1 GB" shape, and a rehearsal
# size that the Pallas interpreter runs in seconds
FULL = dict(n=250_000, d=2048, k=50, big_k=250, chunk=8192)
TINY = dict(n=2048, d=256, k=8, big_k=32, chunk=512)
SEED = 21

TIE_GAP = 1e-3  # rows whose top-2 similarity gap is below this may differ
STATS_RTOL = 1e-4  # assign_stats sums and RSS against the float64 oracle
RSS_RTOL = 0.01  # a driver's final RSS against the same driver on XLA
PURITY_ATOL = 0.01
MST_W_ATOL = 1e-5  # distributed against single-device edge weights

_KERNEL = re.compile(
    r'%([A-Za-z_][\w.-]*) = [^\n]*custom_call_target="tpu_custom_call"'
)


class CheckFailed(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise CheckFailed(what)


def kernels_in(compiled) -> list[str]:
    """Names of the Pallas kernels (``tpu_custom_call``) in a compiled program."""
    return sorted(
        {re.sub(r"(\.\d+)+$", "", m.group(1))
         for m in _KERNEL.finditer(compiled.as_text())}
    )


def say(phase: str, **fields) -> None:
    parts = [f"phase={phase}"]
    for key, val in fields.items():
        if isinstance(val, float):
            val = repr(val)
        elif isinstance(val, (list, tuple)):
            val = ",".join(map(str, val)) or "-"
        parts.append(f"{key}={val}")
    print(" ".join(parts), flush=True)


def near_tie_gap(x_host, centers, rows=25_000):
    """float64 (idx, top-2 gap) of every row of x against ``centers``."""
    import numpy as np

    c = np.asarray(centers, np.float64)
    idx, gap = [], []
    for a in range(0, x_host.shape[0], rows):
        s = x_host[a : a + rows].astype(np.float64) @ c.T
        top2 = np.partition(s, -2, axis=1)[:, -2:]
        idx.append(np.argmax(s, axis=1))
        gap.append(top2[:, 1] - top2[:, 0])
    return np.concatenate(idx), np.concatenate(gap)


def agree_up_to_ties(got, want, gap) -> int:
    """Rows where two assignments differ; every one must be a near tie."""
    import numpy as np

    diff = np.asarray(got) != np.asarray(want)
    check(
        bool(np.all(gap[diff] < TIE_GAP)),
        f"{int(np.sum(diff & (gap >= TIE_GAP)))} rows assigned differently"
        f" with a top-2 gap >= {TIE_GAP}",
    )
    return int(diff.sum())


# ----------------------------------------------------------------- one chip


def oracle_phase(x, x_host, key, k, impl_kw, on_tpu):
    """One ``ops.assign_stats`` pass against a float64 numpy oracle on the
    same x and centers. Rows whose float64 top-2 gap is below TIE_GAP keep
    the kernel's choice in the oracle's statistics; everywhere else the
    oracle assigns for itself, so counts must match exactly."""
    import jax
    import numpy as np

    from repro.core import metrics
    from repro.core.kmeans import init_random_centers
    from repro.kernels import ops

    centers = init_random_centers(key, x, k)
    t0 = time.perf_counter()
    compiled = jax.jit(lambda x, c: ops.assign_stats(x, c, **impl_kw)).lower(
        x, centers
    ).compile()
    t_compile = time.perf_counter() - t0
    t0 = time.perf_counter()
    st = jax.block_until_ready(compiled(x, centers))
    wall = time.perf_counter() - t0
    kern = kernels_in(compiled)

    idx = np.asarray(st.idx)
    want_idx, gap = near_tie_gap(x_host, centers)
    flips = agree_up_to_ties(idx, want_idx, gap)
    use = np.where(gap < TIE_GAP, idx, want_idx)
    c64 = np.asarray(centers, np.float64)
    sums = np.zeros((k, x_host.shape[1]))
    rowsq = 0.0
    for a in range(0, x_host.shape[0], 25_000):
        xs = x_host[a : a + 25_000].astype(np.float64)
        hot = np.eye(k)[use[a : a + 25_000]]
        sums += hot.T @ xs
        rowsq += float(np.sum(xs * xs))
    counts = np.bincount(use, minlength=k).astype(np.float64)
    rss_want = rowsq - float(np.sum(np.sum(sums * sums, 1) / np.maximum(counts, 1)))
    rss_got = float(
        metrics.rss_from_assignment_stats(st.sums, st.counts, st.sumsq.sum(), k)
    )
    sums_err = float(np.max(np.abs(np.asarray(st.sums) - sums)) / np.max(np.abs(sums)))
    rss_err = abs(rss_got - rss_want) / abs(rss_want)
    sim_err = float(
        np.max(np.abs(np.asarray(st.best_sim) - np.max(x_host @ c64.T, axis=1)))
        if x_host.shape[0] <= 4096
        else np.nan
    )
    say(
        "oracle_assign_stats", wall_s=wall, compile_s=t_compile, rss=rss_got,
        rss_oracle=rss_want, rss_rel_err=rss_err, sums_rel_err=sums_err,
        tie_flips=flips, near_ties=int(np.sum(gap < TIE_GAP)), kernels=kern,
        **({} if np.isnan(sim_err) else {"best_sim_err": sim_err}),
    )
    check(np.array_equal(np.asarray(st.counts), counts), "assign_stats counts")
    check(sums_err <= STATS_RTOL, f"assign_stats sums rel err {sums_err}")
    check(rss_err <= STATS_RTOL, f"assign_stats RSS rel err {rss_err}")
    check(not on_tpu or "assign_stats_pallas" in kern, "assign_stats kernel")
    return kern


def driver_phase(name, fn, x, labels, k, impl_kw, on_tpu):
    """Compile one resident driver call for x, run it, and print its line."""
    import jax

    t0 = time.perf_counter()
    compiled = jax.jit(lambda x: fn(x, **impl_kw)).lower(x).compile()
    t_compile = time.perf_counter() - t0
    t0 = time.perf_counter()
    res = jax.block_until_ready(compiled(x))
    wall = time.perf_counter() - t0
    res = getattr(res, "kmeans", res)  # BuckshotResult wraps its K-Means
    return finish_phase(
        name, res.assignment, res.rss, labels, k, impl_kw, on_tpu,
        kernels_in(compiled), wall_s=wall, compile_s=t_compile,
    )


def finish_phase(name, assignment, rss, labels, k, impl_kw, on_tpu, kern, **times):
    from repro.core import metrics
    from repro.kernels import ops

    impl = impl_kw.get("impl", "default")
    pur = float(metrics.purity(assignment, labels, k, k))
    say(f"{name}[{impl}]", **times, rss=float(rss), purity=pur, kernels=kern)
    if impl == "xla":
        check(not kern, f"{name}[xla] ran a Pallas kernel")
    elif on_tpu:
        check(bool(kern), f"{name} ran no Pallas kernel")
    check(not ops.pallas_degraded(), "a Pallas kernel degraded to XLA")
    return {"assignment": assignment, "rss": float(rss), "purity": pur,
            "kernels": kern}


def stream_phase(x_host, labels, key, cfg, impl_kw, on_tpu):
    """Streaming Buckshot over the resident matrix cut into chunks: the run
    drives run_pass, its prefetcher and the streamed fold. The first run
    compiles; the second is timed. Its kernels are read from its two device
    programs: phase 1 on the sample and the per-chunk fold."""
    import jax
    import jax.numpy as jnp

    from repro.core import buckshot_stream
    from repro.core.buckshot import phase1_from_sample
    from repro.core.kmeans import _stream_fold_chunk
    from repro.kernels import ops
    from repro.text.stream import CorpusStream

    k, d, chunk = cfg["k"], cfg["d"], cfg["chunk"]
    stream = CorpusStream.from_array(x_host, chunk=chunk)
    t0 = time.perf_counter()
    buckshot_stream(stream, k, key, kmeans_iters=2, **impl_kw)
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    res = buckshot_stream(stream, k, key, kmeans_iters=2, **impl_kw)
    jax.block_until_ready(res.kmeans.centers)
    wall = time.perf_counter() - t0

    f32 = jnp.float32
    rows = jax.ShapeDtypeStruct((res.sample_idx.shape[0], d), f32)
    fold = _stream_fold_chunk.lower(
        ops.stats_identity(k, d), jax.ShapeDtypeStruct((chunk, d), f32),
        jax.ShapeDtypeStruct((chunk,), f32), jax.ShapeDtypeStruct((k, d), f32),
        **impl_kw,
    ).compile()
    p1 = jax.jit(lambda xs: phase1_from_sample(xs, k, **impl_kw)).lower(rows).compile()
    kern = sorted(set(kernels_in(fold)) | set(kernels_in(p1)))
    return finish_phase(
        "buckshot_stream", jnp.asarray(res.kmeans.assignment), res.kmeans.rss,
        labels, k, impl_kw, on_tpu, kern, wall_s=wall, first_run_s=first,
    )


def one_chip(cfg, kernel_impl, on_tpu):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import bkc, buckshot, kmeans
    from repro.text import synth, tfidf

    n, d, k, big_k = cfg["n"], cfg["d"], cfg["k"], cfg["big_k"]
    t0 = time.perf_counter()
    corpus = synth.make_corpus(n, d, k, seed=SEED)
    say("corpus", wall_s=time.perf_counter() - t0, n=n, d=d, topics=k)

    t0 = time.perf_counter()
    x = jax.block_until_ready(tfidf.tfidf(jnp.asarray(corpus.counts)))
    say("tfidf", wall_s=time.perf_counter() - t0, bytes=x.nbytes,
        device=str(list(x.devices())[0]))
    labels = jnp.asarray(corpus.labels)
    del corpus
    x_host = np.asarray(x)
    key = jax.random.PRNGKey(SEED)
    ran = set(oracle_phase(x, x_host, key, k, kernel_impl, on_tpu))

    drivers = (
        ("kmeans", lambda x, **kw: kmeans(x, k, key, max_iters=8, tol=0.0, **kw)),
        ("bkc", lambda x, **kw: bkc(x, big_k, k, key, **kw)),
        ("buckshot", lambda x, **kw: buckshot(x, k, key, kmeans_iters=2, **kw)),
    )
    results = []
    for name, fn in drivers:
        got = driver_phase(name, fn, x, labels, k, kernel_impl, on_tpu)
        ref = driver_phase(name, fn, x, labels, k, {"impl": "xla"}, on_tpu)
        results.append((name, got, ref))
    got = stream_phase(x_host, labels, key, cfg, kernel_impl, on_tpu)
    ref = stream_phase(x_host, labels, key, cfg, {"impl": "xla"}, on_tpu)
    results.append(("buckshot_stream", got, ref))

    for name, got, ref in results:
        ran |= set(got["kernels"])
        rss_err = abs(got["rss"] - ref["rss"]) / abs(ref["rss"])
        dpur = abs(got["purity"] - ref["purity"])
        differ = int(np.sum(np.asarray(got["assignment"]) != np.asarray(ref["assignment"])))
        say(f"{name}_vs_xla", rss_rel_err=rss_err, purity_diff=dpur,
            rows_assigned_differently=differ)
        check(rss_err <= RSS_RTOL, f"{name}: RSS {rss_err:.4f} from XLA's")
        check(dpur <= PURITY_ATOL, f"{name}: purity {dpur:.4f} from XLA's")
    if on_tpu:
        for kernel in ("assign_stats_pallas", "sim_best_edge_pallas"):
            check(kernel in ran, f"{kernel} never ran")
    say("kernels", ran=sorted(ran))


# --------------------------------------------------------------- four chips


def four_chips(cfg, kernel_impl, on_tpu):
    """buckshot_distributed on a 2x2 pod mesh against its single-device
    counterpart from the same sample rows."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.common import l2_normalize
    from repro.core import metrics
    from repro.core.buckshot import phase1_from_sample
    from repro.core.hac import boruvka_mst
    from repro.core.kmeans import kmeans_fit
    from repro.core.sampling import buckshot_sample_size
    from repro.distrib.cluster import buckshot_distributed, sample_rows_distributed
    from repro.distrib.hac_parallel import boruvka_mst_distributed
    from repro.distrib.sharding import make_pod_mesh, shard_rows
    from repro.kernels import ops
    from repro.text import synth, tfidf

    n, d, k = cfg["n"], cfg["d"], cfg["k"]
    check(len(jax.devices()) == 4, f"need 4 devices, have {len(jax.devices())}")
    mesh, axes = make_pod_mesh(2, 2), ("pod", "data")
    t0 = time.perf_counter()
    corpus = synth.make_corpus(n, d, k, seed=SEED)
    say("corpus", wall_s=time.perf_counter() - t0, n=n, d=d, topics=k)

    t0 = time.perf_counter()
    x = jax.block_until_ready(
        shard_rows(mesh, axes, tfidf.tfidf(shard_rows(mesh, axes, corpus.counts)))
    )
    w = shard_rows(mesh, axes, np.ones((n,), np.float32))
    labels = jnp.asarray(corpus.labels)
    del corpus
    held = [dev.memory_stats()["bytes_in_use"] for dev in jax.devices()] if on_tpu else []
    say("tfidf_sharded", wall_s=time.perf_counter() - t0, bytes=x.nbytes,
        devices=len(x.sharding.device_set), bytes_in_use=held)
    check(len(x.sharding.device_set) == 4, "x does not span 4 devices")
    for b in held:
        check(0.9 * x.nbytes / 4 <= b <= 1.5 * x.nbytes / 4,
              f"a device holds {b} bytes, not about a quarter of x")

    key = jax.random.PRNGKey(SEED)
    s = buckshot_sample_size(n, k)
    rows = sample_rows_distributed(mesh, axes, x, w, s, key)
    rows1 = jax.device_put(rows, jax.devices()[0])

    t0 = time.perf_counter()
    e_dist = jax.block_until_ready(
        boruvka_mst_distributed(mesh, axes, l2_normalize(rows), **kernel_impl)
    )
    t_mst_d = time.perf_counter() - t0
    t0 = time.perf_counter()
    e_one = jax.block_until_ready(boruvka_mst(rows1, **kernel_impl))
    t_mst_1 = time.perf_counter() - t0

    def tree(e):
        """Undirected valid edges, sorted, with their weights."""
        v = np.asarray(e.valid)
        u, w_ = np.asarray(e.u)[v], np.asarray(e.v)[v]
        a, b = np.minimum(u, w_), np.maximum(u, w_)
        order = np.lexsort((b, a))
        return a[order], b[order], np.asarray(e.w)[v][order]

    ud, vd, wd = tree(e_dist)
    u1, v1, w1 = tree(e_one)
    same_tree = (
        len(ud) == len(u1) == s - 1
        and np.array_equal(ud, u1) and np.array_equal(vd, v1)
    )
    w_err = float(np.max(np.abs(wd - w1))) if same_tree else float("nan")
    say("mst", edges=len(ud), single_device_edges=len(u1), same_tree=same_tree,
        w_max_abs_diff=w_err, dist_wall_s=t_mst_d, single_wall_s=t_mst_1)
    check(same_tree, "distributed MST (u, v, valid) differs from single-device")
    check(w_err <= MST_W_ATOL, f"MST weights differ by {w_err}")

    t0 = time.perf_counter()
    res_d = buckshot_distributed(
        mesh, axes, x, w, k, key, sample_size=s, kmeans_iters=2,
        hac="boruvka", sample_rows=rows, **kernel_impl,
    )
    jax.block_until_ready(res_d.assignment)
    t_dist = time.perf_counter() - t0

    x1 = jax.device_put(x, jax.devices()[0])
    t0 = time.perf_counter()
    _, init = phase1_from_sample(rows1, k, hac="boruvka", **kernel_impl)
    res_1 = kmeans_fit(x1, init, k, max_iters=2, tol=0.0, **kernel_impl)
    jax.block_until_ready(res_1.assignment)
    t_one = time.perf_counter() - t0

    got = np.asarray(res_d.assignment)[:n]
    want = np.asarray(res_1.assignment)
    _, gap = near_tie_gap(np.asarray(x1), res_1.centers)
    flips = agree_up_to_ties(got, want, gap)
    for name, res, t in (("buckshot_distributed", res_d, t_dist),
                         ("buckshot_single_device", res_1, t_one)):
        pur = float(metrics.purity(jnp.asarray(res.assignment)[:n], labels, k, k))
        say(name, wall_s=t, rss=float(res.rss), purity=pur)
    say("distributed_vs_single", rows_assigned_differently=flips,
        rss_rel_err=abs(float(res_d.rss) - float(res_1.rss)) / float(res_1.rss))
    check(not ops.pallas_degraded(), "a Pallas kernel degraded to XLA")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny size on the CPU, kernels in interpret mode")
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if args.chips == 4:
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

    import jax

    from repro.common import use_compile_cache  # fails outside a checkout

    devices = jax.devices()
    on_tpu = devices[0].platform == "tpu"
    if not on_tpu and not args.rehearse:
        print(f"no TPU: JAX found {devices[0].platform} devices only",
              file=sys.stderr)
        return 2
    use_compile_cache()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    say("device", **device)

    cfg = TINY if args.rehearse else FULL
    kernel_impl = {"impl": "pallas_interpret"} if args.rehearse else {}
    t0 = time.perf_counter()
    try:
        (four_chips if args.chips == 4 else one_chip)(cfg, kernel_impl, on_tpu)
    except CheckFailed as e:
        print(f"CHECK FAILED: {e}", file=sys.stderr)
        return 1
    say("total", wall_s=time.perf_counter() - t0)
    if args.rehearse:
        print(json.dumps({"ok": False, "rehearsal": True, "device": device}))
        return 0
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
