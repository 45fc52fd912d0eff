"""End-to-end distributed clustering driver — the paper's main experiment.

    PYTHONPATH=src python examples/cluster_20ng.py --devices 8 --n 20000 --k 50

Simulates a multi-node cluster with host devices (the same shard_map code
runs unchanged on a real TPU mesh), prepares the corpus with DISTRIBUTED
tf-idf, then runs parallel K-Means, BKC (3 MapReduce jobs) and Buckshot
(distributed sample -> HAC -> 2 K-Means iterations), reporting the paper's
metrics (time, RSS) plus purity/NMI against ground truth.
"""

import argparse
import os
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--n", type=int, default=20_000)
    ap.add_argument("--k", type=int, default=50)
    ap.add_argument("--big-k", type=int, default=250)
    ap.add_argument("--vocab", type=int, default=2048)
    ap.add_argument("--hac", choices=["replicated", "boruvka"], default="replicated")
    args = ap.parse_args()
    # NOTE: timings include one-time XLA job compilation (the analogue of
    # Hadoop's per-job setup). Warm jobs are timed on the chip by
    # benchmarks/chip/run.py. --hac boruvka demonstrates the sharded
    # PARABLE-style HAC (log(s) extra job rounds; wins only at much larger
    # sample sizes).

    os.environ.setdefault(
        "XLA_FLAGS", f"--xla_force_host_platform_device_count={args.devices}"
    )

    import jax
    import jax.numpy as jnp

    from repro.common import use_compile_cache
    from repro.core import metrics
    from repro.core.sampling import buckshot_sample_size
    from repro.distrib import cluster as dc
    from repro.distrib.sharding import make_flat_mesh
    from repro.text.pipeline import prepare_synthetic

    use_compile_cache()
    mesh = make_flat_mesh(args.devices)
    axes = ("data",)
    print(f"mesh: {args.devices} devices; corpus: n={args.n}, vocab={args.vocab}")

    prep = prepare_synthetic(
        mesh, axes, n_docs=args.n, vocab=args.vocab, n_topics=20, seed=20
    )
    labels = jnp.asarray(prep.labels)
    key = jax.random.PRNGKey(0)
    k = args.k

    def quality(assignment):
        a = assignment[: prep.n]
        return (
            float(metrics.purity(a, labels, k, 20)),
            float(metrics.nmi(a, labels, k, 20)),
        )

    # ---- parallel K-Means (PKMeans baseline)
    from repro.common import l2_normalize

    init = l2_normalize(prep.x[jax.random.choice(key, prep.n, (k,), replace=False)])
    t0 = time.perf_counter()
    km = dc.kmeans_distributed(mesh, axes, prep.x, prep.w, init, k, max_iters=8)
    jax.block_until_ready(km.centers)
    t_km = time.perf_counter() - t0
    pur, nmi = quality(km.assignment)
    print(f"K-Means   {t_km*1e3:9.1f} ms  RSS={float(km.rss):9.2f} "
          f"iters={km.iterations}  purity={pur:.3f} nmi={nmi:.3f}")

    # ---- BKC (the paper's three MapReduce jobs)
    ckey = jax.random.fold_in(key, 1)
    cinit = l2_normalize(
        prep.x[jax.random.choice(ckey, prep.n, (args.big_k,), replace=False)]
    )
    t0 = time.perf_counter()
    bk = dc.bkc_distributed(mesh, axes, prep.x, prep.w, cinit, args.big_k, k)
    jax.block_until_ready(bk.centers)
    t_bk = time.perf_counter() - t0
    pur, nmi = quality(bk.assignment)
    print(f"BKC       {t_bk*1e3:9.1f} ms  RSS={float(bk.rss):9.2f} "
          f"({100*(1-t_bk/t_km):5.1f}% faster)  purity={pur:.3f} nmi={nmi:.3f}")

    # ---- Buckshot (distributed sample -> single-link HAC -> 2 iterations)
    s = buckshot_sample_size(args.n, k)
    s -= s % args.devices  # shard-aligned sample
    t0 = time.perf_counter()
    bs = dc.buckshot_distributed(
        mesh, axes, prep.x, prep.w, k, jax.random.fold_in(key, 2),
        sample_size=s, kmeans_iters=2, hac=args.hac,
    )
    jax.block_until_ready(bs.centers)
    t_bs = time.perf_counter() - t0
    pur, nmi = quality(bs.assignment)
    print(f"Buckshot  {t_bs*1e3:9.1f} ms  RSS={float(bs.rss):9.2f} "
          f"({100*(1-t_bs/t_km):5.1f}% faster, s={s}, hac={args.hac})  "
          f"purity={pur:.3f} nmi={nmi:.3f}")


if __name__ == "__main__":
    sys.exit(main())
