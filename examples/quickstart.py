"""Quickstart: cluster a synthetic 20-newsgroups-like corpus three ways,
OUT-OF-CORE — the dense (n, d) matrix never exists.

    PYTHONPATH=src python examples/quickstart.py

Generates 4000 documents from a 12-topic model as a chunked stream
(4 chunks of 1000), weights them with streaming two-pass tf-idf, and runs
the paper's three algorithms through their streaming entry points (K-Means
baseline, BKC, Buckshot), printing time / RSS / purity for each. Chunks
prefetch on a background thread while the device folds (DESIGN.md §11;
``REPRO_STREAM_PREFETCH=0`` disables). Peak residency is O(chunk·d), so the
same script runs at n = 1M by changing two numbers. ~30s on CPU.

With more than one visible device the same stream also runs the DISTRIBUTED
streaming Buckshot (chunks sharded on arrival, sample drawn by the sharded
one-pass reservoir, one collective per pass):

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        PYTHONPATH=src python examples/quickstart.py
"""

import time

import jax
import jax.numpy as jnp

from repro.common import use_compile_cache
from repro.core import bkc_stream, buckshot_stream, kmeans_stream, metrics
from repro.text import pipeline


def main() -> None:
    use_compile_cache()
    n, k, chunk = 4000, 12, 1000
    print(f"streaming corpus: n={n}, topics={k}, chunks of {chunk}")
    prep = pipeline.prepare_synthetic_stream(
        n_docs=n, vocab=2048, n_topics=k, seed=0, chunk=chunk
    )
    xs, labels = prep.x, jnp.asarray(prep.labels)
    key = jax.random.PRNGKey(0)

    def report(name, fn):
        fn()  # compile
        t0 = time.perf_counter()
        res = fn()
        jax.block_until_ready(res.centers if hasattr(res, "centers") else res.kmeans.centers)
        dt = time.perf_counter() - t0
        assignment = res.assignment if hasattr(res, "assignment") else res.kmeans.assignment
        rss = res.rss if hasattr(res, "rss") else res.kmeans.rss
        pur = metrics.purity(jnp.asarray(assignment), labels, k, k)
        print(f"{name:22s} {dt*1e3:8.1f} ms   RSS={float(rss):8.2f}   "
              f"purity={float(pur):.3f}")
        return dt, float(rss)

    t_km, rss_km = report("K-Means (8 iters)",
                          lambda: kmeans_stream(xs, k, key, max_iters=8))
    t_bk, rss_bk = report("BKC (BigK=64)", lambda: bkc_stream(xs, 64, k, key))
    t_bs, rss_bs = report("Buckshot (2 iters)",
                          lambda: buckshot_stream(xs, k, key, kmeans_iters=2))

    print(f"\nBKC:      {100*(1-t_bk/t_km):5.1f}% faster, "
          f"RSS loss {100*(rss_bk/rss_km-1):+5.2f}%")
    print(f"Buckshot: {100*(1-t_bs/t_km):5.1f}% faster, "
          f"RSS loss {100*(rss_bs/rss_km-1):+5.2f}%")

    if jax.device_count() > 1 and chunk % jax.device_count() == 0:
        from repro.core.sampling import buckshot_sample_size
        from repro.distrib.cluster import buckshot_distributed_stream
        from repro.distrib.sharding import make_flat_mesh, make_pod_mesh

        nd = jax.device_count()
        if nd >= 4 and nd % 2 == 0:
            # pod mesh: collectives resolve intra-pod before anything
            # crosses pods, and the sharded candidate sweep's ring rotates
            # per tier (DESIGN.md §15-§16)
            mesh, axes, layout = (
                make_pod_mesh(2, nd // 2), ("pod", "data"), f"pod 2x{nd // 2}"
            )
        else:
            mesh, axes, layout = make_flat_mesh(), ("data",), f"flat {nd}"
        res = buckshot_distributed_stream(
            mesh, axes, xs, k, key,
            sample_size=buckshot_sample_size(n, k), kmeans_iters=2,
        )
        pur = metrics.purity(jnp.asarray(res.assignment), labels, k, k)
        print(f"\ndistributed streaming Buckshot ({layout} mesh): "
              f"RSS={float(res.rss):8.2f}   purity={float(pur):.3f}")
    else:
        print("\n(more than one device — a count dividing the chunk size — "
              "unlocks the distributed streaming Buckshot; see the module "
              "docstring)")


if __name__ == "__main__":
    main()
