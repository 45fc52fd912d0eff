"""Distributed paths (8 simulated devices via subprocess — the main pytest
process must keep a single device so smoke tests and benches see 1 device).

The heavy equivalence content lives in repro/distrib/selftest.py; here we run
it, plus targeted in-subprocess checks for the MapReduce engine, distributed
tf-idf, Borůvka HAC, and elastic checkpoint restore.
"""

from __future__ import annotations

import os
import subprocess
import sys
import textwrap

import pytest

ENV = dict(
    os.environ,
    XLA_FLAGS="--xla_force_host_platform_device_count=8",
    PYTHONPATH="src",
    JAX_PLATFORMS="cpu",
)


def _run(code: str, timeout: int = 600) -> str:
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=timeout, env=ENV,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


def test_selftest_clustering_equivalence():
    """kmeans/bkc/buckshot distributed == single-device reference (8 shards)."""
    out = subprocess.run(
        [sys.executable, "-m", "repro.distrib.selftest"],
        capture_output=True, text=True, timeout=900, env=ENV,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    assert "SELFTEST OK" in out.stdout


def test_engine_reducers():
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.distrib.engine import make_job
    from repro.distrib.sharding import make_flat_mesh, shard_rows

    mesh = make_flat_mesh(8)
    x = jnp.arange(64, dtype=jnp.float32).reshape(64, 1)
    xs = shard_rows(mesh, ("data",), x)

    def mc(data, bcast):
        v = data["x"]
        return {"sum": jnp.sum(v), "min": jnp.min(v), "max": jnp.max(v),
                "rows": v * 2.0}

    job = make_job(mesh, ("data",), mc,
                   {"sum": "sum", "min": "min", "max": "max", "rows": "shard"})
    out = job({"x": xs}, {})
    assert float(out["sum"]) == float(x.sum()), out["sum"]
    assert float(out["min"]) == 0.0 and float(out["max"]) == 63.0
    np.testing.assert_array_equal(np.asarray(out["rows"]), np.asarray(x) * 2)
    print("ENGINE OK")
    """)


def test_distributed_tfidf_matches_local():
    _run("""
    import jax.numpy as jnp, numpy as np
    from repro.distrib.sharding import make_flat_mesh, pad_rows_to_multiple, shard_rows
    from repro.text import synth, tfidf

    mesh = make_flat_mesh(8)
    c = synth.make_corpus(203, vocab=64, n_topics=4, seed=2)  # non-divisible n
    local = np.asarray(tfidf.tfidf(jnp.asarray(c.counts)))

    counts, w = pad_rows_to_multiple(jnp.asarray(c.counts), 8)
    counts = shard_rows(mesh, ("data",), counts)
    w = shard_rows(mesh, ("data",), w)
    dist = np.asarray(tfidf.tfidf_distributed(mesh, ("data",), counts, w))[:203]
    np.testing.assert_allclose(local, dist, rtol=1e-5, atol=1e-6)
    print("TFIDF OK")
    """)


def test_distributed_boruvka_matches_prim():
    _run("""
    import numpy as np, jax.numpy as jnp
    from repro.common import l2_normalize
    from repro.core.hac import single_link_labels
    from repro.distrib.hac_parallel import single_link_labels_distributed
    from repro.distrib.sharding import make_flat_mesh

    mesh = make_flat_mesh(8)
    rng = np.random.default_rng(7)
    xs = l2_normalize(jnp.asarray(rng.normal(size=(320, 16)).astype(np.float32)))
    ref = np.asarray(single_link_labels(xs @ xs.T, 9))
    got = np.asarray(single_link_labels_distributed(mesh, ("data",), xs, 9))
    assert (ref == got).all()
    print("BORUVKA OK")
    """)


def test_distributed_boruvka_non_divisible_sample():
    """Paper-default s rarely divides the mesh: the replicated sample is
    padded to a shard multiple and the pad rows must not change the labels."""
    _run("""
    import numpy as np, jax.numpy as jnp
    from repro.common import l2_normalize
    from repro.core.hac import single_link_labels
    from repro.distrib.hac_parallel import single_link_labels_distributed
    from repro.distrib.sharding import make_flat_mesh

    rng = np.random.default_rng(11)
    for n_dev, s, k in ((8, 321, 7), (3, 1000, 10), (8, 9, 3)):
        mesh = make_flat_mesh(n_dev)
        assert s % n_dev != 0
        xs = l2_normalize(jnp.asarray(
            rng.normal(size=(s, 16)).astype(np.float32)))
        ref = np.asarray(single_link_labels(xs @ xs.T, k))
        got = np.asarray(
            single_link_labels_distributed(mesh, ("data",), xs, k))
        assert (ref == got).all(), (n_dev, s, k)
    print("BORUVKA PAD OK")
    """)


def test_distributed_boruvka_prewarm_parity():
    """The async round-shape pre-warm (AOT executables + device_put placement)
    must be a pure scheduling change: edges bit-identical to the synchronous
    compile path, including a padded (non-divisible) sample, and the
    cancelled-pending teardown must not wedge or abort the process."""
    _run("""
    import numpy as np, jax, jax.numpy as jnp
    from repro.common import l2_normalize
    from repro.distrib.hac_parallel import boruvka_mst_distributed
    from repro.distrib.sharding import make_flat_mesh

    rng = np.random.default_rng(7)
    mesh = make_flat_mesh(8)
    for s in (256, 321):
        xs = l2_normalize(jnp.asarray(
            rng.normal(size=(s, 16)).astype(np.float32)))
        warm = boruvka_mst_distributed(mesh, ("data",), xs, prewarm=True)
        sync = boruvka_mst_distributed(mesh, ("data",), xs, prewarm=False)
        for a, b in zip(warm, sync):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # second warmed call hits the executable cache
        again = boruvka_mst_distributed(mesh, ("data",), xs, prewarm=True)
        np.testing.assert_array_equal(np.asarray(again.u), np.asarray(warm.u))
    print("PREWARM PARITY OK")
    """)


def test_distributed_boruvka_pre_reduce_4dev_matches_oracles():
    """Shuffle-light path: per-shard per-component pre-reduce + the engine's
    'component' fold must match BOTH the single-device Borůvka and the Prim
    oracle on a forced 4-device mesh — including a non-shard-multiple s and
    the legacy per-row gather path it replaces."""
    env4 = dict(ENV, XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent("""
    import numpy as np, jax.numpy as jnp
    from repro.common import l2_normalize
    from repro.core.hac import single_link_labels, single_link_labels_boruvka
    from repro.distrib.hac_parallel import single_link_labels_distributed
    from repro.distrib.sharding import make_flat_mesh

    mesh = make_flat_mesh(4)
    rng = np.random.default_rng(7)
    for s, k in ((320, 9), (322, 7), (9, 3)):  # 322, 9: non-shard-multiple
        xs = l2_normalize(jnp.asarray(
            rng.normal(size=(s, 16)).astype(np.float32)))
        prim = np.asarray(single_link_labels(xs @ xs.T, k))
        single = np.asarray(single_link_labels_boruvka(xs, k))
        pre = np.asarray(
            single_link_labels_distributed(mesh, ("data",), xs, k))
        legacy = np.asarray(single_link_labels_distributed(
            mesh, ("data",), xs, k, pre_reduce=False))
        assert (prim == single).all(), (s, k, "single-device")
        assert (prim == pre).all(), (s, k, "pre-reduce")
        assert (prim == legacy).all(), (s, k, "row-gather")
    print("BORUVKA PRE-REDUCE OK")
        """)],
        capture_output=True, text=True, timeout=600, env=env4,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    assert "BORUVKA PRE-REDUCE OK" in out.stdout


def test_engine_component_reduce_lexicographic():
    """The 'component' reduce kind must pick the global (w desc, row asc)
    winner per segment across shards, with empty-segment identities losing."""
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.distrib.engine import make_job
    from repro.distrib.sharding import make_flat_mesh, shard_rows
    from repro.kernels import ops, ref

    mesh = make_flat_mesh(8)
    rng = np.random.default_rng(3)
    r, c = 64, 11
    w = jnp.asarray(rng.normal(size=r).astype(np.float32))
    w = w.at[::6].set(float(jnp.finfo(jnp.float32).min))
    w = w.at[17].set(w[50])  # cross-shard duplicate weight: row tie-break
    col = jnp.asarray(rng.integers(-1, 40, size=r).astype(np.int32))
    rows = jnp.arange(r, dtype=jnp.int32)
    comp = jnp.asarray(rng.integers(0, c + 1, size=r).astype(np.int32))

    def mc(data, bcast):
        bw, brow, bcol = ops.component_best_edge(
            data["w"], data["col"], data["rows"], data["comp"], c, impl="xla")
        return {"best": {"w": bw, "row": brow, "col": bcol}}

    job = make_job(mesh, ("data",), mc, {"best": "component"})
    sh = lambda v: shard_rows(mesh, ("data",), v)
    out = job({"w": sh(w), "col": sh(col), "rows": sh(rows),
               "comp": sh(comp)}, {})
    want = ref.component_best_edge(w, col, rows, comp, c)
    np.testing.assert_array_equal(np.asarray(out["best"]["w"]), np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(out["best"]["row"]), np.asarray(want[1]))
    np.testing.assert_array_equal(np.asarray(out["best"]["col"]), np.asarray(want[2]))
    print("COMPONENT REDUCE OK")
    """)


def test_compressed_psum_close_to_exact():
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.distrib.compression import compressed_psum
    from repro.distrib.sharding import make_flat_mesh, shard_rows

    mesh = make_flat_mesh(8)
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 32))
    xs = shard_rows(mesh, ("data",), x)

    def f(v):
        return jax.lax.psum(v, ("data",)), compressed_psum(v, ("data",))

    exact, approx = jax.jit(jax.shard_map(
        f, mesh=mesh, in_specs=P("data"), out_specs=(P(), P()), check_vma=False
    ))(xs)
    rel = float(jnp.max(jnp.abs(exact - approx)) / jnp.max(jnp.abs(exact)))
    assert rel < 0.02, rel  # int8 wire: ~1/127 relative error budget
    print("COMPRESS OK", rel)
    """)


def test_elastic_checkpoint_reshard():
    """Save params sharded one way, restore onto a different mesh layout."""
    _run("""
    import jax, jax.numpy as jnp, numpy as np, tempfile
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.distrib.sharding import make_flat_mesh
    from repro.train import checkpoint as ck

    d = tempfile.mkdtemp()
    mesh8 = make_flat_mesh(8)
    tree = {"w": jax.device_put(
        jnp.arange(64.0).reshape(8, 8), NamedSharding(mesh8, P("data", None)))}
    ck.save(d, 5, tree)

    mesh4 = make_flat_mesh(4)  # 'cluster shrank': restore onto 4 devices
    shardings = {"w": NamedSharding(mesh4, P("data", None))}
    restored = ck.restore(d, 5, tree, shardings)
    np.testing.assert_array_equal(
        np.asarray(restored["w"]), np.arange(64.0).reshape(8, 8))
    assert restored["w"].sharding.mesh.shape["data"] == 4
    print("ELASTIC OK")
    """)


def test_multipod_mesh_axes():
    """make_production_mesh constructs both meshes (needs 512 devices)."""
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent("""
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
            from repro.launch.mesh import make_production_mesh, policy_for
            m1 = make_production_mesh()
            assert dict(m1.shape) == {"data": 16, "model": 16}, m1.shape
            m2 = make_production_mesh(multi_pod=True)
            assert dict(m2.shape) == {"pod": 2, "data": 16, "model": 16}
            p = policy_for(m2)
            assert p.dp == ("pod", "data")
            print("MESH OK")
        """)],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu"),
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"


def test_debug_mesh_train_step_compiles():
    """Reduced-config train step lowers+compiles on a 2x2 debug mesh with the
    same sharding machinery as the production dry-run."""
    _run("""
    import jax, jax.numpy as jnp
    from repro.configs import get_config
    from repro.launch.mesh import make_debug_mesh, policy_for
    from repro.models.registry import get_model
    from repro.models.common import abstract
    from repro.train.optimizer import AdamWConfig, abstract_opt_state
    from repro.train.step import make_train_step
    from repro.models.registry import batch_specs

    cfg = get_config("qwen2-1.5b", reduced=True)
    mesh = make_debug_mesh((2, 2))
    policy = policy_for(mesh)
    model = get_model(cfg)
    params = model.abstract_params(policy, jnp.float32)
    opt = abstract_opt_state(model.recs, policy)
    batch = batch_specs(cfg, 8, 64, policy)
    with mesh:
        fn = make_train_step(cfg, AdamWConfig(), policy)
        compiled = jax.jit(fn).lower(params, opt, batch).compile()
    assert compiled.cost_analysis() is not None
    print("DEBUG MESH OK")
    """, timeout=900)
