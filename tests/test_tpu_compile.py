"""Compile the main-path Pallas kernels for a described TPU v5e chip.

Nothing here runs on a TPU: the TPU compiler, installed alongside jaxlib,
compiles each kernel for a v5e that is described (``topologies``) and not
attached. That catches what interpret mode cannot — tiles the Mosaic lowering
refuses, and kernels that need more scoped VMEM than the chip grants — at the
widths the drivers use. Each test asserts the compiled program really holds
the kernel (``tpu_custom_call``), so a silent fall back to XLA fails too.

The topology is described inside a module-scoped fixture, never at import
time: only one process at a time may load the TPU library, and a test worker
that describes it while collecting would change what the other workers see.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.kernels import ops


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the persistent
    # cache without that chip: keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def pod_mesh(topo):
    from repro.distrib.sharding import make_mesh

    return make_mesh((2, 2), ("pod", "data"), devices=topo.devices)


def _compiled_text(fn, *structs) -> str:
    return jax.jit(fn).lower(*structs).compile().as_text()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize(
    "k,dtype", [(1024, jnp.float32), (1024, jnp.bfloat16), (50, jnp.float32)]
)
def test_assign_stats_compiles(one_chip, k, dtype):
    n, d = 8192, 2048
    txt = _compiled_text(
        lambda x, c: ops.assign_stats(x, c, impl="pallas"),
        _spec(one_chip, (n, d), dtype),
        _spec(one_chip, (k, d), dtype),
    )
    assert "tpu_custom_call" in txt


def test_assign_stats_bounded_compiles(one_chip):
    n, d, k = 8192, 2048, 256

    def fn(x, c, bidx, lo, hi, drift, perm, group_of):
        index = ops.CenterIndex(perm, group_of)
        return ops.assign_stats_bounded(
            x, c, ops.Bounds(bidx, lo, hi), drift, index=index, impl="pallas"
        )

    txt = _compiled_text(
        fn,
        _spec(one_chip, (n, d)),
        _spec(one_chip, (k, d)),
        _spec(one_chip, (n,), jnp.int32),
        _spec(one_chip, (n,)),
        _spec(one_chip, (n,)),
        _spec(one_chip, (k,)),
        _spec(one_chip, (k,), jnp.int32),
        _spec(one_chip, (k,), jnp.int32),
    )
    assert "tpu_custom_call" in txt


def test_label_stats_compiles(one_chip):
    n, d, k = 8192, 2048, 1024
    txt = _compiled_text(
        lambda x, idx: ops.label_stats(x, idx, k, impl="pallas"),
        _spec(one_chip, (n, d)),
        _spec(one_chip, (n,), jnp.int32),
    )
    assert "tpu_custom_call" in txt


def test_sim_best_edge_compiles(one_chip):
    s, d = 4096, 2048
    txt = _compiled_text(
        lambda xs, lab: ops.sim_best_edge(xs, xs, lab, lab, impl="pallas"),
        _spec(one_chip, (s, d)),
        _spec(one_chip, (s,), jnp.int32),
    )
    assert "tpu_custom_call" in txt


def test_component_best_edge_compiles(one_chip):
    r, c = 4096, 512
    txt = _compiled_text(
        lambda w, j, rows, comp: ops.component_best_edge(
            w, j, rows, comp, c, impl="pallas"
        ),
        _spec(one_chip, (r,)),
        _spec(one_chip, (r,), jnp.int32),
        _spec(one_chip, (r,), jnp.int32),
        _spec(one_chip, (r,), jnp.int32),
    )
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("k", [50, 1024])
def test_assign_argmax_compiles(one_chip, k):
    n, d = 8192, 2048
    txt = _compiled_text(
        lambda x, c: ops.assign_argmax(x, c, impl="pallas"),
        _spec(one_chip, (n, d)),
        _spec(one_chip, (k, d)),
    )
    assert "tpu_custom_call" in txt


# Four chips: replicated work on a mesh must reach its kernels through
# shard_map, since XLA's partitioner refuses to partition a Pallas kernel.


@pytest.mark.parametrize("part", ["label_centers", "center_index"])
def test_replicated_job_compiles_on_pod_mesh(pod_mesh, part):
    from repro.distrib import cluster

    s, d, k = 3536, 2048, 50

    def rep(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=NamedSharding(pod_mesh, P()))

    if part == "label_centers":
        fn = functools.partial(cluster._label_centers, k=k, impl="pallas")
        args = (rep((s, d)), rep((s,), jnp.int32))
    else:
        fn = functools.partial(ops.build_center_index, impl="pallas")
        args = (rep((k, d)),)
    txt = cluster._replicated_job(pod_mesh, fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in txt


def test_kmeans_job_compiles_on_pod_mesh(pod_mesh):
    from repro.distrib import cluster
    from repro.distrib.engine import make_job

    n, d, k, axes = 32768, 2048, 50, ("pod", "data")

    def spec(shape, p):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=NamedSharding(pod_mesh, p))

    map_combine, kinds = cluster._assign_stats_map(k, "pallas")
    job = make_job(pod_mesh, axes, map_combine, kinds, name="kmeans_iter")
    txt = job.lower(
        {"x": spec((n, d), P(axes, None)), "w": spec((n,), P(axes))},
        {"centers": spec((k, d), P())},
    ).compile().as_text()
    assert "tpu_custom_call" in txt
    assert "all-reduce" in txt
