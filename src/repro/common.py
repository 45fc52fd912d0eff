"""Shared small utilities: normalization, dtype policy, pytree helpers."""

from __future__ import annotations

import functools
import os
import pathlib
from typing import Any

import jax
import jax.numpy as jnp

EPS = 1e-12

CHECKOUT = pathlib.Path(__file__).resolve().parents[2]


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for an entry point, and
    return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it and nothing else
    is set. Otherwise the cache is the fixed ``<checkout>/.jax_cache``: the
    path is part of what a cached program is found by, so it is never built
    from a temporary name, a process id or the time."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def l2_normalize(x: jax.Array, axis: int = -1, eps: float = EPS) -> jax.Array:
    """L2-normalize along `axis`; zero vectors stay zero."""
    norm = jnp.sqrt(jnp.sum(x * x, axis=axis, keepdims=True))
    return x / jnp.maximum(norm, eps)


def cosine_sim_matrix(a: jax.Array, b: jax.Array) -> jax.Array:
    """(n,d) x (m,d) -> (n,m) cosine similarity (inputs need not be normalized)."""
    return l2_normalize(a) @ l2_normalize(b).T


def count_params(tree: Any) -> int:
    return sum(int(x.size) for x in jax.tree_util.tree_leaves(tree))


def tree_bytes(tree: Any) -> int:
    return sum(int(x.size) * x.dtype.itemsize for x in jax.tree_util.tree_leaves(tree))


def pretty_bytes(n: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024.0:
            return f"{n:.2f}{unit}"
        n /= 1024.0
    return f"{n:.2f}PiB"


@functools.partial(jax.jit, static_argnames=("k",))
def segment_sum(data: jax.Array, segment_ids: jax.Array, k: int) -> jax.Array:
    """Sum rows of `data` into `k` bins given by `segment_ids` (XLA scatter-add)."""
    return jax.ops.segment_sum(data, segment_ids, num_segments=k)


@functools.partial(jax.jit, static_argnames=("k",))
def segment_min(data: jax.Array, segment_ids: jax.Array, k: int) -> jax.Array:
    return jax.ops.segment_min(data, segment_ids, num_segments=k)


@functools.partial(jax.jit, static_argnames=("k",))
def bincount(segment_ids: jax.Array, k: int) -> jax.Array:
    return jax.ops.segment_sum(
        jnp.ones_like(segment_ids, dtype=jnp.int32), segment_ids, num_segments=k
    )
