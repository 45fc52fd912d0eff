"""HLO cost parser: the roofline's measurement layer must be trustworthy.

Validates against constructs with known analytic costs: plain matmuls, scans
(while loops with known trip counts), nested scans, slicing patterns, and
collectives under shard_map (subprocess, 8 devices).
"""

from __future__ import annotations

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.hlo_costs import parse_hlo_costs


def _costs(fn, *args):
    c = jax.jit(fn).lower(*args).compile()
    return parse_hlo_costs(c.as_text()), c


def test_single_matmul_flops():
    x = jnp.zeros((256, 256), jnp.float32)
    r, c = _costs(lambda a: a @ a, x)
    want = 2 * 256**3
    assert abs(r["flops"] - want) / want < 0.01
    # parser should agree with XLA's own analysis when no loops are involved
    xla = c.cost_analysis().get("flops", 0)
    assert abs(r["flops"] - xla) / want < 0.01


def test_scan_flops_scaled_by_trip_count():
    def f(x):
        def body(carry, _):
            return carry @ carry, None
        out, _ = jax.lax.scan(body, x, None, length=11)
        return out

    x = jnp.zeros((128, 128), jnp.float32)
    r, c = _costs(f, x)
    want = 11 * 2 * 128**3
    assert abs(r["flops"] - want) / want < 0.02
    # and the raw XLA number is ~11x smaller (the bug this parser fixes)
    xla = c.cost_analysis().get("flops", 0)
    assert xla < r["flops"] / 5


def test_nested_scan_flops_multiply():
    def g(x):
        def outer(c, _):
            def inner(d, _):
                return d @ d, None
            d, _ = jax.lax.scan(inner, c, None, length=3)
            return d, None
        out, _ = jax.lax.scan(outer, x, None, length=5)
        return out

    x = jnp.zeros((64, 64), jnp.float32)
    r, _ = _costs(g, x)
    want = 15 * 2 * 64**3
    assert abs(r["flops"] - want) / want < 0.05


def test_scan_slice_bytes_not_full_operand():
    """Scanning over stacked weights must charge one layer per step, not all."""
    w = jnp.zeros((40, 64, 64), jnp.float32)  # 40 layers
    x = jnp.zeros((8, 64), jnp.float32)

    def f(w, x):
        def body(h, wi):
            return h @ wi, None
        out, _ = jax.lax.scan(body, x, w)
        return out

    r, _ = _costs(f, w, x)
    # traffic should be ~ 40 * (one layer 16KiB + activations) + constants,
    # NOT 40 * full 655KiB stack
    assert r["bytes"] < 40 * (64 * 64 * 4) * 6, r["bytes"]


def test_elementwise_flops_counted():
    x = jnp.zeros((1000,), jnp.float32)
    r, _ = _costs(lambda a: jnp.exp(a) + a * 2.0, x)
    assert 1000 <= r["flops"] <= 10_000


def test_no_collectives_single_device():
    x = jnp.zeros((64, 64), jnp.float32)
    r, _ = _costs(lambda a: a @ a, x)
    assert r["collectives"]["total"] == 0


def test_collectives_in_scan_scaled():
    """psum inside a scan must be multiplied by the trip count (subprocess
    with 8 devices so a real all-reduce is emitted)."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.launch.hlo_costs import parse_hlo_costs

        mesh = jax.make_mesh((8,), ("d",))
        def f(x):
            def body(c, _):
                return jax.lax.psum(c, "d"), None
            out, _ = jax.lax.scan(body, x, None, length=5)
            return out
        g = jax.shard_map(f, mesh=mesh, in_specs=P(None, None),
                      out_specs=P(None, None), check_vma=False)
        x = jnp.zeros((64, 256), jnp.float32)
        c = jax.jit(g).lower(x).compile()
        r = parse_hlo_costs(c.as_text())
        want = 5 * 64 * 256 * 4
        ar = r["collectives"]["all-reduce"]
        assert abs(ar - want) / want < 0.01, (ar, want)
        assert r["collectives"]["n_ops"] == 5
        print("COLL OK")
    """)
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=600,
        env=dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu"),
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"


def test_shape_parsing_tuples_and_scalars():
    from repro.launch.hlo_costs import _shape_numel_bytes

    assert _shape_numel_bytes("f32[128,128]{1,0}") == (128 * 128, 128 * 128 * 4)
    n, b = _shape_numel_bytes("(s32[], f32[8]{0})")
    assert n == 9 and b == 4 + 32
    assert _shape_numel_bytes("bf16[2,3]{1,0}")[1] == 12
    assert _shape_numel_bytes("token[]") == (0, 0)
    assert _shape_numel_bytes("f32[]")[0] == 1


def test_device_peaks_known_and_unknown():
    from repro.launch.hlo_costs import device_peaks

    assert device_peaks("TPU v5 lite")["flops_bf16"] == 197e12
    with pytest.raises(ValueError, match="no published peaks"):
        device_peaks("TPU v99")


def test_compile_cache_dir(monkeypatch):
    """The entry points' cache: JAX_COMPILATION_CACHE_DIR wins untouched,
    else the fixed <checkout>/.jax_cache."""
    from repro.common import CHECKOUT, use_compile_cache

    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        jax.config.update("jax_compilation_cache_dir", None)
        assert use_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir is None
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        want = str(CHECKOUT / ".jax_cache")
        assert use_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
