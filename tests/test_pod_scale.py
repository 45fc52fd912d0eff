"""Pod-scale phase 1 (8 simulated devices via subprocess): two-tier
'component' collectives, the sharded component-graph merge, the ring-sharded
candidate sweep (overlap on/off), its SIGKILL resume parity, the
owner-scatter reservoir finalize, and the tier/overlap cache identity.

Everything here is a bit-exactness claim: the tiering/sharding changes where
bytes flow and where state lives, never the answer (DESIGN.md §15). Meshes
deliberately include non-power-of-two device counts (6 of the 8) and
non-shard-multiple s, so the pad lanes (label -1 / weight f32.min) are
exercised on every path.
"""

from __future__ import annotations

import os
import subprocess
import sys
import textwrap

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


def test_tiered_component_reduce_matches_flat_and_oracle():
    """The 'component' reduce run per mesh axis (intra-pod then cross-pod)
    equals both the flat single-axis reduce and a numpy lexicographic oracle,
    bit for bit — on pod (2, 4), flat (8,), and non-pow-2 flat (6,)."""
    _run("""
    import numpy as np, jax, jax.numpy as jnp
    from repro.distrib.engine import make_job
    from repro.distrib.sharding import make_flat_mesh, make_pod_mesh
    from repro.kernels.ref import BIG_I

    NEG = float(jnp.finfo(jnp.float32).min)
    c = 37
    rng = np.random.default_rng(0)

    def shard_cands(P, seed):
        # per-shard per-segment winner candidates, some segments empty,
        # deliberate weight ties (quantized weights) broken by row id
        r = np.random.default_rng(seed)
        w = np.round(r.random((P, c)).astype(np.float32), 1)
        row = r.permutation(P * c).reshape(P, c).astype(np.int32)
        col = r.integers(0, 1000, (P, c)).astype(np.int32)
        empty = r.random((P, c)) < 0.3
        w[empty] = NEG
        row[empty] = BIG_I
        col[empty] = -1
        return w, row, col

    def oracle(w, row, col):
        P = w.shape[0]
        bw = np.full(c, NEG, np.float32)
        br = np.full(c, BIG_I, np.int32)
        bc = np.full(c, -1, np.int32)
        for p in range(P):
            take = (w[p] > bw) | ((w[p] == bw) & (row[p] < br))
            bw = np.where(take, w[p], bw)
            br = np.where(take, row[p], br)
            bc = np.where(take, col[p], bc)
        return bw, br, bc

    def run(mesh, axes, w, row, col):
        job = make_job(mesh, axes, lambda d, b: d, "component", name="t")
        out = job({"w": jnp.asarray(w.reshape(-1, c)[:, None, :]),
                   "row": jnp.asarray(row.reshape(-1, c)[:, None, :]),
                   "col": jnp.asarray(col.reshape(-1, c)[:, None, :])}, {})
        # each shard held one (1, c) slice; reduce output is replicated
        return tuple(
            np.asarray(v)[0, 0] for v in (out["w"], out["row"], out["col"]))

    for P, builds in ((8, (("flat", make_flat_mesh(8), ("data",)),
                           ("pod24", make_pod_mesh(2, 4), ("pod", "data")),
                           ("pod42", make_pod_mesh(4, 2), ("pod", "data")))),
                      (6, (("flat6", make_flat_mesh(6), ("data",)),
                           ("pod32", make_pod_mesh(3, 2), ("pod", "data"))))):
        w, row, col = shard_cands(P, 100 + P)
        want = oracle(w, row, col)
        for name, mesh, axes in builds:
            got = run(mesh, axes, w, row, col)
            for g, o in zip(got, want):
                np.testing.assert_array_equal(g, o, err_msg=name)
    print("TIERED REDUCE OK")
    """)


def test_component_fold_kind_matches_oneshot():
    """Fold-mode 'component' (per-shard running winner carry, one tiered
    finalize) over a chunked stream == the one-shot job handed the
    concatenation, on both flat and pod meshes."""
    _run("""
    import numpy as np, jax, jax.numpy as jnp
    from repro.distrib.engine import make_fold_job, make_job
    from repro.distrib.sharding import make_flat_mesh, make_pod_mesh

    c, chunks = 29, 4
    rng = np.random.default_rng(3)
    # row ids globally unique ACROSS chunks — the totality of the
    # (w desc, row asc) order is what makes the fold a monoid
    all_rows = rng.permutation(10_000)[: chunks * 8 * c].astype(np.int32)

    def chunk(i):
        r = np.random.default_rng(50 + i)
        return {
            "w": jnp.asarray(np.round(
                r.random((8, 1, c)).astype(np.float32), 1)),
            "row": jnp.asarray(
                all_rows[i * 8 * c:(i + 1) * 8 * c].reshape(8, 1, c)),
            "col": jnp.asarray(
                r.integers(0, 99, (8, 1, c)).astype(np.int32)),
        }

    data = [chunk(i) for i in range(chunks)]
    for mesh, axes in ((make_flat_mesh(8), ("data",)),
                       (make_pod_mesh(2, 4), ("pod", "data"))):
        fold = make_fold_job(mesh, axes, lambda d, b: d, "component")
        carry = None
        for ch in data:
            carry, _ = fold.step(carry, ch, {})
        got = fold.finalize(carry)

        # numpy oracle: lexicographic (w desc, row asc) best per segment
        # over every (chunk, shard) candidate set
        bw = np.full(c, -np.inf, np.float32)
        br = np.full(c, np.iinfo(np.int32).max, np.int32)
        bc = np.full(c, -1, np.int32)
        for ch in data:
            for p in range(8):
                w = np.asarray(ch["w"])[p, 0]
                row = np.asarray(ch["row"])[p, 0]
                col = np.asarray(ch["col"])[p, 0]
                take = (w > bw) | ((w == bw) & (row < br))
                bw = np.where(take, w, bw)
                br = np.where(take, row, br)
                bc = np.where(take, col, bc)
        for k, want in (("w", bw), ("row", br), ("col", bc)):
            np.testing.assert_array_equal(np.asarray(got[k])[0, 0], want)
    print("COMPONENT FOLD OK")
    """)


def test_sharded_merge_edges_bit_identical():
    """merge='comp' (sharded O(s/P) label state, c-sized relabel broadcast)
    produces the same MSTEdges as merge='point' (replicated labels: u, v,
    valid exact, w to 2 ulp of 1.0) and
    oracle-matching Prim cuts — at s=321 and s=9 (non-shard-multiple: pad
    label -1 must not propagate into any component), on flat (8,),
    pod (2, 4), and non-pow-2 flat (6,) meshes."""
    _run("""
    import numpy as np, jax, jax.numpy as jnp
    from repro.common import l2_normalize
    from repro.core.hac import cut_mst_edges, single_link_labels
    from repro.distrib.hac_parallel import boruvka_mst_distributed
    from repro.distrib.sharding import make_flat_mesh, make_pod_mesh

    W_ATOL = 2 * np.finfo(np.float32).eps
    meshes = ((make_flat_mesh(8), ("data",)),
              (make_pod_mesh(2, 4), ("pod", "data")),
              (make_flat_mesh(6), ("data",)))
    for s in (321, 9):
        rng = np.random.default_rng(s)
        xs = l2_normalize(jnp.asarray(
            rng.normal(size=(s, 12)).astype(np.float32)))
        k = 4
        want_labels = np.asarray(single_link_labels(xs @ xs.T, k))
        for mesh, axes in meshes:
            ep = boruvka_mst_distributed(
                mesh, axes, xs, merge="point", compact=False)
            ec = boruvka_mst_distributed(
                mesh, axes, xs, merge="comp", compact=False)
            # the two merges sweep through different block shapes, which XLA
            # may round differently: the tree (u, v, valid) is exact, and w
            # agrees to 2 ulp of 1.0, the scale of a unit-vector dot
            for a, b in ((ec.u, ep.u), (ec.v, ep.v), (ec.valid, ep.valid)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            np.testing.assert_allclose(
                np.asarray(ec.w), np.asarray(ep.w), rtol=0, atol=W_ATOL)
            assert int(np.sum(np.asarray(ec.valid))) == s - 1
            got = np.asarray(cut_mst_edges(ec, s, k))
            # labels are canonical min-member ids -> comparable directly
            np.testing.assert_array_equal(got, want_labels)
            assert got.min() >= 0  # no pad label -1 leaked into a cut

            # compact mode: same valid edge SET (slot layout differs)
            ek = boruvka_mst_distributed(
                mesh, axes, xs, merge="comp", compact=True)
            def triples(e):
                v = np.asarray(e.valid)
                t = np.stack([np.asarray(e.u)[v], np.asarray(e.v)[v],
                              np.asarray(e.w)[v].view(np.int32)])
                return t[:, np.lexsort(t)]
            np.testing.assert_array_equal(triples(ek), triples(ec))
    print("SHARDED MERGE OK")
    """, timeout=900)


def test_sharded_sweep_edges_bit_identical():
    """The ring-sharded candidate sweep (sweep='sharded': no (s, d) xs
    broadcast, block copies rotate via per-axis ppermute rings) produces the
    same MSTEdges as the replicated sweep (sweep='bcast': u, v, valid exact,
    w to 2 ulp of 1.0) — on
    1-device, 4-device, non-power-of-two 6-device, and (3, 2) pod meshes,
    at non-shard-multiple s (pad rows ride the ring with label -1), with
    the overlapped exchange schedule both on and off."""
    _run("""
    import numpy as np, jax, jax.numpy as jnp
    from repro.distrib.hac_parallel import boruvka_mst_distributed
    from repro.distrib.sharding import make_flat_mesh, make_pod_mesh

    W_ATOL = 2 * np.finfo(np.float32).eps
    rng = np.random.default_rng(7)
    xs = jnp.asarray(rng.normal(size=(321, 24)).astype(np.float32))

    def edges(mesh, axes, **kw):
        e = boruvka_mst_distributed(
            mesh, axes, xs, compact=False, prewarm=False, **kw)
        return [np.asarray(v) for v in (e.u, e.v, e.w, e.valid)]

    ref = edges(make_flat_mesh(1), ("data",), sweep="bcast")
    assert int(ref[3].sum()) == 321 - 1
    for mesh, axes, tag in (
            (make_flat_mesh(1), ("data",), "flat1"),
            (make_flat_mesh(4), ("data",), "flat4"),
            (make_flat_mesh(6), ("data",), "flat6"),
            (make_pod_mesh(3, 2), ("pod", "data"), "pod32")):
        for overlap in (True, False):
            got = edges(mesh, axes, sweep="sharded", overlap=overlap)
            msg = f"{tag} overlap={overlap}"
            for i in (0, 1, 3):  # u, v, valid: exact
                np.testing.assert_array_equal(ref[i], got[i], err_msg=msg)
            # w: the ring contracts in other block shapes than the
            # replicated sweep, which XLA may round apart
            np.testing.assert_allclose(
                ref[2], got[2], rtol=0, atol=W_ATOL, err_msg=msg)
    print("SHARDED SWEEP OK")
    """, timeout=900)


def test_sharded_sweep_sigkill_resume_bit_parity():
    """SIGKILL a checkpointed sharded-sweep Borůvka run mid-pass (the carry
    snapshot includes the sharded comp slice); the resumed run must produce
    edges bit-identical to an uninterrupted oracle, actually restore from
    the snapshot (not cold-start), and delete it on completion."""
    import signal
    import subprocess
    import sys
    import tempfile
    import textwrap

    kill_code = """
    import os, signal, sys
    import numpy as np, jax.numpy as jnp
    from repro.distrib.hac_parallel import boruvka_mst_distributed
    from repro.distrib.sharding import make_flat_mesh
    from repro.resilience import DiskCheckpointer

    class KillingCkpt(DiskCheckpointer):
        saves = 0
        def save(self, *a, **k):
            super().save(*a, **k)
            KillingCkpt.saves += 1
            if KillingCkpt.saves >= 2:
                os.kill(os.getpid(), signal.SIGKILL)

    rng = np.random.default_rng(13)
    xs = jnp.asarray(rng.normal(size=(256, 16)).astype(np.float32))
    ck = KillingCkpt(os.environ["CKPT_DIR"], every=1)
    boruvka_mst_distributed(
        make_flat_mesh(4), ("data",), xs, check_every=1, prewarm=False,
        checkpoint=ck)
    raise SystemExit("survived the kill")
    """
    resume_code = """
    import os
    import numpy as np, jax.numpy as jnp
    from repro.distrib.hac_parallel import boruvka_mst_distributed
    from repro.distrib.sharding import make_flat_mesh
    from repro.resilience import DiskCheckpointer

    class Spy(DiskCheckpointer):
        hit = None
        def load(self, *a, **k):
            out = super().load(*a, **k)
            Spy.hit = out
            return out

    rng = np.random.default_rng(13)
    xs = jnp.asarray(rng.normal(size=(256, 16)).astype(np.float32))
    mesh = make_flat_mesh(4)
    ck = Spy(os.environ["CKPT_DIR"], every=1)
    got = boruvka_mst_distributed(
        mesh, ("data",), xs, check_every=1, prewarm=False, checkpoint=ck)
    assert Spy.hit is not None and Spy.hit["chunk"] >= 1, "cold start"
    want = boruvka_mst_distributed(
        mesh, ("data",), xs, check_every=1, prewarm=False)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert not [f for f in os.listdir(os.environ["CKPT_DIR"])
                if f.endswith(".ckpt")], "snapshot not deleted"
    print("RESUME OK")
    """
    with tempfile.TemporaryDirectory() as tmp:
        env = dict(ENV, CKPT_DIR=tmp)
        cwd = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        killed = subprocess.run(
            [sys.executable, "-c", textwrap.dedent(kill_code)],
            capture_output=True, text=True, timeout=600, env=env, cwd=cwd,
        )
        assert killed.returncode == -signal.SIGKILL, (
            f"rc={killed.returncode}\nSTDOUT:\n{killed.stdout}\n"
            f"STDERR:\n{killed.stderr}")
        assert [f for f in os.listdir(tmp) if f.endswith(".ckpt")], (
            "kill left no snapshot")
        out = subprocess.run(
            [sys.executable, "-c", textwrap.dedent(resume_code)],
            capture_output=True, text=True, timeout=600, env=env, cwd=cwd,
        )
        assert out.returncode == 0, (
            f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}")
        assert "RESUME OK" in out.stdout


def test_synthetic_merge_rounds_comp_vs_point_parity():
    """The merge-only driver (synthetic pair-merge candidates): the sharded
    comp path and the replicated point path agree on round count, the exact
    valid-edge triple set (s-1 edges), and the resulting cut labels."""
    _run("""
    import numpy as np, jax, jax.numpy as jnp
    from repro.core.hac import MSTEdges, cut_mst_edges
    from repro.distrib.hac_parallel import synthetic_merge_rounds
    from repro.distrib.sharding import make_flat_mesh, make_pod_mesh

    for s in (321, 1000):
        for mesh, axes in ((make_flat_mesh(8), ("data",)),
                           (make_pod_mesh(2, 4), ("pod", "data"))):
            ec, rc = synthetic_merge_rounds(mesh, axes, s, merge="comp")
            ep, rp = synthetic_merge_rounds(mesh, axes, s, merge="point")
            assert rc == rp, (s, rc, rp)

            def triples(e):
                v = np.asarray(e.valid)
                assert int(v.sum()) == s - 1
                t = np.stack([np.asarray(e.u)[v], np.asarray(e.v)[v],
                              np.asarray(e.w)[v].view(np.int32)])
                return t[:, np.lexsort(t)]
            np.testing.assert_array_equal(triples(ec), triples(ep))
            np.testing.assert_array_equal(
                np.asarray(cut_mst_edges(ec, s, 3)),
                np.asarray(cut_mst_edges(ep, s, 3)))
    print("SYNTH MERGE OK")
    """, timeout=900)


def test_owner_scatter_topk_finalize_matches_oracle_pod_mesh():
    """Fold-mode 'topk' with the owner-scatter finalize: scores gathered,
    payload rows moved only by their owner shard — bit-identical to the
    numpy rank-then-index oracle, on a pod (2, 4) mesh where the owner id
    spans two mesh axes."""
    _run("""
    import numpy as np, jax, jax.numpy as jnp
    from repro.distrib.engine import make_fold_job
    from repro.distrib.sharding import make_flat_mesh, make_pod_mesh

    P, s, d = 8, 16, 5
    rng = np.random.default_rng(11)
    score = rng.permutation(P * s).astype(np.float32).reshape(P, s)
    rows = rng.normal(size=(P, s, d)).astype(np.float32)
    gidx = np.arange(P * s, dtype=np.int32).reshape(P, s)

    flat = -score.reshape(-1)
    want_pos = np.argsort(flat, kind="stable")[:s]
    want = {"score": score.reshape(-1)[want_pos],
            "rows": rows.reshape(-1, d)[want_pos],
            "gidx": gidx.reshape(-1)[want_pos]}

    for mesh, axes in ((make_flat_mesh(8), ("data",)),
                       (make_pod_mesh(2, 4), ("pod", "data"))):
        fold = make_fold_job(mesh, axes, lambda data, b: data, "topk")
        carry, _ = fold.step(None, {
            "score": jnp.asarray(score.reshape(P * s)),
            "rows": jnp.asarray(rows.reshape(P * s, d)),
            "gidx": jnp.asarray(gidx.reshape(P * s)),
        }, {})
        out = fold.finalize(carry)
        np.testing.assert_array_equal(np.asarray(out["score"]), want["score"])
        np.testing.assert_array_equal(np.asarray(out["gidx"]), want["gidx"])
        np.testing.assert_array_equal(np.asarray(out["rows"]), want["rows"])
    print("OWNER SCATTER OK")
    """)


def test_tier_topology_is_part_of_cache_identity():
    """Two pod meshes over the SAME 8 devices with the SAME axis names but
    different tier splits — (2, 4) vs (4, 2) — must land in distinct
    candidate-job cache entries and distinct prewarm slots: the tiered
    'component' reduce lowers different collectives per topology."""
    _run("""
    import jax
    from repro.distrib import hac_parallel as hp
    from repro.distrib.sharding import make_pod_mesh, tier_sizes

    axes = ("pod", "data")
    m24, m42 = make_pod_mesh(2, 4), make_pod_mesh(4, 2)
    assert tier_sizes(m24, axes) == (2, 4)
    assert tier_sizes(m42, axes) == (4, 2)

    j24 = hp._cand_job(m24, tier_sizes(m24, axes), axes, "xla", "comp")
    j42 = hp._cand_job(m42, tier_sizes(m42, axes), axes, "xla", "comp")
    assert j24 is not j42
    # the ring sweep's overlap schedule is its own lowered program, so it is
    # its own cache identity too
    jov = hp._cand_job(
        m24, tier_sizes(m24, axes), axes, "xla", "comp_sharded", True)
    jser = hp._cand_job(
        m24, tier_sizes(m24, axes), axes, "xla", "comp_sharded", False)
    assert jov is not jser

    s, d, pad = 64, 4, 0
    for mesh in (m24, m42):
        slots = hp.prewarm_candidate_rounds(
            mesh, axes, "xla", s=s, d=d, pad=pad, rounds=1, mode="comp")
        assert slots[0].result() is not None
    # _WARM key layout: (mesh, tiers, axes, impl, mode, overlap, s, d, pad, cap)
    with hp._WARM_LOCK:
        tiers_seen = {k[1] for k in hp._WARM
                      if k[4] == "comp" and k[6] == s and k[7] == d}
    assert {(2, 4), (4, 2)} <= tiers_seen, tiers_seen
    print("CACHE KEY OK")
    """)


def test_job_caches_bounded_and_clearable():
    """The candidate/relabel job caches are bounded lru caches, and
    ``clear_job_caches`` empties them plus the AOT executable table and the
    rounds hint — nothing keeps pinning Mesh objects afterwards."""
    _run("""
    from repro.distrib import hac_parallel as hp
    from repro.distrib.sharding import make_flat_mesh, tier_sizes

    assert hp._cand_job.cache_info().maxsize == 32
    assert hp._relabel_job.cache_info().maxsize == 32

    mesh, axes = make_flat_mesh(4), ("data",)
    tiers = tier_sizes(mesh, axes)
    hp._cand_job(mesh, tiers, axes, "xla", "comp")
    hp._relabel_job(mesh, tiers, axes)
    slots = hp.prewarm_candidate_rounds(
        mesh, axes, "xla", s=32, d=4, pad=0, rounds=1, mode="comp")
    assert slots[0].result() is not None
    assert hp._cand_job.cache_info().currsize > 0
    assert hp._WARM

    hp.clear_job_caches()
    assert hp._cand_job.cache_info().currsize == 0
    assert hp._relabel_job.cache_info().currsize == 0
    assert not hp._WARM and not hp._WARM_ROUNDS_HINT
    # caches repopulate cleanly after a clear
    hp._cand_job(mesh, tiers, axes, "xla", "comp")
    assert hp._cand_job.cache_info().currsize == 1
    print("CACHE BOUND OK")
    """)


def test_pod_mesh_validation():
    """make_pod_mesh: non-pow-2 pod counts work; a device-count mismatch
    raises instead of silently truncating."""
    _run("""
    import jax, pytest
    from repro.distrib.sharding import make_pod_mesh, tier_sizes

    m = make_pod_mesh(3, 2)  # 6 of the 8 simulated devices
    assert tier_sizes(m, ("pod", "data")) == (3, 2)
    m = make_pod_mesh(2)  # pod_size inferred: all 8 devices
    assert tier_sizes(m, ("pod", "data")) == (2, 4)
    try:
        make_pod_mesh(3, 3)  # 9 > 8 devices
    except ValueError:
        pass
    else:
        raise AssertionError("oversubscribed pod mesh did not raise")
    print("POD MESH OK")
    """)
