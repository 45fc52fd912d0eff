"""Fault injection, resilience, and guarded numerics (DESIGN.md §12).

The contract under test: every resilience feature is OPT-IN and, when armed,
degrades a failure into either a clean recovery (retry, checkpoint resume,
Pallas→XLA degradation) or an attributed error (StreamFault, StreamTimeout,
GuardError) — never a hang, never silent corruption. Recovery paths must be
BIT-IDENTICAL to the uninterrupted oracle: the monoid carries replay the
same f32 add sequence, and per-chunk rng keys are pure functions of the
chunk index.

The SIGKILL tests run the job in a subprocess (REPRO_FAULTS=kill@gN), let it
die mid-pass, rerun it against the same DiskCheckpointer directory, and
compare assignments to an uninterrupted oracle — on one device and on a
4-device mesh (re-sharded carry restore).
"""

from __future__ import annotations

import os
import pickle
import signal
import subprocess
import sys
import textwrap
import threading
import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common import l2_normalize
from repro.core.kmeans import kmeans_fit_stream, kmeans_step
from repro.kernels import ops
from repro.resilience import (
    DiskCheckpointer,
    GuardError,
    MemoryCheckpointer,
    RetryPolicy,
    StreamFault,
    StreamTimeout,
    array_token,
    carry_fingerprint,
)
from repro.testing import faults
from repro.testing.faults import FaultPlan, InjectedFault
from repro.text import tfidf
from repro.text.stream import CorpusStream, run_pass

ENV = dict(
    os.environ,
    PYTHONPATH="src",
    JAX_PLATFORMS="cpu",
)
ENV.pop("REPRO_FAULTS", None)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _no_leftover_faults():
    faults.clear()
    yield
    faults.clear()


def _stream(n=96, dim=8, chunk=16, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, dim)).astype(np.float32)
    return CorpusStream.from_array(x, chunk=chunk), x


def _sum_fold(state, ch, ci):
    return state + float(np.sum(ch.x * ch.w[:, None]))


# ------------------------------------------------------------ spec parsing


def test_fault_spec_grammar():
    plan = FaultPlan.from_spec("raise@c2x3, nan@g17, stall@c0:1.5, pallasx2")
    kinds = [f.kind for f in plan.faults]
    assert kinds == ["raise", "nan", "stall", "pallas"]
    assert plan.faults[0].where == ("c", 2) and plan.faults[0].times == 3
    assert plan.faults[1].where == ("g", 17) and plan.faults[1].times == 1
    assert plan.faults[2].seconds == 1.5
    assert plan.faults[3].where is None and plan.faults[3].times == 2
    # x* = unlimited
    assert FaultPlan.from_spec("raise@c1x*").faults[0].times is None
    # bare integer trigger = chunk index
    assert FaultPlan.from_spec("raise@3").faults[0].where == ("c", 3)


@pytest.mark.parametrize(
    "bad",
    ["", "frobnicate@c1", "raise", "raise@z9", "stall@c0", "pallas@c1", "raise@cx"],
)
def test_fault_spec_rejects(bad):
    with pytest.raises(ValueError):
        FaultPlan.from_spec(bad)


# ------------------------------------------------------------ retry


def test_retry_recovers_and_matches_oracle():
    st, x = _stream()
    oracle = run_pass(st, _sum_fold, 0.0)
    plan = faults.install("raise@c2x2")
    got = run_pass(st, _sum_fold, 0.0, retry=3)
    assert got == oracle
    assert plan.fired("raise") == 2


def test_fail_fast_is_the_default():
    st, _ = _stream()
    faults.install("raise@c1")
    with pytest.raises(InjectedFault):  # original exception, unwrapped
        run_pass(st, _sum_fold, 0.0)


def test_stream_fault_attribution_past_budget():
    st, _ = _stream()
    faults.install("raise@c1x*")
    with pytest.raises(StreamFault) as ei:
        run_pass(st, _sum_fold, 0.0, pass_id="p", retry=2)
    assert ei.value.chunk == 1 and ei.value.attempts == 3
    assert ei.value.pass_id == "p"
    assert isinstance(ei.value.__cause__, InjectedFault)


def test_retry_policy_backoff_and_env(monkeypatch):
    p = RetryPolicy(retries=4, base_delay=0.05, max_delay=0.12)
    assert p.delay(1) == 0.05 and p.delay(2) == 0.10
    assert p.delay(3) == 0.12  # capped
    monkeypatch.setenv("REPRO_STREAM_RETRIES", "3")
    assert RetryPolicy.resolve(None).retries == 3
    assert RetryPolicy.resolve(5).retries == 5  # explicit wins


def test_stream_contract_errors_still_surface():
    """Producer-side contract violations (from_blocks) must keep raising
    ValueError through the retry layer with retries=0 — the seed contract."""

    def blocks():
        yield np.zeros((4, 8), np.float32)  # short block before the end
        yield np.zeros((16, 8), np.float32)

    st = CorpusStream.from_blocks(blocks, n=20, dim=8, chunk=16)
    with pytest.raises(ValueError, match="short block"):
        run_pass(st, _sum_fold, 0.0)


# ------------------------------------------------------------ guard


def test_guard_attributes_pass_and_chunk():
    st, _ = _stream()
    faults.install("nan@c3")
    with pytest.raises(GuardError) as ei:
        run_pass(st, _sum_fold, jnp.float32(0.0), pass_id="g", guard="finite")
    assert ei.value.pass_id == "g" and ei.value.chunk == 3


def test_guard_off_by_default_lets_nan_flow():
    st, _ = _stream()
    faults.install("nan@c3")
    got = run_pass(st, _sum_fold, 0.0)
    assert np.isnan(got)


def test_guard_checks_device_and_host_leaves():
    st, _ = _stream()
    faults.install("inf@c2")

    def fold(state, ch, ci):  # device carry leaf
        return state + jnp.sum(jnp.asarray(ch.x) * jnp.asarray(ch.w)[:, None])

    with pytest.raises(GuardError) as ei:
        run_pass(st, fold, jnp.float32(0.0), guard="finite")
    assert ei.value.chunk == 2


def test_guard_env_knob(monkeypatch):
    st, _ = _stream()
    faults.install("nan@c1")
    monkeypatch.setenv("REPRO_STREAM_GUARD", "finite")
    with pytest.raises(GuardError):
        run_pass(st, _sum_fold, 0.0, pass_id="env")


# ------------------------------------------------------------ watchdog


def test_watchdog_turns_stall_into_timeout():
    st, _ = _stream()
    faults.install("stall@c1:30")
    t0 = time.monotonic()
    with pytest.raises(StreamTimeout) as ei:
        run_pass(st, _sum_fold, 0.0, pass_id="wd", timeout=0.3)
    assert time.monotonic() - t0 < 10.0
    assert ei.value.pass_id == "wd" and ei.value.chunk == 1


def test_watchdog_quiet_when_stream_is_healthy():
    st, _ = _stream()
    oracle = run_pass(st, _sum_fold, 0.0)
    assert run_pass(st, _sum_fold, 0.0, timeout=30.0) == oracle


# ------------------------------------------------------------ pallas degrade


@pytest.fixture
def _pallas_armed():
    ops._reset_pallas_degradation()
    yield
    ops._reset_pallas_degradation()


def test_pallas_failure_degrades_to_xla(_pallas_armed):
    rng = np.random.default_rng(1)
    # unique shape: the dispatch (and its guard) runs at trace time, so a
    # cached jit of a previously-seen shape would bypass the injection
    x = jnp.asarray(rng.normal(size=(37, 16)).astype(np.float32))
    c = jnp.asarray(rng.normal(size=(3, 16)).astype(np.float32))
    want_idx, want_sim = ops.assign_argmax(x, c, impl="xla")

    faults.install("pallas")
    assert not ops.pallas_degraded()
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        idx, sim = ops.assign_argmax(x, c, impl="pallas")
    assert ops.pallas_degraded()
    assert any("degrading to the XLA" in str(wi.message) for wi in w)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(want_idx))
    np.testing.assert_allclose(np.asarray(sim), np.asarray(want_sim), rtol=1e-6)

    # degradation is sticky: later traces skip Pallas without consulting the
    # plan (the armed 'pallas' fault was already consumed above)
    x2 = jnp.asarray(rng.normal(size=(41, 16)).astype(np.float32))
    idx2, _ = ops.assign_argmax(x2, c, impl="pallas")
    want2, _ = ops.assign_argmax(x2, c, impl="xla")
    np.testing.assert_array_equal(np.asarray(idx2), np.asarray(want2))


def test_real_pallas_failure_propagates(_pallas_armed, monkeypatch):
    """A Pallas failure that no injected fault caused ends the call: it is
    never replaced by XLA in silence."""
    from repro.kernels import assign_argmax as kmod

    def refused(*a, **kw):
        raise RuntimeError("Mosaic refused the kernel")

    monkeypatch.setattr(kmod, "assign_argmax_pallas", refused)
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(43, 16)).astype(np.float32))
    c = jnp.asarray(rng.normal(size=(3, 16)).astype(np.float32))
    with pytest.raises(RuntimeError, match="Mosaic refused"):
        ops.assign_argmax(x, c, impl="pallas")
    assert not ops.pallas_degraded()


# ------------------------------------------------------------ prefetcher


def test_prefetcher_leaves_no_threads_behind():
    st, _ = _stream(n=512, chunk=16)
    baseline = {t for t in threading.enumerate()}
    for _ in range(3):  # completed passes
        run_pass(st, _sum_fold, 0.0, prefetch=2)
    from repro.text.stream import iter_chunks

    it = iter_chunks(st, prefetch=2)  # abandoned pass
    next(it)
    it.close()

    def failing(state, ch, ci):
        if ci == 2:
            raise RuntimeError("boom")
        return state

    with pytest.raises(RuntimeError, match="boom"):  # failed pass
        run_pass(st, failing, 0.0, prefetch=2)

    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline:
        extra = [
            t
            for t in threading.enumerate()
            if t not in baseline and t.name.startswith("corpus-stream")
        ]
        if not extra:
            break
        time.sleep(0.05)
    assert not extra, f"leaked prefetch threads: {extra}"


def test_pass_restartable_after_failure():
    st, _ = _stream()
    oracle = run_pass(st, _sum_fold, 0.0)
    faults.install("raise@c1")
    with pytest.raises(InjectedFault):
        run_pass(st, _sum_fold, 0.0)
    faults.clear()
    assert run_pass(st, _sum_fold, 0.0) == oracle


# ------------------------------------------------------------ checkpointing


def test_checkpoint_resume_bit_identical_in_process():
    st, _ = _stream(n=128, chunk=16)
    ck = MemoryCheckpointer(every=2)
    oracle = run_pass(st, _sum_fold, 0.0)
    faults.install("raise@c5")
    with pytest.raises(InjectedFault):
        run_pass(st, _sum_fold, 0.0, pass_id="p", checkpoint=ck)
    faults.clear()
    assert ck._store  # a mid-pass snapshot survived the failure
    got = run_pass(st, _sum_fold, 0.0, pass_id="p", checkpoint=ck)
    assert got == oracle
    assert not ck._store  # completion deletes the snapshot


def test_checkpoint_invalidated_by_fingerprint_and_meta():
    st, _ = _stream(n=64, chunk=16)
    ck = MemoryCheckpointer(every=1)
    faults.install("raise@c2")
    with pytest.raises(InjectedFault):
        run_pass(st, _sum_fold, 0.0, pass_id="p", checkpoint=ck,
                 meta={"token": "a"})
    faults.clear()
    fp = carry_fingerprint(0.0)
    full_meta = {
        "stream": {"n": st.n, "dim": st.dim, "chunk": st.chunk},
        "token": "a",
    }
    assert ck.load("p", fingerprint=fp, meta=full_meta) is not None
    # different broadcast state (meta) -> cold start
    assert ck.load("p", fingerprint=fp,
                   meta={**full_meta, "token": "b"}) is None
    # different carry structure -> cold start
    assert ck.load("p", fingerprint=carry_fingerprint((0.0, [])),
                   meta=full_meta) is None
    # different pass id -> nothing there
    assert ck.load("q", fingerprint=fp, meta=full_meta) is None


def test_disk_checkpointer_survives_corruption(tmp_path):
    ck = DiskCheckpointer(tmp_path, every=1)
    ck.save("p", chunk=3, carry_host=1.25, fingerprint="float", meta={})
    snap = ck.load("p", fingerprint="float", meta={})
    assert snap is not None and snap["chunk"] == 3 and snap["carry"] == 1.25
    # torn/corrupt file degrades to a cold start, never an exception
    (path,) = [p for p in os.listdir(tmp_path) if p.endswith(".ckpt")]
    with open(os.path.join(tmp_path, path), "wb") as f:
        f.write(b"\x80garbage")
    assert ck.load("p", fingerprint="float", meta={}) is None
    # version skew degrades the same way
    ck.save("p", chunk=3, carry_host=1.25, fingerprint="float", meta={})
    with open(os.path.join(tmp_path, path), "wb") as f:
        state = {"version": 999, "pass_id": "p", "chunk": 3, "carry": 1.25,
                 "fingerprint": "float", "meta": {}}
        f.write(pickle.dumps(state))
    assert ck.load("p", fingerprint="float", meta={}) is None


def test_scoped_checkpointer_namespaces():
    ck = MemoryCheckpointer(every=4)
    sub = ck.scoped("buckshot")
    sub.save("kmeans/iter0", chunk=1, carry_host=1.0, fingerprint="f", meta={})
    ck.save("kmeans/iter0", chunk=2, carry_host=2.0, fingerprint="f", meta={})
    assert sub.load("kmeans/iter0", fingerprint="f", meta={})["carry"] == 1.0
    assert ck.load("kmeans/iter0", fingerprint="f", meta={})["carry"] == 2.0


def test_checkpoint_result_roundtrip_and_token():
    ck = MemoryCheckpointer()
    c = np.arange(6, dtype=np.float32).reshape(2, 3)
    ck.save_result("iter0", {"token": array_token(c), "centers": c})
    got = ck.load_result("iter0")
    assert got["token"] == array_token(c)
    np.testing.assert_array_equal(got["centers"], c)
    assert ck.load_result("missing") is None
    ck.delete_result("iter0")
    assert ck.load_result("iter0") is None


# --------------------------------------------------- SIGKILL resume parity

_KILL_JOB = """
    import os
    import numpy as np
    import jax
    import jax.numpy as jnp

    from repro.common import l2_normalize
    from repro.resilience import DiskCheckpointer
    from repro.text.stream import CorpusStream

    rng = np.random.default_rng(7)
    x = np.asarray(l2_normalize(
        jnp.asarray(rng.normal(size=(512, 16)).astype(np.float32))))
    st = CorpusStream.from_array(x, chunk=64)
    init = np.asarray(l2_normalize(
        jnp.asarray(rng.normal(size=(5, 16)).astype(np.float32))))
    ck = DiskCheckpointer(os.environ["CKPT_DIR"], every=2)

    if os.environ.get("MESH") == "1":
        from jax.sharding import Mesh
        from repro.distrib.cluster import kmeans_distributed_stream

        mesh = Mesh(np.array(jax.devices()), ("data",))
        res = kmeans_distributed_stream(
            mesh, ("data",), st, jnp.asarray(init), 5,
            max_iters=3, tol=0.0, checkpoint=ck)
    else:
        from repro.core.kmeans import kmeans_fit_stream

        res = kmeans_fit_stream(
            st, jnp.asarray(init), 5, max_iters=3, tol=0.0, checkpoint=ck)
    np.save(os.environ["OUT"], np.asarray(res.assignment))
    np.save(os.environ["OUT"] + ".centers.npy", np.asarray(res.centers))
"""


def _run_kill_job(tmp_path, tag: str, *, devices: int, fault: str | None,
                  extra_env: dict | None = None):
    env = dict(
        ENV,
        CKPT_DIR=str(tmp_path / f"ckpt-{tag}"),
        OUT=str(tmp_path / f"out-{tag}.npy"),
        XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
        MESH="1" if devices > 1 else "0",
        **(extra_env or {}),
    )
    if fault:
        env["REPRO_FAULTS"] = fault
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(_KILL_JOB)],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO,
    )
    return out, env


@pytest.mark.parametrize("devices", [1, 4])
def test_sigkill_resume_bit_identical(tmp_path, devices):
    """Kill the job mid-final-pass (29th chunk served, of 8 chunks/pass x 4
    passes = 32), restart it from disk, and the assignments and centers must
    equal the uninterrupted oracle's exactly."""
    # oracle: clean run, its own checkpoint dir
    out, _ = _run_kill_job(tmp_path, f"oracle{devices}", devices=devices, fault=None)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"

    # killed run: SIGKILL as the 29th chunk is produced
    out, env = _run_kill_job(tmp_path, f"kill{devices}", devices=devices,
                             fault="kill@g28")
    assert out.returncode == -signal.SIGKILL, (
        f"expected SIGKILL death, got rc={out.returncode}\n"
        f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    )
    assert not os.path.exists(env["OUT"])
    assert os.listdir(env["CKPT_DIR"])  # snapshots survived the kill

    # resume: same checkpoint dir, no fault
    env.pop("REPRO_FAULTS")
    out2 = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(_KILL_JOB)],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO,
    )
    assert out2.returncode == 0, f"STDOUT:\n{out2.stdout}\nSTDERR:\n{out2.stderr}"

    oracle = np.load(tmp_path / f"out-oracle{devices}.npy")
    resumed = np.load(env["OUT"])
    np.testing.assert_array_equal(resumed, oracle)
    np.testing.assert_array_equal(
        np.load(env["OUT"] + ".centers.npy"),
        np.load(str(tmp_path / f"out-oracle{devices}.npy") + ".centers.npy"),
    )
    # completion cleaned every snapshot and stored result
    assert not [p for p in os.listdir(env["CKPT_DIR"]) if p.endswith(".ckpt")]


@pytest.mark.parametrize("devices", [1, 4])
def test_sigkill_resume_bit_identical_bounded(tmp_path, devices):
    """Same kill/restart protocol, but with bound-pruned assignment armed
    (REPRO_ASSIGN_BOUNDS=1): the bounds carry rides the snapshot, and the
    resumed run must equal a clean bounded run on assignments AND centers.
    (Prune COUNTS may legitimately differ on resume — a skipped pass restarts
    the carry from the sentinel — which is why the contract is labels and
    centers, never bounds state.)"""
    benv = {"REPRO_ASSIGN_BOUNDS": "1"}
    out, _ = _run_kill_job(tmp_path, f"boracle{devices}", devices=devices,
                           fault=None, extra_env=benv)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"

    out, env = _run_kill_job(tmp_path, f"bkill{devices}", devices=devices,
                             fault="kill@g28", extra_env=benv)
    assert out.returncode == -signal.SIGKILL, (
        f"expected SIGKILL death, got rc={out.returncode}\n"
        f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    )
    assert not os.path.exists(env["OUT"])
    assert os.listdir(env["CKPT_DIR"])

    env.pop("REPRO_FAULTS")
    out2 = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(_KILL_JOB)],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO,
    )
    assert out2.returncode == 0, f"STDOUT:\n{out2.stdout}\nSTDERR:\n{out2.stderr}"

    np.testing.assert_array_equal(
        np.load(env["OUT"]), np.load(tmp_path / f"out-boracle{devices}.npy")
    )
    np.testing.assert_array_equal(
        np.load(env["OUT"] + ".centers.npy"),
        np.load(str(tmp_path / f"out-boracle{devices}.npy") + ".centers.npy"),
    )
    assert not [p for p in os.listdir(env["CKPT_DIR"]) if p.endswith(".ckpt")]


def test_bounded_pallas_failure_degrades_to_xla(_pallas_armed):
    """assign_stats_bounded shares the once-per-process guard: a Pallas
    failure degrades it to its XLA pair with identical outputs, and the
    second armed fault is never consumed (degradation is sticky)."""
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.normal(size=(53, 24)).astype(np.float32))
    c = jnp.asarray(rng.normal(size=(6, 24)).astype(np.float32))
    b = ops.bounds_identity(53)
    drift = jnp.zeros((6,), jnp.float32)
    want = ops.assign_stats_bounded(x, c, b, drift, impl="xla")

    plan = faults.install("pallasx2")
    assert not ops.pallas_degraded()
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        got = ops.assign_stats_bounded(x, c, b, drift, impl="pallas")
    assert ops.pallas_degraded()
    assert any("degrading to the XLA" in str(wi.message) for wi in w)
    np.testing.assert_array_equal(np.asarray(want.idx), np.asarray(got.idx))
    np.testing.assert_array_equal(
        np.asarray(want.counts), np.asarray(got.counts))
    np.testing.assert_array_equal(
        np.asarray(want.sums), np.asarray(got.sums))

    # sticky: a fresh shape re-traces but skips Pallas without consulting
    # the plan — the second armed fault stays unconsumed
    x2 = jnp.asarray(rng.normal(size=(59, 24)).astype(np.float32))
    b2 = ops.bounds_identity(59)
    got2 = ops.assign_stats_bounded(x2, c, b2, drift, impl="pallas")
    want2 = ops.assign_stats_bounded(x2, c, b2, drift, impl="xla")
    np.testing.assert_array_equal(np.asarray(want2.idx), np.asarray(got2.idx))
    assert plan.fired("pallas") == 1


# ------------------------------------------------------------ reseed policy


def test_kmeans_reseed_splits_empty_cluster():
    rng = np.random.default_rng(5)
    d = 8
    a = np.zeros((40, d), np.float32)
    a[:, 0] = 1.0
    b = np.zeros((40, d), np.float32)
    b[:, 1] = 1.0
    x = np.concatenate([a, b]) + 0.05 * rng.normal(size=(80, d)).astype(np.float32)
    x = np.asarray(l2_normalize(jnp.asarray(x)))
    init = np.zeros((3, d), np.float32)
    init[0, 0] = 1.0
    init[1, 1] = 1.0
    init[2, 0] = -1.0  # antipodal: no document picks it

    # default (seed behavior): the empty center is carried unchanged forever
    c1, _, _, _, counts1 = kmeans_step(jnp.asarray(x), jnp.asarray(init), 3)
    assert int(np.asarray(counts1)[2]) == 0
    np.testing.assert_array_equal(np.asarray(c1)[2], init[2])

    # reseed='split': the empty center moves to a split of the worst cluster
    c2, _, _, _, counts2 = kmeans_step(
        jnp.asarray(x), jnp.asarray(init), 3, reseed="split"
    )
    assert int(np.asarray(counts2)[2]) == 0  # counts are THIS step's stats
    assert not np.array_equal(np.asarray(c2)[2], init[2])
    assert np.all(np.isfinite(np.asarray(c2)))
    # and the reseeded center captures documents on the next step
    _, _, _, _, counts3 = kmeans_step(jnp.asarray(x), c2, 3, reseed="split")
    assert int(np.asarray(counts3)[2]) > 0


def test_kmeans_reseed_validation():
    x = jnp.eye(4, dtype=jnp.float32)
    with pytest.raises(ValueError, match="fused"):
        kmeans_step(x, x, 4, fused=False, reseed="split")
    with pytest.raises(ValueError, match="reseed"):
        kmeans_step(x, x, 4, reseed="bogus")


def test_kmeans_reseed_noop_when_no_empty_cluster(blob_data):
    x, _, k = blob_data
    key = jax.random.PRNGKey(0)
    from repro.core.kmeans import init_random_centers

    init = init_random_centers(key, x, k)
    c_def, _, _, _, counts = kmeans_step(x, init, k)
    if int(np.asarray(counts).min()) > 0:  # all clusters populated
        c_rs, _, _, _, _ = kmeans_step(x, init, k, reseed="split")
        np.testing.assert_array_equal(np.asarray(c_def), np.asarray(c_rs))


# ------------------------------------------------------------ tfidf edges


def test_tfidf_rejects_empty_collection():
    with pytest.raises(ValueError, match="empty collection"):
        tfidf.tfidf(jnp.zeros((0, 16), jnp.float32))


def test_df_stream_rejects_empty_stream():
    st = CorpusStream.from_array(np.zeros((0, 16), np.float32), chunk=4)
    with pytest.raises(ValueError, match="empty stream"):
        tfidf.df_stream(st)


def test_tfidf_all_zero_row_stays_zero_and_finite():
    counts = np.zeros((4, 8), np.float32)
    counts[0, 1] = 3.0
    counts[1, 2] = 1.0
    counts[3, 1] = 2.0  # row 2 is an empty document
    x = np.asarray(tfidf.tfidf(jnp.asarray(counts)))
    assert np.all(np.isfinite(x))
    np.testing.assert_array_equal(x[2], np.zeros(8, np.float32))
    # streaming path agrees on the degenerate row
    st = CorpusStream.from_array(counts, chunk=2)
    xs = tfidf.tfidf_stream(st).materialize()
    np.testing.assert_array_equal(np.asarray(xs), x)


# ------------------------------------------------------------ serve faults


def test_serve_fault_spec_grammar():
    plan = FaultPlan.from_spec("kill@refit, stall@assign:2, nan@ingest, raise@validatex*")
    assert plan.faults[0].where == ("s", "refit") and plan.faults[0].times == 1
    assert plan.faults[1].where == ("s", "assign") and plan.faults[1].seconds == 2.0
    assert plan.faults[2].where == ("s", "ingest")
    assert plan.faults[3].where == ("s", "validate") and plan.faults[3].times is None
    assert FaultPlan.from_spec("kill@refitx2").faults[0].times == 2


@pytest.mark.parametrize("bad", ["kill@frobnicate", "stall@assign", "pallas@refit"])
def test_serve_fault_spec_rejects(bad):
    with pytest.raises(ValueError):
        FaultPlan.from_spec(bad)


def test_serve_point_kill_and_raise_both_raise():
    """A worker THREAD cannot be SIGKILLed, so 'kill' at a serve point means
    the attempt dies with InjectedFault — same as 'raise'."""
    for kind in ("kill", "raise"):
        plan = faults.install(f"{kind}@refit")
        with pytest.raises(InjectedFault, match="refit"):
            faults.serve_point("refit")
        faults.serve_point("refit")  # budget consumed: second call is a no-op
        assert plan.fired() == 1
        faults.clear()


def test_serve_point_nan_corrupts_only_the_given_array():
    plan = faults.install("nan@ingest")
    a = np.ones((3, 4), np.float32)
    out = faults.serve_point("ingest", a)
    assert np.isnan(out[0]).all() and np.isfinite(out[1:]).all()
    np.testing.assert_array_equal(a, np.ones((3, 4), np.float32))  # copy, not in place
    assert plan.fired("nan") == 1


def test_serve_point_stall_sleeps():
    faults.install("stall@assign:0.2")
    t0 = time.monotonic()
    faults.serve_point("assign")
    assert time.monotonic() - t0 >= 0.15


def test_serve_point_is_a_noop_without_a_plan():
    a = np.ones((2, 2), np.float32)
    assert faults.serve_point("assign", a) is a


def test_serve_point_rejects_unknown_point():
    faults.install("kill@refit")
    with pytest.raises(ValueError, match="serve point"):
        faults.serve_point("frobnicate")


def test_serve_faults_never_fire_on_chunks_and_vice_versa():
    """A serve-scoped fault must not trip a streaming pass, and a chunk fault
    must not trip a serve point — the two trigger namespaces are disjoint."""
    st, _ = _stream()
    oracle = run_pass(st, _sum_fold, 0.0)
    plan = faults.install("kill@refit, nan@ingest")
    assert run_pass(st, _sum_fold, 0.0) == oracle
    assert plan.fired() == 0
    faults.clear()
    plan = faults.install("raise@c0, nan@g1")
    out = faults.serve_point("refit", np.ones((2, 2), np.float32))
    assert np.isfinite(out).all() and plan.fired() == 0


# ------------------------------------------------------------ retry policy


def test_retry_policy_delay_bound_and_growth():
    """delay(i) is min(base * 2^(i-1), max): monotone non-decreasing, doubles
    exactly until the cap, and never exceeds the cap for ANY attempt."""
    p = RetryPolicy(retries=10, base_delay=0.01, max_delay=1.0)
    delays = [p.delay(i) for i in range(1, 32)]
    assert delays[0] == p.base_delay
    assert all(b >= a for a, b in zip(delays, delays[1:]))  # monotone
    assert all(d <= p.max_delay for d in delays)  # bounded
    for i, (a, b) in enumerate(zip(delays, delays[1:]), start=1):
        if b < p.max_delay:
            assert b == pytest.approx(2.0 * a)  # exact doubling pre-cap
    assert p.delay(1_000) == p.max_delay  # no overflow surprise at huge i
    assert p.delay(0) == p.base_delay  # attempt 0 clamps to the base


def test_retry_policy_zero_base_never_sleeps(monkeypatch):
    called = []
    monkeypatch.setattr(time, "sleep", lambda s: called.append(s))
    RetryPolicy(retries=3, base_delay=0.0).sleep(5)
    assert called == []


def test_stream_timeout_attribution_4dev_mesh():
    """satellite: StreamTimeout pass/chunk attribution under stall@ injection
    on a 4-device mesh — the watchdog lives in run_pass, which the
    distributed fold drives too, so attribution must survive sharding."""
    env = dict(
        ENV,
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
        REPRO_FAULTS="stall@c2:30",
    )
    code = """
    import numpy as np, jax, jax.numpy as jnp
    from repro.distrib.sharding import make_flat_mesh
    from repro.resilience import StreamTimeout
    from repro.text import synth, tfidf

    mesh = make_flat_mesh(4)
    assert len(jax.devices()) == 4
    st, _ = synth.stream_corpus(400, vocab=64, n_topics=4, seed=0, chunk=80)
    try:
        tfidf.df_fold_distributed(mesh, ("data",), st)
    except StreamTimeout as e:
        assert e.pass_id == "pass" and e.chunk == 2, (e.pass_id, e.chunk)
        print("TIMEOUT ATTRIBUTED", e.chunk)
    else:
        raise AssertionError("stall did not become StreamTimeout")
    """
    env["REPRO_STREAM_TIMEOUT"] = "0.5"
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO,
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    assert "TIMEOUT ATTRIBUTED 2" in out.stdout


# ------------------------------------------------------------ disk durability


def test_disk_checkpointer_fsyncs_directory_after_rename(tmp_path, monkeypatch):
    """The atomic rename persists the directory ENTRY only if the directory
    inode is fsynced too: _put must fsync (file, then directory)."""
    import stat

    synced = []
    real_fsync = os.fsync

    def spy(fd):
        synced.append(stat.S_ISDIR(os.fstat(fd).st_mode))
        real_fsync(fd)

    monkeypatch.setattr(os, "fsync", spy)
    ck = DiskCheckpointer(tmp_path / "ck")
    ck.save_result("p", {"v": 1})
    assert synced.count(False) >= 1  # the payload file
    assert synced.count(True) >= 1  # the parent directory, after the rename
    assert ck.load_result("p") == {"v": 1}


def test_disk_checkpointer_survives_injected_dir_fsync_failure(
    tmp_path, monkeypatch
):
    """Injected os-level fault: a filesystem that refuses directory fsync
    (EINVAL — some network/overlay mounts) must degrade to best-effort,
    never fail the write."""
    import stat

    real_fsync = os.fsync

    def failing(fd):
        if stat.S_ISDIR(os.fstat(fd).st_mode):
            raise OSError(22, "Invalid argument")
        real_fsync(fd)

    monkeypatch.setattr(os, "fsync", failing)
    ck = DiskCheckpointer(tmp_path / "ck")
    ck.save_result("p", {"v": 2})  # must not raise
    assert ck.load_result("p") == {"v": 2}

    # and a directory that cannot even be opened read-only degrades the same
    monkeypatch.setattr(
        os, "open",
        lambda *a, **k: (_ for _ in ()).throw(OSError(13, "denied")),
    )
    ck.save_result("q", {"v": 3})
    assert ck.load_result("q") == {"v": 3}
