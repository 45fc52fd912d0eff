"""The spans of the stream executor and the stream drivers in the profiler's
trace (text/stream.py, core/kmeans.py, core/sampling.py, core/buckshot.py).

A tiny streamed Buckshot job runs under ``jax.profiler.trace``; the trace is
read with ``jax.profiler.ProfileData`` and every span is counted exactly.
Every profiler test of the suite lives in this file: a process runs one
profiler session at a time, and ``--dist loadfile`` keeps a file on one
worker. The profiler starts inside a fixture, never at import.
"""

from __future__ import annotations

import glob
import os

import jax
import numpy as np
import pytest

from repro.core import buckshot_stream
from repro.text.stream import CorpusStream

N, CHUNK, K = 600, 128, 4
N_CHUNKS = -(-N // CHUNK)
PASSES = 4  # the reservoir sample, two K-Means iterations, the final pass


def _from_blocks(x):
    """The rows of ``x`` from a source that regenerates its blocks in every
    pass, as ``from_texts`` does."""
    return CorpusStream.from_blocks(
        lambda: (x[s : s + CHUNK] for s in range(0, N, CHUNK)),
        n=N, dim=x.shape[1], chunk=CHUNK,
    )


def _job(make_stream=lambda x: CorpusStream.from_array(x, chunk=CHUNK)):
    x = np.random.default_rng(0).random((N, 32), np.float32)
    x /= np.linalg.norm(x, axis=1, keepdims=True)
    return buckshot_stream(
        make_stream(x), K, jax.random.key(3),
        kmeans_iters=2, impl="xla", bounded=False,
    )


def _traced(logdir: str, **job):
    """The job under the profiler, and each host thread's span names."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(logdir, profiler_options=opts):
        result = _job(**job)
        jax.block_until_ready(result.kmeans.centers)
    (path,) = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"), recursive=True)
    threads = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans = [
                    (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events
                    if e.name.startswith(("stream.", "kmeans.", "buckshot."))
                ]
                if spans:
                    threads.append(spans)
    return result, threads


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    with pytest.MonkeyPatch.context() as mp:
        mp.delenv("REPRO_STREAM_PREFETCH", raising=False)
        return _traced(str(tmp_path_factory.mktemp("trace")))


@pytest.fixture(scope="module")
def traced_inline(tmp_path_factory):
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("REPRO_STREAM_PREFETCH", "0")
        return _traced(str(tmp_path_factory.mktemp("trace_inline")))


@pytest.fixture(scope="module")
def traced_blocks(tmp_path_factory):
    with pytest.MonkeyPatch.context() as mp:
        mp.delenv("REPRO_STREAM_PREFETCH", raising=False)
        return _traced(
            str(tmp_path_factory.mktemp("trace_blocks")), make_stream=_from_blocks
        )


def _consumer(threads):
    (line,) = [t for t in threads if any(n == "stream.pass" for n, _, _ in t)]
    return line


@pytest.mark.parametrize(
    "name,count",
    [
        ("stream.pass", PASSES),
        ("stream.h2d", PASSES * N_CHUNKS),
        # one per take, the last of a pass finding the source exhausted
        ("stream.wait", PASSES * (N_CHUNKS + 1)),
        ("stream.produce", PASSES * (N_CHUNKS + 1)),
        # the final pass's collects, two center movements, the sample's indices
        ("stream.sync", N_CHUNKS + 3),
        ("buckshot.phase1", 1),
        ("kmeans.update", 2),
        # the 88-row tail, padded once when the stream is built
        ("stream.pad", 1),
    ],
)
def test_span_count(traced, name, count):
    _, threads = traced
    assert sum(n == name for t in threads for n, _, _ in t) == count


def test_produce_spans_run_on_the_producer_thread(traced):
    _, threads = traced
    consumer = _consumer(threads)
    assert not any(n == "stream.produce" for n, _, _ in consumer)
    producers = [t for t in threads if t is not consumer]
    assert {n for t in producers for n, _, _ in t} == {"stream.produce"}


def test_from_array_pads_once_when_the_stream_is_built(traced):
    consumer = _consumer(traced[1])
    first_pass = min(s for n, s, _ in consumer if n == "stream.pass")
    (pad,) = [(s, e) for n, s, e in consumer if n == "stream.pad"]
    assert pad[1] <= first_pass


def test_regenerating_source_pads_once_per_pass_on_the_producer(
    traced, traced_blocks
):
    result, threads = traced_blocks
    consumer = _consumer(threads)
    assert not any(n == "stream.pad" for n, _, _ in consumer)
    producers = [t for t in threads if t is not consumer]
    pads = [(s, e) for t in producers for n, s, e in t if n == "stream.pad"]
    produces = [(s, e) for t in producers for n, s, e in t if n == "stream.produce"]
    assert len(pads) == PASSES
    assert all(any(a <= s and e <= b for a, b in produces) for s, e in pads)
    want = traced[0]
    np.testing.assert_array_equal(
        np.asarray(result.kmeans.centers), np.asarray(want.kmeans.centers)
    )
    np.testing.assert_array_equal(result.kmeans.assignment, want.kmeans.assignment)


def test_consumer_spans_do_not_overlap(traced):
    """h2d, wait and sync run one after another on the consumer thread, each
    inside a pass, so the idle time under each can be added up."""
    consumer = _consumer(traced[1])
    passes = [(s, e) for n, s, e in consumer if n == "stream.pass"]
    parts = sorted(
        (s, e) for n, s, e in consumer
        if n in ("stream.h2d", "stream.wait", "stream.sync")
    )
    assert all(e0 <= s1 for (_, e0), (s1, _) in zip(parts, parts[1:]))
    for n, s, e in consumer:
        if n in ("stream.h2d", "stream.wait"):
            assert any(a <= s and e <= b for a, b in passes)


def test_without_prefetch_produce_runs_inside_wait(traced_inline):
    _, threads = traced_inline
    assert len(threads) == 1
    (consumer,) = threads
    waits = [(s, e) for n, s, e in consumer if n == "stream.wait"]
    produces = [(s, e) for n, s, e in consumer if n == "stream.produce"]
    assert len(produces) == len(waits) == PASSES * (N_CHUNKS + 1)
    assert all(any(a <= s and e <= b for a, b in waits) for s, e in produces)


def test_spans_leave_the_answer_unchanged(traced, monkeypatch):
    monkeypatch.setenv("REPRO_STREAM_PREFETCH", "0")
    want = _job()
    got = traced[0]
    np.testing.assert_array_equal(
        np.asarray(got.kmeans.centers), np.asarray(want.kmeans.centers)
    )
    np.testing.assert_array_equal(got.kmeans.assignment, want.kmeans.assignment)
    np.testing.assert_array_equal(
        np.asarray(got.sample_idx), np.asarray(want.sample_idx)
    )
