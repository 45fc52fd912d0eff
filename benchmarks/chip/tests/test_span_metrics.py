"""CPU tests of the per-layer metrics that read the program's spans.

    python -m pytest -q benchmarks/chip/tests/test_span_metrics.py

Covered: each reader's exact value on a hand-built trace; the four readers
plus the idle under a program span but under none of ``stream.h2d``,
``stream.wait`` and ``stream.sync`` add up to ``idle.stream``; a trace with no
program span reads no number; and a traced rehearsal of the stream cell
carries all four numbers in its result line.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
ROOT = BENCH.parents[1]
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402
import spans  # noqa: E402
import traces  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
STREAM = "paper1gb-buckshot-stream"
READERS = ("idle_h2d.stream", "idle_wait.stream", "idle_sync.stream",
           "idle_untraced.stream")
CONSUMER = ("stream.h2d", "stream.wait", "stream.sync")


def _read(name, trace):
    ctx = harness.Ctx.__new__(harness.Ctx)
    ctx.trace = trace
    return harness.load_module(BENCH / "metrics" / f"{name}.py").read(ctx)


# device 0 is busy in [10, 20), [50, 60), [90, 95): idle 75 of the window
BUSY = [("fusion.1", 10, 20), ("assign_stats_pallas.3", 50, 60), ("copy", 90, 95)]
HOST = [
    (traces.WINDOW, 0, 100),
    ("TransferToDevice", 2, 4),  # PJRT's own: no program span
    ("stream.produce", 0, 70),  # the producer thread, over consumer spans
    ("stream.pass", 5, 98),
    ("stream.wait", 5, 15),  # idle [5, 10)
    ("stream.h2d", 20, 30),  # idle [20, 30)
    ("stream.sync", 30, 40),  # idle [30, 45) with the next
    ("stream.sync", 40, 45),
    ("kmeans.update", 60, 80),
    ("stream.sync", 70, 75),  # idle [70, 75), inside kmeans.update
]
# idle under a program span and none of the three: [0, 5), [45, 50),
# [60, 70), [75, 90), [95, 98); under no program span: [98, 100)
WANT = {"idle_h2d.stream": 10.0, "idle_wait.stream": 5.0,
        "idle_sync.stream": 20.0, "idle_untraced.stream": 2.0}
OTHER = 38.0


def _hand_trace(devices=1):
    ops = {0: BUSY}
    if devices == 2:
        ops[1] = [("fusion.2", 0, 100)]  # never idle: every share halves
    return traces.Trace(ops=ops, host=HOST, window=(0, 100))


def _other(trace):
    """Idle under a program span and under none of the consumer spans."""
    program = spans.cover(trace, lambda n: n.startswith(spans.PROGRAM))
    consumer = spans.cover(trace, lambda n: n in CONSUMER)
    return spans.idle_share(trace, traces.subtract(program, consumer))


@pytest.mark.parametrize("devices", [1, 2])
@pytest.mark.parametrize("name", READERS)
def test_reader_on_hand_trace(name, devices):
    assert _read(name, _hand_trace(devices)) == pytest.approx(WANT[name] / devices, abs=1e-12)


@pytest.mark.parametrize("devices", [1, 2])
def test_readers_add_up_to_idle(devices):
    t = _hand_trace(devices)
    idle = _read("idle.stream", t)
    assert idle == pytest.approx(75.0 / devices, abs=1e-12)
    assert _other(t) == pytest.approx(OTHER / devices, abs=1e-12)
    got = sum(_read(n, t) for n in READERS) + _other(t)
    assert abs(got - idle) < 1e-9


def test_reader_of_a_missing_span_reads_none():
    t = _hand_trace()
    t = t._replace(host=[h for h in t.host if h[0] != "stream.h2d"])
    assert _read("idle_h2d.stream", t) is None
    assert _read("idle_wait.stream", t) == pytest.approx(5.0)


@pytest.mark.parametrize("name", READERS)
def test_trace_without_program_spans_reads_none(name):
    rec = json.loads((HERE / "recorded_trace.json").read_text())
    t = traces.Trace(ops={0: [tuple(e) for e in rec["ops"]["0"]]},
                     host=[tuple(h) for h in rec["host"]], window=tuple(rec["window"]))
    assert _read(name, t) is None


def test_stream_cell_reports_the_span_metrics():
    metrics = [m for m in harness.Cell(SPEC, STREAM).per_layer if m["name"] in READERS]
    assert sorted(m["name"] for m in metrics) == sorted(READERS)
    for m in metrics:
        assert m["source"] == "program_span" and m["unit"] == "%"
        assert m["layer"] == "stream executor" and m["moves"] == "stream_job_s"


def test_traced_rehearsal_carries_every_span_metric():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    r = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", STREAM,
         "--seed", "2147483659", "--seconds", "0.5", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900,
    )
    assert r.returncode == 0, r.stderr[-3000:]
    got = json.loads(r.stdout.strip().splitlines()[-1])["metrics"]
    for name in READERS:
        assert got[name]["unit"] == "%"
        assert 0.0 <= got[name]["value"] <= 100.0
    parts = sum(got[n]["value"] for n in READERS)
    assert parts <= got["idle.stream"]["value"] + 1e-9
