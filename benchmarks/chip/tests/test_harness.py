"""CPU tests of the benchmark harness.

    python -m pytest -q benchmarks/chip/tests

Covered: every cell of BENCHMARK.json resolves to its files, and every
fault its entry names can be planted; a cell added as new files alone runs;
names and units keep to the allowed characters; the work formulas match hand
counts; the trace reductions and the roofline of one or of a cycle of call
shapes give known answers on a small recorded trace; the stall record
catches a job over its threshold with its stack, counts a collection in its
own job alone, and leaves the window's job times as they were; a run
without a TPU exits non-zero; a rehearsal with the timed path broken underneath reads a
number over its limit; and the control, the reference one precision step
down in the program's place, reads over a limit.
"""

from __future__ import annotations

import filecmp
import gc
import importlib
import json
import math
import os
import re
import shutil
import statistics
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
ROOT = BENCH.parents[1]
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402
import traces  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in SPEC["workloads"]]


def _entry(cell):
    return importlib.import_module(f"entries.{harness.Cell(SPEC, cell).traffic['entry']}")


# ------------------------------------------------------------------- the spec


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["benchmarks/chip"]
    assert 1 <= SPEC["run_seconds"] <= 51
    assert len(json.dumps(SPEC)) < 64 * 1024


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_its_files(cell):
    c = harness.Cell(SPEC, cell)
    assert c.cfg["n"] > 0 and c.cfg["k"] > 0
    assert (BENCH / "entries" / f"{c.traffic['entry']}.py").is_file()
    assert (BENCH / "kinds" / f"{c.traffic['kind']}.py").is_file()
    assert (BENCH / "corpora" / f"{c.cfg['corpus']['generator']}.py").is_file()
    entry = _entry(cell)
    assert isinstance(entry.HOST_CORPUS, bool) and callable(entry.make)
    assert entry.FAULTS, "every entry names the faults valid for its jobs"
    # each planter replaces its function of the program in a process of its own
    r = _run(["-c", PLANT, c.traffic["entry"], *entry.FAULTS])
    assert r.returncode == 0, r.stderr[-3000:]
    assert any(m["name"] == c.metric for m in SPEC["end_to_end"])
    assert c.per_layer, "every cell reports a per-layer metric"
    for m in c.per_layer:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
    for name in ("center_gap", "assign_gap", "rss_gap", "sim_gap"):
        assert name in c.traffic["limits"]


def test_every_name_and_unit_is_allowed():
    named = SPEC["configs"] + SPEC["workloads"] + SPEC["end_to_end"] + SPEC["per_layer"]
    names = [x["name"] for x in named]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for w in SPEC["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for c in SPEC["configs"]:
        for key in c["reduced"]:
            assert NAME.match(key)
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["reduced"] == c["reduced"]
    layers = {m["layer"] for m in SPEC["per_layer"]}
    assert all(1 <= len(x) <= 200 for x in layers)
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    assert all(m["moves"] in e2e for m in SPEC["per_layer"])


def test_roofline_metrics_name_their_kernel():
    for m in SPEC["per_layer"]:
        if m["name"].endswith("_roofline"):
            kernel = m["name"][: -len("_roofline")]
            assert (BENCH / "work" / f"{kernel}.py").is_file()
            assert m["unit"] == "%"


# -------------------------------------------------------------------- work


def test_assign_stats_work_by_hand():
    work = harness.load_module(BENCH / "work" / "assign_stats.py").work
    flops, nbytes = work(rows=3, k=2, d=4)
    # similarities 2*3*2*4 = 48, sums 3*4 = 12, squared norms 2*3*4 = 24
    assert flops == 84
    # x 12, centers 8, idx+sim 6, sums 8, counts+min+sumsq 6 values of 4 bytes
    assert nbytes == 4 * 40


def test_sim_best_edge_work_by_hand():
    work = harness.load_module(BENCH / "work" / "sim_best_edge.py").work
    flops, nbytes = work(rows=2, cols=3, d=5)
    assert flops == 2 * 2 * 3 * 5
    # rows 10 + cols 15 values, labels 5, index + similarity per row 4
    assert nbytes == 4 * 34


# ------------------------------------------------------------------ traces


def _trace(ops, window=(0, 100)):
    return traces.Trace(ops=ops, host=[("PjitFunction(step)", 40, 70)], window=window)


def _recorded_trace():
    rec = json.loads((HERE / "recorded_trace.json").read_text())
    return traces.Trace(ops={0: [tuple(e) for e in rec["ops"]["0"]]},
                        host=[tuple(h) for h in rec["host"]], window=tuple(rec["window"]))


def _ctx(trace, flops_per_s, bytes_per_s):
    ctx = harness.Ctx.__new__(harness.Ctx)
    ctx.trace, ctx.peaks = trace, {"flops_bf16": flops_per_s, "hbm_bytes_per_s": bytes_per_s}
    return ctx


def test_union_and_subtract():
    assert traces.union([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3), (5, 9)]
    assert traces.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert traces.subtract([(0, 4), (6, 8)], [(3, 7)]) == [(0, 3), (7, 8)]


def test_idle_and_roofline_on_known_trace():
    t = _trace({0: [("assign_stats_pallas.3", 10, 30), ("fusion.1", 20, 40),
                    ("assign_stats_pallas.3", 60, 80), ("copy", 95, 120)]})
    # busy: [10, 40) + [60, 80) + [95, 100) = 55 of 100
    assert traces.busy_share(t) == pytest.approx(0.55)
    assert traces.kernel_calls(t, "assign_stats_pallas") == [20, 20]
    ctx = _ctx(t, 1e9, 1e9)
    # least time max(10/1e9, 5/1e9) s = 10 ns a call, against 20 ns a call
    assert ctx.roofline("assign_stats_pallas", [(10.0, 5.0)]) == pytest.approx(50.0)
    assert ctx.roofline("sim_best_edge_pallas", [(10.0, 5.0)]) is None
    gaps = traces.breakdown(t)["idle_gaps"]
    assert gaps[0] == ["PjitFunction(step)", 20e-9]


def test_reduction_on_recorded_chip_trace():
    """300 ms of the traced window of a one-chip paper1gb-buckshot run, as
    the TPU trace names its operations (names cut to 100 characters).
    Busy time is checked against a sweep over every event boundary, and the
    kernels' calls against a count of their HLO names."""
    t = _recorded_trace()
    ops = t.ops[0]
    lo, hi = t.window
    cuts = sorted({lo, hi, *(min(max(s, lo), hi) for _, s, e in ops),
                   *(min(max(e, lo), hi) for _, s, e in ops)})
    starts = sorted((s, e) for _, s, e in ops)
    busy = 0
    for a, b in zip(cuts, cuts[1:]):
        if any(s <= a and e >= b for s, e in starts if s <= a):
            busy += b - a
    assert traces.busy_share(t) == pytest.approx(busy / (hi - lo), rel=1e-12)
    for kernel in ("assign_stats_pallas", "sim_best_edge_pallas"):
        pattern = re.compile(rf"^%{kernel}(\.\d+)* = ")
        want = sum(1 for n, s, e in ops if pattern.match(n) and s >= lo and e <= hi)
        assert want > 0
        assert len(traces.kernel_calls(t, kernel)) == want
    work = harness.load_module(BENCH / "work" / "sim_best_edge.py").work
    flops, nbytes = work(rows=10_000, cols=10_000, d=2048)
    ctx = _ctx(t, 197e12, 819e9)
    calls = traces.kernel_calls(t, "sim_best_edge_pallas")
    want = 100 * (flops / 197e12) / (sum(calls) / len(calls) / 1e9)
    assert ctx.roofline("sim_best_edge_pallas", [(flops, nbytes)]) == pytest.approx(want)
    assert 0 < want < 100


@pytest.mark.parametrize("kernel,work,shape", [
    ("assign_stats_pallas", "assign_stats", {"rows": 250_000, "k": 400, "d": 2048}),
    ("sim_best_edge_pallas", "sim_best_edge", {"rows": 10_000, "cols": 10_000, "d": 2048}),
])
def test_roofline_of_one_work_reads_as_every_call_alike(kernel, work, shape):
    """A cycle of one work reads, to the last digit, what the share read when
    every call of the kernel was given the same work."""
    flops, nbytes = harness.load_module(BENCH / "work" / f"{work}.py").work(**shape)
    ctx = _ctx(_recorded_trace(), 197e12, 819e9)
    calls = traces.kernel_calls(ctx.trace, kernel)
    least = max(flops / 197e12, nbytes / 819e9)
    assert ctx.roofline(kernel, [(flops, nbytes)]) == 100.0 * len(calls) * least * 1e9 / sum(calls)


def test_roofline_of_a_two_shape_cycle_by_hand():
    """Calls alternate between 800 centers and 400, as a BKC job's do."""
    t = _trace({0: [("assign_stats_pallas.1", 0, 40), ("assign_stats_pallas.2", 40, 60),
                    ("assign_stats_pallas.1", 60, 100), ("assign_stats_pallas.2", 100, 120)]},
               window=(0, 120))
    ctx = _ctx(t, 1e9, 1e9)
    # least times: max(30, 6) = 30 ns and max(10, 12) = 12 ns, over 2 cycles
    # against 40 + 20 + 40 + 20 = 120 ns of device time
    share = ctx.roofline("assign_stats_pallas", [(30.0, 6.0), (10.0, 12.0)])
    assert share == pytest.approx(100.0 * 2 * (30 + 12) / 120)


@pytest.mark.parametrize("calls,cycle", [(3, 2), (4, 3), (1, 2)])
def test_roofline_of_a_partial_cycle_reads_none(calls, cycle):
    t = _trace({0: [("assign_stats_pallas", 10 * i, 10 * i + 5) for i in range(calls)]})
    ctx = _ctx(t, 1e9, 1e9)
    assert ctx.roofline("assign_stats_pallas", [(1.0, 1.0)] * cycle) is None
    assert ctx.roofline("assign_stats_pallas", [(1.0, 1.0)]) is not None


# ------------------------------------------------------------------ stalls


def _stuck_in_a_job(seconds):
    import time

    time.sleep(seconds)


def _sleepy_run(stall_at=None, job=0.01, stall=0.4):
    """A job of ``job`` seconds, and one of ``stall`` at job ``stall_at``."""
    count = [0]

    def run(key):
        _stuck_in_a_job(stall if count[0] == stall_at else job)
        count[0] += 1
        return {"j": count[0] - 1}

    return run


def _keys(j):
    return j


def test_one_stall_line_with_its_job_and_a_stack(capfd):
    import numpy as np

    stalls = harness.Stalls(0.15)
    _, jobs, _, times = harness.window(_sleepy_run(stall_at=2), _keys, 0.5,
                                       np.random.default_rng(0), stalls)
    err = capfd.readouterr().err
    lines = [x for x in err.splitlines() if x.startswith("stall ")]
    assert len(lines) == 1, err
    assert lines[0].startswith("stall j=2 job_s=")
    for field in ("gc_s=", "utime_s=", "stime_s=", "nivcsw=", "majflt=", "hbm_in_use=none",
                  "largest_free=none", "loadavg=", "psi="):
        assert f" {field}" in lines[0], field
    # the watchdog's dump, written while the job was still asleep, before its line
    assert "most recent call first" in err
    assert err.index("in _stuck_in_a_job") < err.index("stall j=2 ")
    assert [r["j"] for r in stalls.records] == [2] and jobs > 3
    summary = dict(f.split("=") for f in stalls.summary(times).split())
    assert summary["stalls"] == "1"
    assert float(summary["stall_excess_s"]) == times[2] - statistics.median(times)


def test_jobs_under_the_threshold_leave_no_stall(capfd):
    import numpy as np

    stalls = harness.Stalls(0.15)
    _, jobs, _, times = harness.window(_sleepy_run(), _keys, 0.3,
                                       np.random.default_rng(0), stalls)
    err = capfd.readouterr().err
    assert jobs > 5 and max(times) < 0.15
    assert "stall" not in err and "most recent call first" not in err
    assert stalls.records == [] and stalls.summary(times).startswith("stalls=0 stall_excess_s=0")
    # the watchdog is disarmed and the hook gone once the window has closed
    assert stalls._gc_clock not in gc.callbacks
    assert signal_timer_left() == 0.0


def signal_timer_left():
    import signal

    return signal.getitimer(signal.ITIMER_REAL)[0]


def test_forced_collection_counts_in_its_job_alone():
    import numpy as np

    def run(key):
        if key == 3:
            for _ in range(20_000):  # cycles that only a collection frees
                a = []
                a.append(a)
            gc.collect()
        return key

    was = gc.isenabled()
    gc.disable()  # no collection but the forced one
    try:
        stalls = harness.Stalls(10.0)
        _, jobs, _, _ = harness.window(run, _keys, 0.05, np.random.default_rng(0), stalls)
    finally:
        if was:
            gc.enable()
    assert jobs > 4 and len(stalls.gc_s) == jobs
    assert stalls.gc_s[3] > 0
    assert all(s == 0.0 for j, s in enumerate(stalls.gc_s) if j != 3)
    totals = dict(f.split("=") for f in stalls.totals().split())
    assert float(totals["gc_s"]) == stalls.gc_s[3]
    assert totals["gc_collections"] == "0,0,1"


def _window_without_probes(run, keys, seconds, rng):
    """The window as it was before the stall record, verbatim."""
    import time

    kept: list = []
    times: list = []
    j = 0
    t0 = time.perf_counter()
    while True:
        t_job = time.perf_counter()
        out = run(keys(j))
        times.append(time.perf_counter() - t_job)
        if len(kept) < harness.CHECKED_JOBS:
            kept.append((j, out))
        else:
            r = int(rng.integers(0, j + 1))
            if r < harness.CHECKED_JOBS:
                kept[r] = (j, out)
        j += 1
        if time.perf_counter() - t0 >= seconds:
            break
    return time.perf_counter() - t0, j, kept, times


def test_window_times_and_job_s_unchanged_by_the_probes(monkeypatch):
    """On a clock that moves only inside a job, the window reads the same
    elapsed time, jobs, kept answers and job times as it did before the
    stall record, and elapsed / jobs is the job's time."""
    import numpy as np

    clock = [0.0]
    monkeypatch.setattr(harness.time, "perf_counter", lambda: clock[0])

    def run(key):
        clock[0] += 0.01
        return key

    clock[0] = 0.0
    before = _window_without_probes(run, _keys, 0.095, np.random.default_rng(7))
    clock[0] = 0.0
    stalls = harness.Stalls(0.5)
    assert harness.window(run, _keys, 0.095, np.random.default_rng(7), stalls) == before
    elapsed, jobs, _, times = before
    assert jobs == 10 and times == pytest.approx([0.01] * 10)
    assert elapsed / jobs == pytest.approx(0.01)
    assert stalls.records == [] and len(stalls.gc_s) == jobs


# ------------------------------------------------------------------- runs


def _run(args, env_extra=None, timeout=600):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env_extra or {})}
    return subprocess.run(
        [sys.executable, *args], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=timeout,
    )


def test_no_tpu_exits_nonzero_and_prints_no_result():
    r = _run(["benchmarks/chip/run.py", "--workload", CELLS[0], "--seed", "1",
              "--seconds", "1", "--trace", "0"])
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no TPU" in r.stderr


PLANT = """
import sys
sys.path[:0] = ["benchmarks/chip", "src"]
import faults
for name in sys.argv[2:]:
    faults.plant(sys.argv[1], name)
"""
FAULT_RUNNER = """
import sys
sys.path.insert(0, "benchmarks/chip")
sys.path.insert(0, "src")
import faults, harness
fault = {fault!r}
if fault:
    faults.plant({entry!r}, fault)
raise SystemExit(harness.main(sys.argv[1:]))
"""
CASES = [(c, f) for c in CELLS for f in [None, *_entry(c).FAULTS]]


def _over_limit(cell, fault):
    entry = harness.Cell(SPEC, cell).traffic["entry"]
    runner = FAULT_RUNNER.format(fault=fault, entry=entry)
    r = _run(["-c", runner, "--workload", cell,
              "--seed", "2147483659", "--seconds", "0.5", "--trace", "0",
              "--rehearse"], timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    res = json.loads(r.stdout.strip().splitlines()[-1])
    assert res["correct"] is False  # a rehearsal never is
    assert set(res["checks"]) == set(harness.Cell(SPEC, cell).traffic["limits"])
    return res["failed"], [n for n, c in res["checks"].items() if not c["value"] <= c["limit"]]


@pytest.mark.parametrize("cell,fault", CASES)
def test_broken_timed_path_is_not_correct(cell, fault):
    failed, over = _over_limit(cell, fault)
    if fault is None:
        assert failed == 0 and over == [], f"a sound rehearsal read over a limit: {over}"
    else:
        assert failed > 0 and over, f"{fault} left every number of {cell} within its limit"


@pytest.mark.parametrize("cell", CELLS)
def test_control_reads_over_a_limit(cell):
    """The reference put in the program's place one precision step down
    fails on every seed, at a size a test run holds."""
    r = _run(["benchmarks/chip/control.py", "--workload", cell, "--seeds",
              "11,12,13", "--side", "control", "--rehearse"], timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    limits = harness.Cell(SPEC, cell).traffic["limits"]
    for line in r.stdout.strip().splitlines():
        got = json.loads(line)
        assert got["correct"] is False
        assert any(not v <= limits[n] for n, v in got["numbers"].items()), got


def test_seed_beyond_32_bits_gives_distinct_keys():
    import jax

    a = harness.base_key(2**31 + 5)
    b = harness.base_key(5)
    assert not bool((jax.random.key_data(a) == jax.random.key_data(b)).all())
    assert math.isfinite(float(jax.random.uniform(harness.base_key(2**33 + 1))))


TOY_ENTRY = '''"""One K-Means iteration a job, under names of its own."""

import faults

HOST_CORPUS = False


def make(cell, x, impl, devices):
    import jax

    from repro.core import kmeans

    k, iters = cell.cfg["k"], cell.traffic["iters"]
    fn = jax.jit(lambda x, key: kmeans(x, k, key, max_iters=iters, tol=0.0, **impl))

    def run(key):
        r = jax.block_until_ready(fn(x, key))
        return {"centers": r.centers, "assignment": r.assignment, "rss": r.rss,
                "best_sim": r.best_sim}

    return run


FAULTS = {"step_unchanged": faults.step_unchanged}
'''
TOY_KIND = '''"""Checked as a K-Means job is."""

from kinds.kmeans import numbers, run  # noqa: F401
'''


def test_cell_added_as_files_alone_runs(tmp_path):
    """A copy of the benchmark takes a new cell as new files and one entry of
    ``workloads``: an entry, a kind and a traffic mix of its own. Its
    rehearsal reads every check within its limit, and no file that was
    there before differs afterwards."""
    bench = tmp_path / "benchmarks" / "chip"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns("__pycache__"))
    limits = json.loads((BENCH / "traffic" / "kmeans-resident.json").read_text())["limits"]
    added = {
        "entries/toy_one_step.py": TOY_ENTRY,
        "kinds/toy_one_step.py": TOY_KIND,
        "traffic/toy-one-step.json": json.dumps(
            {"entry": "toy_one_step", "kind": "toy_one_step", "iters": 1,
             "metric": "job_s", "limits": limits}),
    }
    for rel, text in added.items():
        assert not (bench / rel).exists()
        (bench / rel).write_text(text)
    toy = {"name": "toy-one-step", "config": SPEC["configs"][0]["name"],
           "traffic": "toy-one-step", "chips": 1, "why": "one K-Means iteration a job"}
    spec = {**SPEC, "workloads": [*SPEC["workloads"], toy]}
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(ROOT / "src")}
    r = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", "toy-one-step",
         "--seed", "2147483659", "--seconds", "0.5", "--trace", "0", "--rehearse"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=600,
    )
    assert r.returncode == 0, r.stderr[-3000:]
    res = json.loads(r.stdout.strip().splitlines()[-1])
    assert res["rehearsal"] is True and res["failed"] == 0
    # the stall record is on stderr, and nothing of it in the result line
    assert re.search(r"^window jobs=\d+ .*compiles_in_window=0 stalls=\d+ stall_excess_s=",
                     r.stderr, re.M), r.stderr[-3000:]
    assert re.search(r"^window gc_s=\S+ gc_collections=\d+,\d+,\d+ utime_s=", r.stderr, re.M)
    assert "stall" not in r.stdout
    assert set(res["metrics"]) == {"setup_s", "job_s"}
    assert set(res["checks"]) == set(limits)
    assert all(c["value"] <= c["limit"] for c in res["checks"].values()), res["checks"]

    def files(top):
        return {str(f.relative_to(top)) for f in top.rglob("*")
                if f.is_file() and "__pycache__" not in f.parts}

    assert files(bench) == files(BENCH) | set(added)
    for rel in files(BENCH):
        assert filecmp.cmp(BENCH / rel, bench / rel, shallow=False), rel
