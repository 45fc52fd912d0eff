"""One run of one cell: set-up, the measured window, the checks, the line.

Every part of a cell is a file of its own, found by its name, so that a new
deployment is added as files alone and no file here names one:

- ``BENCHMARK.json`` names the cell's configuration and traffic mix;
- ``configs/<config>.json``: the deployment (sizes, precision, and a
  ``corpus`` block that names its ``generator``);
- ``corpora/<generator>.py``: ``make(key, corpus, n, d)`` gives the corpus
  on the device from the seed;
- ``traffic/<mix>.json``: the ``entry`` of the program that a job calls and
  the ``kind`` of reference that checks it, with their parameters, the
  end-to-end metric its jobs report, and the limit of each number that the
  comparison with the reference reads;
- ``entries/<entry>.py``: ``make(cell, corpus, impl, devices)`` gives
  ``run(key)``, one timed job, which returns its answers by name;
  ``HOST_CORPUS`` says whether it takes the corpus in host memory; and
  ``FAULTS`` names the faults valid for its jobs (``faults.py``);
- ``kinds/<kind>.py``: the plain reference of such a job and the numbers
  that compare a job's answers with it (``reference.py``);
- ``metrics/<metric>.py``: ``read(ctx)`` gives the metric from the trace, or
  None where the trace holds nothing for it;
- ``work/<kernel>.py``: ``work(**shape)`` gives (FLOPs, bytes) that the
  kernel's call needs, from its logical shape.

Configurations, corpora, entries and kinds may be shared by several cells:
a cell is one pair of configuration and traffic mix.

Every window keeps a stall record (``Stalls``), on stderr only: per job the
process's CPU seconds, context switches, page faults and seconds of garbage
collection, and a watchdog that dumps every Python thread's stack while a
job over its threshold (3 x a warm job, at least 0.5 s) is still stuck. Each
such job leaves one ``stall`` line, with the device's allocator and the
host's load and pressure read as it ends; the ``window`` lines give how many
there were and their excess over the median job. Nothing of it reaches the
result line or its metrics.
"""

from __future__ import annotations

import faulthandler
import gc
import importlib
import importlib.util
import json
import math
import os
import resource
import shutil
import signal
import statistics
import sys
import tempfile
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]

# a traced window is a few seconds of jobs: traces are large and tracing
# slows the host, so the per-layer metrics read a window of their own
TRACE_SECONDS = 8.0
CHECKED_JOBS = 2  # jobs of the window, drawn from the seed, that are checked
STALL_MIN_S = 0.5  # the watchdog's threshold is 3 x a warm job, never under this


class Cell:
    def __init__(self, spec: dict, name: str, rehearse: bool = False):
        cells = {w["name"]: w for w in spec["workloads"]}
        if name not in cells:
            raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
        w = cells[name]
        conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
        self.name = name
        self.chips = int(w["chips"])
        self.cfg = json.loads((ROOT / conf["file"]).read_text())
        self.traffic = json.loads((HERE / "traffic" / f"{w['traffic']}.json").read_text())
        if rehearse:
            self.cfg = {**self.cfg, **self.cfg.get("rehearse", {})}
            self.traffic = {**self.traffic, **self.traffic.get("rehearse", {})}
        self.metric = self.traffic["metric"]
        self.per_layer = [
            m for m in spec["per_layer"]
            if (name in m["workloads"] if "workloads" in m else m["moves"] == self.metric)
        ]

    def params(self) -> dict:
        """What the reference needs to know of one job: the configuration's
        keys and the traffic's, which win on a clash, with ``s`` for the
        sample size."""
        cfg = {n: v for n, v in self.cfg.items() if n != "rehearse"}
        traffic = {n: v for n, v in self.traffic.items() if n != "rehearse"}
        return {**cfg, "s": self.cfg.get("sample_size"), **traffic}


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def base_key(seed: int):
    """A key for any whole number: JAX's seed holds 32 bits, the rest is
    folded in."""
    import jax

    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


# ------------------------------------------------------------------- set-up


class Setup:
    """The data on the device and the job that the window drives."""

    def __init__(self, cell: Cell, key, rehearse: bool):
        import jax

        cfg, t = cell.cfg, cell.traffic
        impl = {"impl": "pallas_interpret"} if rehearse else {}
        self.kind = t["kind"]
        corpus = importlib.import_module(f"corpora.{cfg['corpus']['generator']}")
        entry = importlib.import_module(f"entries.{t['entry']}")
        t0 = time.perf_counter()
        self.x = jax.block_until_ready(corpus.make(key, cfg["corpus"], cfg["n"], cfg["d"]))
        self.corpus_s = time.perf_counter() - t0
        self.x_host = None
        if entry.HOST_CORPUS:
            t0 = time.perf_counter()
            self.x_host = jax.device_get(self.x)
            self.x.delete()
            self.x = None
            self.to_host_s = time.perf_counter() - t0
        devices = jax.devices()[: cell.chips]
        self.run = entry.make(cell, self.x_host if entry.HOST_CORPUS else self.x, impl, devices)

    def data(self):
        """The corpus on the device, for the reference."""
        import jax

        if self.x is None:
            self.x = jax.device_put(self.x_host, jax.devices()[0])
        return self.x


# ------------------------------------------------------------------ compiles


class CompileCounter:
    """Counts programs compiled or loaded from the cache while ``on``."""

    def __init__(self):
        import jax

        self.on = False
        self.count = 0
        self.hits = 0
        self.misses = 0
        self.names: list[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration" and self.on:
            self.count += 1
            self.names.append(str(kw.get("fun_name", "?")))

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


# ------------------------------------------------------------------- the run


class JobKeys:
    """Job j's key is fold_in(fold_in(seed key, 1), j). The first ``count``
    are made in one call at set-up and held on the host, so the window
    dispatches nothing of its own between jobs."""

    def __init__(self, seed_key, count: int = 1 << 16):
        import jax
        import jax.numpy as jnp

        self.base = jax.random.fold_in(seed_key, 1)
        self.held = jax.device_get(
            jax.vmap(lambda j: jax.random.fold_in(self.base, j))(
                jnp.arange(count, dtype=jnp.uint32)
            )
        )

    def __call__(self, j: int):
        import jax

        if j < len(self.held):
            return self.held[j]
        return jax.random.fold_in(self.base, j)


class Stalls:
    """The stall record of one window, on stderr only: nothing of it reaches
    the result line or its metrics.

    Around every job, at two system calls a job: ``getrusage(RUSAGE_SELF)``
    as it ends, whose difference from the reading before it (the end of the
    job before, or the window's start) gives the job's user and system CPU
    seconds, voluntary and involuntary context switches, and minor and major
    page faults; the seconds spent in garbage collection, by generation,
    from a ``gc.callbacks`` hook that the window registers once; and a
    watchdog, the process's real-time interval timer with ``faulthandler``
    registered on its SIGALRM. The timer is set to ``threshold`` as each
    job starts, which also cancels the job before's, and is cancelled when
    the window closes. A job that passes ``threshold`` seconds (with the
    window's few microseconds of bookkeeping after it) dumps every Python
    thread's stack to stderr while it is still stuck, and once it ends
    leaves one line::

        stall j= job_s= threshold_s= gc_s= gc_s_by_gen= utime_s= stime_s=
          nvcsw= nivcsw= minflt= majflt= hbm_in_use= num_allocs=
          largest_free= loadavg= psi=

    with the device's ``memory_stats()`` and the host's ``/proc/loadavg``
    and ``/proc/pressure/{cpu,io,memory}`` (``some``/``full`` as
    avg10/total microseconds) read as it ends; what cannot be read is
    ``none``. ``gc_s`` keeps each job's seconds of collection.
    """

    def __init__(self, threshold: float, device=None):
        self.threshold = threshold
        self.device = device
        self.gc_s: list[float] = []
        self.records: list[dict] = []
        self._gc_by_gen = [0.0, 0.0, 0.0]
        self._gc_began = 0.0

    def _gc_clock(self, phase, info):
        if phase == "start":
            self._gc_began = time.perf_counter()
        else:
            self._gc_by_gen[info["generation"]] += time.perf_counter() - self._gc_began

    def open(self):
        gc.callbacks.append(self._gc_clock)
        faulthandler.register(signal.SIGALRM, file=sys.stderr.fileno(), all_threads=True)
        self._gc_open = [s["collections"] for s in gc.get_stats()]
        self._ru_open = self._ru = resource.getrusage(resource.RUSAGE_SELF)

    def close(self):
        signal.setitimer(signal.ITIMER_REAL, 0)
        faulthandler.unregister(signal.SIGALRM)
        gc.callbacks.remove(self._gc_clock)
        self._ru_close = resource.getrusage(resource.RUSAGE_SELF)
        self._gc_close = [s["collections"] for s in gc.get_stats()]

    def arm(self):
        self._gc0, self._gc1, self._gc2 = self._gc_by_gen
        signal.setitimer(signal.ITIMER_REAL, self.threshold)

    def ended(self, j: int, job_s: float):
        ru = resource.getrusage(resource.RUSAGE_SELF)
        g0, g1, g2 = self._gc_by_gen
        self.gc_s.append((g0 - self._gc0) + (g1 - self._gc1) + (g2 - self._gc2))
        if job_s > self.threshold:
            self._stall(j, job_s, (g0 - self._gc0, g1 - self._gc1, g2 - self._gc2), ru)
        self._ru = ru

    def _stall(self, j, job_s, gc_by_gen, ru):
        rec = {"j": j, "job_s": job_s, "threshold_s": self.threshold,
               "gc_s": sum(gc_by_gen), "gc_s_by_gen": ",".join(map(repr, gc_by_gen)),
               **_rusage_delta(self._ru, ru)}
        stats = {}
        if self.device is not None:
            stats = self.device.memory_stats() or {}
        rec["hbm_in_use"] = stats.get("bytes_in_use")
        rec["num_allocs"] = stats.get("num_allocs")
        rec["largest_free"] = stats.get("largest_free_block_bytes")
        rec["loadavg"] = _loadavg()
        rec["psi"] = _pressure()
        self.records.append(rec)
        say("stall " + _fields(rec))

    def summary(self, times) -> str:
        """How many jobs stalled and their excess over the median job."""
        median = statistics.median(times)
        excess = sum(r["job_s"] - median for r in self.records)
        return (f"stalls={len(self.records)} stall_excess_s={excess!r}"
                f" stall_threshold_s={self.threshold!r}")

    def totals(self) -> str:
        """The window's whole: seconds of collection, in jobs and between
        them, collections by generation, and the getrusage deltas."""
        runs = [b - a for a, b in zip(self._gc_open, self._gc_close)]
        return _fields({"gc_s": sum(self._gc_by_gen), "gc_collections": ",".join(map(str, runs)),
                        **_rusage_delta(self._ru_open, self._ru_close)})


def _rusage_delta(a, b) -> dict:
    return {"utime_s": b.ru_utime - a.ru_utime, "stime_s": b.ru_stime - a.ru_stime,
            "nvcsw": b.ru_nvcsw - a.ru_nvcsw, "nivcsw": b.ru_nivcsw - a.ru_nivcsw,
            "minflt": b.ru_minflt - a.ru_minflt, "majflt": b.ru_majflt - a.ru_majflt}


def _fields(rec: dict) -> str:
    def text(v):
        return "none" if v is None else v if isinstance(v, str) else repr(v)

    return " ".join(f"{n}={text(v)}" for n, v in rec.items())


def _loadavg() -> str | None:
    try:
        return ",".join(Path("/proc/loadavg").read_text().split()[:4])
    except OSError:
        return None


def _pressure() -> str | None:
    """``cpu.some=<avg10>/<total us>;io.some=...;io.full=...`` from
    ``/proc/pressure``, read only."""
    parts = []
    for res in ("cpu", "io", "memory"):
        try:
            lines = Path(f"/proc/pressure/{res}").read_text().splitlines()
        except OSError:
            continue
        for line in lines:
            kind, *kv = line.split()
            v = dict(x.split("=", 1) for x in kv)
            parts.append(f"{res}.{kind}={v.get('avg10')}/{v.get('total')}")
    return ";".join(parts) or None


def window(run, keys, seconds: float, rng, stalls: Stalls):
    """Jobs back to back until ``seconds`` have passed. Keeps the answers of
    CHECKED_JOBS jobs drawn uniformly by ``rng`` (a reservoir), and each
    job's time; ``elapsed / jobs`` is the cell's metric.

    Every job runs under the probes of ``stalls``: a job over its threshold
    dumps every Python thread's stack to stderr while it is still stuck and
    leaves one ``stall`` line there once it ends. The record goes to stderr
    only; the job times and the answers are what they were without it."""
    kept: list = []
    times: list = []
    j = 0
    stalls.open()
    try:
        t0 = time.perf_counter()
        while True:
            stalls.arm()
            t_job = time.perf_counter()
            out = run(keys(j))
            job_s = time.perf_counter() - t_job
            stalls.ended(j, job_s)
            times.append(job_s)
            if len(kept) < CHECKED_JOBS:
                kept.append((j, out))
            else:
                r = int(rng.integers(0, j + 1))
                if r < CHECKED_JOBS:
                    kept[r] = (j, out)
            j += 1
            if time.perf_counter() - t0 >= seconds:
                break
        elapsed = time.perf_counter() - t0
    finally:
        stalls.close()
    return elapsed, j, kept, times


def job_spread(times) -> str:
    """Per-job times on stderr, to tell a window that is slow in every job
    from one slowed by a few stalls. The stall record beside it (``Stalls``:
    the ``stall`` lines, and ``stalls=``, ``stall_excess_s=`` and the
    window's totals on the ``window`` lines) says what each job over the
    threshold was doing; it too goes to stderr only."""
    import numpy as np

    t = np.asarray(times)
    fifth = max(1, len(t) // 5)
    parts = {"job_p50": np.median(t), "job_p90": np.quantile(t, 0.9), "job_max": t.max(),
             "first_fifth": t[:fifth].mean(), "last_fifth": t[-fifth:].mean()}
    return " ".join(f"{n}={float(v)!r}" for n, v in parts.items())


def check(cell: Cell, setup: Setup, keys, kept) -> tuple[dict, int, dict]:
    """The worst of each compared number (those the traffic file gives a
    limit) over the kept jobs, each with its limit; how many kept jobs were
    out of a limit; and each job's other numbers, as diagnostics."""
    import reference

    limits = cell.traffic["limits"]
    x = setup.data()
    worst: dict[str, float] = {}
    diags: dict[str, list] = {}
    failed = 0
    for j, out in kept:
        got = reference.numbers(setup.kind, x, keys(j), out, cell.params())
        bad = any(n not in got for n in limits)  # a limit with no number fails
        for name, v in got.items():
            if name in limits:
                worst[name] = max(worst.get(name, -math.inf), v)
                bad |= not (v <= limits[name])
            else:
                diags.setdefault(name, []).append(v)
        failed += bad
    checks = {n: {"value": v, "limit": limits[n]} for n, v in worst.items()}
    return checks, failed, diags


def device_info(devices) -> dict:
    d = devices[0]
    peak = 0
    for dev in devices:
        stats = dev.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devices),
            "memory_peak_bytes": peak}


class Ctx:
    """What a per-layer metric's reader sees."""

    def __init__(self, cell: Cell, trace, peaks):
        self.cfg = cell.cfg
        self.trace = trace
        self.peaks = peaks

    def work(self, kernel: str):
        return load_module(HERE / "work" / f"{kernel}.py").work

    def roofline(self, kernel: str, works):
        """Least time over device time, in %, over the kernel's calls in the
        window. ``works`` lists the (FLOPs, bytes) of one job's calls of the
        kernel in order, call i needing ``works[i % len(works)]``: None where
        the window's calls are not whole cycles of it."""
        import traces as tr

        calls = tr.kernel_calls(self.trace, kernel)
        if not calls or self.peaks is None or len(calls) % len(works):
            return None
        least = sum(
            max(f / self.peaks["flops_bf16"], b / self.peaks["hbm_bytes_per_s"])
            for f, b in works
        )
        return 100.0 * (len(calls) // len(works)) * least * 1e9 / sum(calls)


def use_compile_cache() -> None:
    """JAX's persistent compile cache at a fixed path: the one
    ``JAX_COMPILATION_CACHE_DIR`` names, else ``<checkout>/.jax_cache``, as
    the program's own entry points use. Every program is kept, however
    fast it compiled, so that a second run compiles nothing."""
    import jax

    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def run_cell(cell: Cell, seed: int, seconds: float, traced: bool, rehearse: bool,
             t_start: float) -> dict:
    import jax
    import numpy as np

    import traces as tr
    from peaks import device_peaks

    devices = jax.devices()[: cell.chips]
    peaks = None if rehearse else device_peaks(devices[0].device_kind)
    counter = CompileCounter()
    key = base_key(seed)
    setup = Setup(cell, jax.random.fold_in(key, 0), rehearse)
    keys = JobKeys(key)
    say(f"setup corpus_s={setup.corpus_s!r}"
        + (f" to_host_s={setup.to_host_s!r}" if setup.x_host is not None else ""))
    warm_key = JobKeys(jax.random.fold_in(key, 2), 1)(0)
    t0 = time.perf_counter()
    setup.run(warm_key)  # one warm-up job: compiles, or loads from the cache
    warmup_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    setup.run(warm_key)  # and again, warm: the watchdog's yardstick
    warm_job_s = time.perf_counter() - t0
    say(f"setup warmup_s={warmup_s!r} warm_job_s={warm_job_s!r} cache_hits={counter.hits}"
        f" cache_misses={counter.misses}")
    setup_s = time.perf_counter() - t_start
    say(f"setup gc_objects={len(gc.get_objects())} gc_frozen={gc.get_freeze_count()}"
        f" gc_counts={','.join(map(str, gc.get_count()))}")
    rng = np.random.default_rng(seed)
    stalls = Stalls(max(STALL_MIN_S, 3 * warm_job_s), devices[0])

    logdir = None
    cpu0 = time.process_time()
    counter.on = True
    if traced:
        logdir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # a traced Python host runs far slower
        jax.profiler.start_trace(logdir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(tr.WINDOW):
                elapsed, jobs, kept, times = window(
                    setup.run, keys, min(seconds, TRACE_SECONDS), rng, stalls
                )
        finally:
            jax.profiler.stop_trace()
    else:
        elapsed, jobs, kept, times = window(setup.run, keys, seconds, rng, stalls)
    counter.on = False
    cpu_s = time.process_time() - cpu0
    say(f"window jobs={jobs} seconds={elapsed!r} compiles_in_window={counter.count}"
        f" {stalls.summary(times)}"
        + (f" compiled={','.join(sorted(set(counter.names)))}" if counter.names else ""))
    say(f"window {job_spread(times)} cpu_s={cpu_s!r}")
    say(f"window {stalls.totals()}")
    device = device_info(devices)

    result: dict = {"correct": False, "attempted": jobs, "failed": 0}
    if traced:
        trace = tr.load(logdir)
        shutil.rmtree(logdir, ignore_errors=True)
        ctx = Ctx(cell, trace, peaks)
        metrics = {}
        for m in cell.per_layer:
            v = load_module(HERE / "metrics" / f"{m['name']}.py").read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        share = tr.busy_share(trace)
        device["busy_s"] = (share or 0.0) * tr.window_ns(trace) / 1e9
        device["window_s"] = tr.window_ns(trace) / 1e9
        result["breakdown"] = tr.breakdown(trace)
    else:
        metrics = {
            "setup_s": {"value": setup_s, "unit": "s"},
            cell.metric: {"value": elapsed / jobs, "unit": "s"},
        }
    result["metrics"] = metrics
    result["device"] = device

    from repro.kernels import ops

    degraded = ops.pallas_degraded()
    t0 = time.perf_counter()
    setup.run = None
    checks, failed, diags = check(cell, setup, keys, kept)
    say(f"reference_s={time.perf_counter() - t0!r} checked_jobs={[j for j, _ in kept]}"
        + "".join(f" {n}={v!r}" for n, v in diags.items()))
    result["failed"] = failed
    result["correct"] = (
        failed == 0 and not degraded and not rehearse
        and all(c["value"] <= c["limit"] for c in checks.values())
    )
    if degraded:
        say("a Pallas kernel degraded to XLA: this run measured no kernel")
    if rehearse:
        result["rehearsal"] = True
    result["checks"] = checks
    for n, c in checks.items():
        say(f"check {n} value={c['value']!r} limit={c['limit']!r}")
    return result


def main(argv=None, t_start: float | None = None) -> int:
    import argparse

    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU, kernels in interpret mode;"
                    " never correct")
    args = ap.parse_args(argv)

    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = Cell(spec, args.workload, rehearse=args.rehearse)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, str(HERE))
    sys.path.insert(0, str(ROOT / "src"))

    import jax

    devices = jax.devices()
    if not args.rehearse and devices[0].platform != "tpu":
        say(f"no TPU: JAX found {devices[0].platform} devices only")
        return 2
    if len(devices) < cell.chips:
        say(f"{cell.name} needs {cell.chips} chips, JAX found {len(devices)}")
        return 2
    if not args.rehearse:
        use_compile_cache()

    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      args.rehearse, t_start)
    print(json.dumps(result), flush=True)
    return 0
