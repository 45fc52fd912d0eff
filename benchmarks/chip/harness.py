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
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import math
import os
import shutil
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


def window(run, keys, seconds: float, rng):
    """Jobs back to back until ``seconds`` have passed. Keeps the answers of
    CHECKED_JOBS jobs drawn uniformly by ``rng`` (a reservoir), and each
    job's time."""
    kept: list = []
    times: list = []
    j = 0
    t0 = time.perf_counter()
    while True:
        t_job = time.perf_counter()
        out = run(keys(j))
        times.append(time.perf_counter() - t_job)
        if len(kept) < CHECKED_JOBS:
            kept.append((j, out))
        else:
            r = int(rng.integers(0, j + 1))
            if r < CHECKED_JOBS:
                kept[r] = (j, out)
        j += 1
        if time.perf_counter() - t0 >= seconds:
            break
    return time.perf_counter() - t0, j, kept, times


def job_spread(times) -> str:
    """Per-job times on stderr, to tell a window that is slow in every job
    from one slowed by a few stalls."""
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
    t0 = time.perf_counter()
    setup.run(JobKeys(jax.random.fold_in(key, 2), 1)(0))  # one warm-up job
    say(f"setup warmup_s={time.perf_counter() - t0!r} cache_hits={counter.hits}"
        f" cache_misses={counter.misses}")
    setup_s = time.perf_counter() - t_start
    rng = np.random.default_rng(seed)

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
                    setup.run, keys, min(seconds, TRACE_SECONDS), rng
                )
        finally:
            jax.profiler.stop_trace()
    else:
        elapsed, jobs, kept, times = window(setup.run, keys, seconds, rng)
    counter.on = False
    cpu_s = time.process_time() - cpu0
    say(f"window jobs={jobs} seconds={elapsed!r} compiles_in_window={counter.count}"
        + (f" compiled={','.join(sorted(set(counter.names)))}" if counter.names else ""))
    say(f"window {job_spread(times)} cpu_s={cpu_s!r}")
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
