"""From a profiler trace to what the per-layer metrics read.

The JAX profiler writes one ``.xplane.pb``; ``jax.profiler.ProfileData``
reads it. On a TPU every chip is a plane ``/device:TPU:<i>`` whose line
``XLA Ops`` holds one event per operation run on that chip. On the CPU the
operations run on host threads and carry an ``hlo_op`` stat, which lets a
rehearsal drive the same reduction. The benchmark marks its measured window
with a host annotation named ``WINDOW``; every reduction is clipped to it.
"""

from __future__ import annotations

import glob
import os
import re
from typing import NamedTuple

WINDOW = "bench_window"

_NAME = re.compile(r"^%?([^\s=]+)")
_SUFFIX = re.compile(r"(\.\d+)+$")
# operations that hold other operations of the same line: their time is
# their body's, so the breakdown leaves them out
CONTAINERS = ("while", "conditional", "call")


class Trace(NamedTuple):
    ops: dict  # device index -> [(name, start_ns, end_ns)], operations
    host: list  # [(name, start_ns, end_ns)], host activity
    window: tuple  # (start_ns, end_ns) of the measured window


def op_kind(name: str) -> str:
    """An operation's instruction name without the numeric suffixes XLA
    appends. A TPU trace names an operation by its whole HLO text,
    ``%assign_stats_pallas.9 = (s32[...]) custom-call(...)``."""
    m = _NAME.match(name)
    return _SUFFIX.sub("", m.group(1) if m else name)


def load(logdir: str) -> Trace:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    data = ProfileData.from_file(paths[-1])
    ops: dict[int, list] = {}
    host: list = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = int(plane.name.rsplit(":", 1)[1])
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.setdefault(dev, []).extend(
                        (e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                        for e in line.events
                    )
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.duration_ns <= 0:
                        continue
                    ev = (e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                    stats = dict(e.stats) if line.name.startswith("tf_XLA") else {}
                    if "hlo_op" in stats:  # an operation of the CPU backend
                        ops.setdefault(int(stats.get("device_ordinal", 0)), []).append(ev)
                    else:
                        host.append(ev)
    marks = [h for h in host if h[0] == WINDOW]
    if not marks:
        raise ValueError(f"the trace holds no {WINDOW!r} annotation")
    return Trace(ops=ops, host=host, window=(marks[-1][1], marks[-1][2]))


# ------------------------------------------------------------ interval sums


def union(iv):
    """Sorted, disjoint cover of the intervals ``[(start, end)]``."""
    out: list = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(iv, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


def length(iv) -> int:
    return sum(e - s for s, e in iv)


def subtract(a, b):
    """The parts of the disjoint sorted intervals ``a`` outside those of ``b``."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k, cur = j, s
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def busy(trace: Trace, dev: int):
    lo, hi = trace.window
    return union(clip([(s, e) for _, s, e in trace.ops.get(dev, [])], lo, hi))


def window_ns(trace: Trace) -> int:
    return trace.window[1] - trace.window[0]


def busy_share(trace: Trace) -> float | None:
    """Busy time over the window, averaged over the devices that ran."""
    if not trace.ops:
        return None
    w = window_ns(trace)
    return sum(length(busy(trace, d)) for d in trace.ops) / (w * len(trace.ops))


def kernel_calls(trace: Trace, kernel: str) -> list[int]:
    """Device durations, in ns, of every call of ``kernel`` in the window."""
    lo, hi = trace.window
    return [
        e - s
        for evs in trace.ops.values()
        for n, s, e in evs
        if op_kind(n) == kernel and s >= lo and e <= hi
    ]


# ---------------------------------------------------------------- breakdown


def breakdown(trace: Trace, top: int = 10) -> dict:
    """The device operations that took most time, averaged over devices,
    and the longest idle gaps named by the innermost host activity around
    their middle."""
    lo, hi = trace.window
    per: dict[str, int] = {}
    for evs in trace.ops.values():
        for n, s, e in clip_events(evs, lo, hi):
            kind = op_kind(n)
            if kind not in CONTAINERS:
                per[kind] = per.get(kind, 0) + (e - s)
    devs = max(len(trace.ops), 1)
    ops = sorted(per.items(), key=lambda kv: -kv[1])[:top]
    gaps = []
    for d in trace.ops:
        gaps += subtract([(lo, hi)], busy(trace, d))
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    named = []
    for s, e in gaps:
        mid = (s + e) // 2
        around = [h for h in trace.host if h[1] <= mid <= h[2] and h[0] != WINDOW]
        name = (min(around, key=lambda h: h[2] - h[1])[0] if around
                else "untraced host code (Python)")
        named.append([name, (e - s) / 1e9])
    return {
        "device_ops": [[n, t / devs / 1e9] for n, t in ops],
        "idle_gaps": named,
    }


def clip_events(evs, lo, hi):
    return [(n, max(s, lo), min(e, hi)) for n, s, e in evs if e > lo and s < hi]
