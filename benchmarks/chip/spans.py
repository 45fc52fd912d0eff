"""Device idle time under the program's own host spans.

The program names its host time with fixed-name spans
(``jax.profiler.TraceAnnotation``): the stream executor's ``stream.*`` and the
drivers' ``kmeans.*`` and ``buckshot.*``. ``traces.load`` keeps them, from
every host thread, in ``Trace.host``, on the clock of the device operations.
A reader gives the share of the traced window in which a chip is idle while
the program is inside a set of spans, per chip and then averaged over the
chips, as ``traces.busy_share`` counts busy time.
"""

import traces

PROGRAM = ("stream.", "kmeans.", "buckshot.")


def cover(trace, match):
    """Sorted, disjoint cover of the window's host spans whose name ``match``
    accepts."""
    lo, hi = trace.window
    return traces.union(traces.clip([(s, e) for n, s, e in trace.host if match(n)], lo, hi))


def idle_share(trace, under, outside: bool = False):
    """Idle time inside the cover ``under`` (outside it, if ``outside``), in %
    of the window. None where the cover is empty or no device ran."""
    if not under or not trace.ops:
        return None
    lo, hi = trace.window
    total = 0
    for d in trace.ops:
        idle = traces.subtract([(lo, hi)], traces.busy(trace, d))
        out = traces.length(traces.subtract(idle, under))
        total += out if outside else traces.length(idle) - out
    return 100.0 * total / (traces.window_ns(trace) * len(trace.ops))


def idle_under(trace, name: str):
    """Idle share under the spans called ``name``."""
    return idle_share(trace, cover(trace, lambda n: n == name))


def idle_untraced(trace):
    """Idle share under no span of the program."""
    return idle_share(trace, cover(trace, lambda n: n.startswith(PROGRAM)), outside=True)
