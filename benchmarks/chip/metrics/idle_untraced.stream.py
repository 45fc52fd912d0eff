"""Share of the traced window, in %, in which the device is idle while the
program is inside none of its own spans (``stream.*``, ``kmeans.*``,
``buckshot.*``): the harness between jobs, or host code that no span names.
Averaged over the chips; None where the window holds no program span."""

import spans


def read(ctx):
    return spans.idle_untraced(ctx.trace)
