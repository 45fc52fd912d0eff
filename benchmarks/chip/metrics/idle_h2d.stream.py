"""Share of the traced window, in %, in which the device is idle while the
program's consumer thread is inside a ``stream.h2d`` span: moving a host
chunk to the device (``text/stream.to_device``). Averaged over the chips;
None where the window holds no such span."""

import spans


def read(ctx):
    return spans.idle_under(ctx.trace, "stream.h2d")
