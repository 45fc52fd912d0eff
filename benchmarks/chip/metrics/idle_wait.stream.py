"""Share of the traced window, in %, in which the device is idle while the
program's consumer thread is inside a ``stream.wait`` span: waiting for the
next chunk from the source or its prefetch thread. Averaged over the chips;
None where the window holds no such span."""

import spans


def read(ctx):
    return spans.idle_under(ctx.trace, "stream.wait")
