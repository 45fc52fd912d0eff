"""Share of the traced window, in %, in which the device is idle while the
program's consumer thread is inside a ``stream.sync`` span: blocked on a
device result that it reads back to the host. Averaged over the chips; None
where the window holds no such span."""

import spans


def read(ctx):
    return spans.idle_under(ctx.trace, "stream.sync")
