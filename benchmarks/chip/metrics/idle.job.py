"""Device idle share of the traced window, in %: 1 - (union of the
intervals in which an operation runs on a device) / window, averaged over
the chips."""

import traces


def read(ctx):
    share = traces.busy_share(ctx.trace)
    return None if share is None else 100.0 * (1.0 - share)
