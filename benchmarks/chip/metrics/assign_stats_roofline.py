"""Share of the roofline reached by the ``assign_stats_pallas`` calls in the
traced window. In the resident cells every call assigns all n rows to the k
centers."""


def read(ctx):
    c = ctx.cfg
    work = ctx.work("assign_stats")(rows=c["n"], k=c["k"], d=c["d"])
    return ctx.roofline("assign_stats_pallas", [work])
