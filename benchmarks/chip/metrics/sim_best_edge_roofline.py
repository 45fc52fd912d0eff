"""Share of the roofline reached by the ``sim_best_edge_pallas`` calls in
the traced window. On one chip every Borůvka round sweeps the whole sample
against itself, s rows by s columns."""


def read(ctx):
    c = ctx.cfg
    s = c["sample_size"]
    work = ctx.work("sim_best_edge")(rows=s, cols=s, d=c["d"])
    return ctx.roofline("sim_best_edge_pallas", [work])
