"""Transform path: all wall time inside ``Model.transform(df)`` calls of the
window over their number, each timed on the host clock to the output columns
as numpy arrays (the generator's ``transform_s``). It is a per-layer metric
and not an end-to-end one because a 0.64 s call, twice a window, spreads by
more between identical runs than half of the widest bound allows (PERF.md
section 2); ``job_s`` carries it end to end."""


def read(ctx):
    return ctx["values"].get("transform_s")
