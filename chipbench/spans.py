"""Helpers shared by the span readers: the program's spans arrive as the
chrome-trace events its telemetry sink hands over (name, dur in µs, args with
span_id and parent_id)."""


def fits(ctx):
    """The window's ``<Estimator>.fit`` root spans."""
    return [s for s in ctx["spans"] if s["name"] == ctx["config"]["annotations"]["fit"]]


def children(ctx, parent, key):
    name = ctx["config"]["annotations"][key]
    pid = parent["args"]["span_id"]
    return [s for s in ctx["spans"] if s["name"] == name and s["args"].get("parent_id") == pid]


def mean_child_seconds(ctx, key):
    roots = fits(ctx)
    if not roots:
        return None
    return sum(c["dur"] for r in roots for c in children(ctx, r, key)) * 1e-6 / len(roots)
