"""Solver and kernels: the traced job's ``fit.dispatch`` span (the fit
function to its results on the host) less the seconds the runtime spent
moving the frame to the device inside it (``h2d_s.fit``'s part of the
dispatch: ``device_put`` only enqueues, so the wait for the transfer lands in
this span and is the data plane's, not the solver's). Without a trace, on a
backend with no transfer thread, the span as it is."""
from chipbench import spans


def read(ctx):
    roots = spans.fits(ctx)
    if not roots:
        return None
    trace = ctx["trace"]
    if not trace:
        return spans.mean_child_seconds(ctx, "dispatch")
    first = min(roots, key=lambda s: s["ts"])          # the traced job is the window's first
    span_s = sum(c["dur"] for c in spans.children(ctx, first, "dispatch")) * 1e-6
    return span_s - trace["transfer_in_s"]["dispatch"]
