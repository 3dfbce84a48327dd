"""Data plane: seconds per fit in which the runtime's transfer thread moves
the frame to the device (``trace_reduce.TRANSFER``: the layout change and the
DMA of ``device_put``) inside the fit's ``preprocess`` and solver-dispatch
annotations of the traced job. No such range in the trace → nothing."""


def read(ctx):
    trace = ctx["trace"]
    if not trace or not trace["phase_count"].get("dispatch"):
        return None
    seconds = trace["transfer_in_s"]["preprocess"] + trace["transfer_in_s"]["dispatch"]
    return seconds / trace["phase_count"]["dispatch"] if seconds > 0.0 else None
