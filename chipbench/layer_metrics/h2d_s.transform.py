"""Transform path: seconds per transform in which the runtime's transfer
thread moves the batches to the device (``trace_reduce.TRANSFER``) inside the
``<Model>.transform`` annotation of the traced job."""


def read(ctx):
    trace = ctx["trace"]
    if not trace or not trace["phase_count"].get("transform"):
        return None
    seconds = trace["transfer_in_s"]["transform"]
    return seconds / trace["phase_count"]["transform"] if seconds > 0.0 else None
