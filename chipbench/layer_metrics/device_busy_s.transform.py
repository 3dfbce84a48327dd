"""Transform path: union of device-op intervals inside the
``<Model>.transform`` annotation of the traced job, seconds per transform."""


def read(ctx):
    trace = ctx["trace"]
    if not trace or not trace["phase_count"].get("transform"):
        return None
    return trace["busy_in_s"]["transform"] / trace["phase_count"]["transform"]
