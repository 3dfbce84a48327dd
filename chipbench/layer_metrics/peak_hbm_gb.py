"""Device: ``memory_stats()["peak_bytes_in_use"]`` of the fullest chip after
the window, in GB (10⁹ bytes). The CPU backend reports none → nothing."""


def read(ctx):
    return ctx["peak_bytes"] / 1e9 if ctx["peak_bytes"] else None
