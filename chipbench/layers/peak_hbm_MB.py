"""device: `memory_stats()["peak_bytes_in_use"]` of the fullest chip after
the window, in MB (1e6 bytes)."""


def read(ctx):
    peak = ctx.get("memory_peak_bytes")
    return peak / 1e6 if peak else None     # a CPU reports none
