"""Peak device memory in use after the window, in MB (10**6 bytes),
on the fullest chip (``memory_stats()["peak_bytes_in_use"]``)."""


def read(ctx):
    peak = ctx["peak_bytes"]
    return None if peak is None else peak / 1e6
