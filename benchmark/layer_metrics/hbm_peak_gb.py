"""Peak device memory on the fullest chip,
``memory_stats()["peak_bytes_in_use"]``, in GB (10^9 bytes)."""


def read(ctx):
    return ctx["memory_peak_bytes"] / 1e9
