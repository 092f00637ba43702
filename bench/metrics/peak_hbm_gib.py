"""GiB of device memory at its peak, the largest over the cell's chips of
``memory_stats()["peak_bytes_in_use"]``, read right after the window and
before the check."""


def read(ctx):
    return ctx["memory_peak_bytes"] / 2**30
