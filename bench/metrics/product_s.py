"""Seconds per product: the window's length over the products completed in
it (closed loop, each product ending in ``block_until_ready``)."""


def read(ctx):
    return ctx["window_s"] / ctx["calls"]
