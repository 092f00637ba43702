"""GiB a chip receives per product in the window, padding included: the
library tracer's ``recv_padded_bytes`` (what the SPMD program moves, summed
over chips) that the window added, over the products completed in it and
over the chips.  Nothing to read where the cell's program counts none."""


def read(ctx):
    window = (ctx["counters"] or {}).get("window", {})
    if "recv_padded_bytes" not in window or not ctx["calls"]:
        return None
    return window["recv_padded_bytes"] / ctx["calls"] / ctx["chips"] / 2**30
