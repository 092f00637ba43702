"""GiB of operand blocks that set-up moved between chips to lay the operands
out for their product: the library tracer's ``migrated_bytes`` at the start
of the window.  Nothing to read where the cell's program counts none."""


def read(ctx):
    setup = (ctx["counters"] or {}).get("setup", {})
    if "migrated_bytes" not in setup:
        return None
    return setup["migrated_bytes"] / 2**30
