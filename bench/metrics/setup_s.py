"""Seconds from the start of the process to the first timed call: imports,
making the operands on the device, planning, compiling (or loading from the
persistent cache) and the traffic's warm-up calls."""


def read(ctx):
    return ctx["setup_s"]
