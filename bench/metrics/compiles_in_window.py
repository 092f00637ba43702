"""XLA compiles inside the measured window (persistent-cache loads count),
from ``jax.monitoring``'s compile-duration events."""


def read(ctx):
    return ctx["compiles_in_window"]
