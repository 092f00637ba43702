"""90th percentile of the products' dispatch-to-ready seconds in the window
(one sample per ``calls_per_sample`` products of the traffic mix)."""

import numpy as np


def read(ctx):
    return float(np.percentile(ctx["samples"], 90))
