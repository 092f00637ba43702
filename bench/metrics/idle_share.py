"""Percent of the traced window in which the least busy chip ran no
operation: 1 - (union of its device-operation intervals) / window."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    busy = min(c["busy_s"] for c in tr["chips"].values())
    return 100.0 * (1.0 - busy / tr["window_s"])
