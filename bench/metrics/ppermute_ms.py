"""Milliseconds of collective-permute operations per product, summed over
each chip's trace and averaged over the chips.  Nothing to read on one chip."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    per_chip = [c["permute_s"] for c in tr["chips"].values()]
    if not any(per_chip):
        return None
    return 1e3 * sum(per_chip) / len(per_chip) / tr["spans"]
