"""Share of the roofline reached per product, in percent.

The least time the chips could take for one product, the larger of its
flops over the chips' bf16 peak and its compulsory HBM bytes over their
bandwidth (both counted by ``bench.work`` from the coordinates, whatever
implements the product), over the busiest chip's device-busy seconds in
the traced window per product completed in it.  The bf16 peak is used on
purpose: fp32 at ``Precision.HIGHEST`` costs several bf16 passes, and a
later change that needs fewer passes for the same accuracy must read as a
gain, never above 100%.
"""


def read(ctx):
    tr, work, peaks = ctx["trace"], ctx["work"], ctx["peaks"]
    if tr is None or work is None or peaks is None:
        return None
    busy = max(c["busy_s"] for c in tr["chips"].values()) / tr["spans"]
    if busy <= 0:
        return None
    chips = ctx["chips"]
    least = max(work["flops"] / (chips * peaks["bf16_flops_per_s"]),
                work["bytes"] / (chips * peaks["hbm_bytes_per_s"]))
    return 100.0 * least / busy
