"""The whole embed step's share of the chip's bf16 peak: the SGNS
operations of the valid positions trained in the traced window, over the
window's length times the peak. Whatever path trains, this bounds it."""


def read(r):
    flops = r.counts.get("sgns_flops", 0.0)
    if flops <= 0:
        return None
    return 100.0 * flops / (r.trace["window_s"] * r.peaks["flops_per_s"])
