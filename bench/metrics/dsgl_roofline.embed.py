"""Share of the roofline that the DSGL chunks reach: the least time the
chip needs for the SGNS work of the valid positions trained in the traced
window (bench/work.py), over the train_chunk programs' busy time. Padding
past a walk's end is not counted, so it shows as a lower share."""

from work import roofline_share

MODULES = ("train_chunk",)


def read(r):
    seconds = r.layer_s()
    flops = r.counts.get("sgns_flops", 0.0)
    if seconds <= 0 or flops <= 0:
        return None
    share, bound = roofline_share(flops, r.counts["sgns_bytes"], seconds,
                                  r.peaks)
    return {"value": share, "bound": bound}
