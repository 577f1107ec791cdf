"""Share of the roofline that top-K scoring reaches: the least time the
chip needs to read phi once and write one score per (query, vertex), and
for 2 x B x |V| x d operations (bench/work.py), over the device time of the
scoring programs in the traced window. The (B, |V|, d) product tensor the
current path builds is not counted."""

from work import roofline_share

MODULES = ("_all_products_jit", "_accumulate_jit", "_topk_from_scores_jit")


def read(r):
    seconds = r.layer_s()
    flops = r.counts.get("topk_flops", 0.0)
    if seconds <= 0 or flops <= 0:
        return None
    share, bound = roofline_share(flops, r.counts["topk_bytes"], seconds,
                                  r.peaks)
    return {"value": share, "bound": bound}
