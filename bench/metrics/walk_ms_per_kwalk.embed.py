"""Device milliseconds of the walk engine per 1,000 walks walked in the
traced window: the run_walk_batch programs' busy time over the walks their
batches held."""

MODULES = ("run_walk_batch",)


def read(r):
    walked = r.counts.get("walks_walked", 0)
    seconds = r.layer_s()
    if walked <= 0 or seconds <= 0:
        return None
    return 1e6 * seconds / walked
