"""Share of the traced window in which no operation ran on the device."""


def read(r):
    window = r.trace["window_s"]
    if window <= 0:
        return None
    return 100.0 * (1.0 - r.trace["busy_s"] / window)
