"""Mean reads per served wave over the server's slot count, in the traced
window: how full the scheduler's waves run at the cell's load."""

def read(r):
    waves = r.counts.get("waves", 0)
    if waves <= 0:
        return None
    return 100.0 * r.counts["reads"] / (waves * r.counts["batch_slots"])
