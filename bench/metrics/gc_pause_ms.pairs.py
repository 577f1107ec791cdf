"""Milliseconds per second of the traced window that Python's garbage
collector held the program: the ``repro.gc.collect.gen<g>`` spans that
``repro.obs`` records, with the collections and the longest pause of each
generation. Reads ``program`` (bench/program_spans.py); nothing where the
program records no span at all."""


def read(r):
    program = r.trace.get("program")
    window = r.trace["window_s"]
    if not program or window <= 0:
        return None
    pause = 0.0
    collections, longest_ms = {}, {}
    for g in range(3):
        e = program.get(f"repro.gc.collect.gen{g}")
        if e:
            pause += e["s"]
            collections[f"gen{g}"] = e["count"]
            longest_ms[f"gen{g}"] = 1e3 * e["max_s"]
    return {"value": 1e3 * pause / window, "collections": collections,
            "longest_ms": longest_ms}
