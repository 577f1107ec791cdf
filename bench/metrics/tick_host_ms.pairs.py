"""Host milliseconds per served wave of the server's ``tick``: the part of
the program's ``repro.serve.tick`` spans in the traced window that no device
operation covers, over the waves. Beside it, the mean milliseconds per wave
of each child phase, the tick time no child covers (``unspanned``) and the
waves counted. Reads ``program`` (bench/program_spans.py); nothing where
the program has no such span."""

TICK = "repro.serve.tick"
CHILDREN = ("form", "group", "dispatch", "fetch", "respond")


def read(r):
    program = r.trace.get("program") or {}
    tick = program.get(TICK)
    if not tick or tick["count"] <= 0:
        return None
    waves = tick["count"]
    out = {"value": 1e3 * tick["host_s"] / waves, "waves": waves}
    covered = 0.0
    for child in CHILDREN:
        s = program.get(f"repro.serve.{child}", {}).get("s", 0.0)
        out[child] = 1e3 * s / waves
        covered += s
    out["unspanned"] = 1e3 * (tick["s"] - covered) / waves
    return out
