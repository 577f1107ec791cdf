"""The controls: each cell's reference, one precision below the
configuration's, put in the program's place and held to the cell's limits.
A control has to come out not correct; the numbers it reads are the upper
readings the limits sit under.

    python3 bench/control.py --workload <cell> --seeds <n> [<n> ...]

For an embed cell, one process sets the cell up per seed (round 0 and the
first chunks through the program), then computes the reference's readings
at float32 and "highest" precision, again in bfloat16, and again on half
of each chunk's lanes. It prints the program's gaps (the lower readings),
the bfloat16 reference's (the control's) and the half-batch fault's. For
a read cell, it draws the seed's table and reads and compares the
bfloat16 reference's answers with the float32 reference's. The
benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH_DIR), "src"))

import numpy as np  # noqa: E402

import common  # noqa: E402
import serving  # noqa: E402


def bf16():
    import ml_dtypes
    return ml_dtypes.bfloat16


def embed_control(cell: common.Cell, seed: int) -> dict:
    """Program and bfloat16-reference gaps against the float32 reference."""
    driver = cell.driver()
    ctx = common.Context(cell=cell, seed=seed, seconds=0.0, trace=False)
    st = driver.setup(ctx)
    driver.release(st)
    limits = cell.traffic["limits"]
    ref = driver.reference_readings(st, ctx)
    low = driver.reference_readings(st, ctx, dtype=bf16(),
                                    precision="default")
    half = driver.reference_readings(
        st, ctx, groups=st.first[0][0].shape[2] // 2)
    program = driver.compare([loss for _, loss in st.first], st.readings,
                             *ref, limits)
    control = driver.compare(*low, *ref, limits)
    fault = driver.compare(*half, *ref, limits)
    return {"program": {c.name: c.value for c in program},
            "control": {c.name: c.value for c in control},
            "control_correct": all(c.ok for c in control),
            "half_batch": {c.name: c.value for c in fault},
            "half_batch_correct": all(c.ok for c in fault)}


def reads_control(cell: common.Cell, seed: int) -> dict:
    """bfloat16-reference answers against float32-reference answers for
    the seed's table and a sample of its reads."""
    t = cell.traffic
    n = int(cell.config["graph"]["num_nodes"])
    phi = serving.make_table(seed, n, int(cell.config["embed"]["dim"]))
    rng = np.random.default_rng(seed)
    users = serving.Users(rng, n, float(t["zipf"])).draw(
        int(t["check_sample"]))
    if "k" in t:
        answers = []
        for u in users:
            scores, ids = serving.reference.topk(phi, int(u), int(t["k"]))
            answers.append((u, ids, scores))
        found = serving.check_topk(phi, answers, int(t["k"]), bf16())
    else:
        cands = rng.integers(0, n, (len(users), int(t["candidates"])))
        answers = [(u, c, c, serving.reference.chain_scores(phi[u], phi[c]))
                   for u, c in zip(users, cands)]
        found = serving.check_pairs(phi, answers, bf16())
    checks = serving.check_list(found, len(answers))
    return {"control": {c.name: c.value for c in checks},
            "control_correct": all(c.ok for c in checks)}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    args = p.parse_args(argv)
    cell = common.resolve(args.workload)
    common.enable_compile_cache()
    embed = cell.traffic["driver"] == "embed"
    for seed in args.seeds:
        t0 = time.perf_counter()
        out = (embed_control if embed else reads_control)(cell, seed)
        out.update(workload=cell.name, seed=seed,
                   seconds=time.perf_counter() - t0)
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
