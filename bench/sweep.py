"""Find the highest open-loop rate a read cell sustains: one set-up, then
one window per offered rate, each with its own schedule from the seed.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \
        --rates <r> [<r> ...]

Prints one JSON line per rate: reads due, p50 and p95 from the due time,
and the backlog at the window's close (reads due but not yet answered).
A rate is sustained where the backlog stays within a few waves. The cell's
traffic file then takes about four fifths of the highest such rate.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH_DIR), "src"))

import numpy as np  # noqa: E402

import common  # noqa: E402
import serving  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--rates", type=float, nargs="+", required=True)
    args = p.parse_args(argv)
    cell = common.resolve(args.workload)
    common.enable_compile_cache()
    driver = cell.driver()
    ctx = common.Context(cell=cell, seed=args.seed, seconds=args.seconds,
                         trace=False)
    st = driver.setup(ctx)
    n = int(cell.config["graph"]["num_nodes"])
    for rate in args.rates:
        rng = np.random.default_rng([args.seed, int(rate)])
        users = serving.Users(rng, n, float(cell.traffic["zipf"]))
        st.due, st.users, st.cands = driver.schedule(
            rng, rate, args.seconds, users, n,
            int(cell.traffic["candidates"]))
        out = driver.window(st, ctx)
        print(json.dumps({"rate_per_s": rate, "reads": out.attempted,
                          "failed": out.failed, **out.metrics,
                          **out.counts}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
