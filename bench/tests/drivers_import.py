"""Load a driver of bench/drivers by name, as the harness does."""

import os

import common


def load_driver(name):
    return common.load_module(
        os.path.join(common.BENCH_DIR, "drivers", name + ".py"),
        "bench_driver_" + name)
