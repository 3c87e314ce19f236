"""Cells of ``BENCHMARK.json`` cut to sizes a CPU test run can hold, and a
loop for them through the harness (never the command line, which
refuses a machine without a TPU)."""
from __future__ import annotations

import time

from chipbench import harness

#: a seed above 32 bits, as the benchmark's own runs draw them
SEED = (1 << 31) + 4242


def spec(name: str) -> dict:
    """``harness.cell_spec(name)`` with the point sets made tiny."""
    s = harness.cell_spec(name)
    conf, mix = s["config"], s["traffic"]
    if conf["name"] == "sift1m":
        conf.update(points=4096, queries=256, clusters=16)
        mix["max_ticks"] = 4
        mix["check"]["requests_per_op"] = 4
    else:
        conf.update(points=2048)
    return s


def run(jax, name: str, control: bool = False, trace: bool = False,
        seconds: float = 0.2, devices=None, log=None) -> dict:
    """One run of the tiny cell on the CPU; returns the result line."""
    s = spec(name)
    devs = devices or jax.devices()[:1]
    lines = []
    out = harness.run_cell(jax, name, SEED, seconds, trace, devs,
                           time.perf_counter(), control=control, spec=s,
                           log=lines.append if log is None else log)
    out["log"] = lines
    return out
