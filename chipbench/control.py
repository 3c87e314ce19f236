#!/usr/bin/env python3
"""Readings that set a cell's limits, several seeds in one process.

    python3 chipbench/control.py --workload <cell> --seconds <s> \\
        --seeds 1 2 3 [--control]

Runs the cell as ``run.py`` does, once per seed, and prints each run's
compared numbers and diagnostics on one JSON line.  With ``--control`` the
program runs with its own bf16 level-1 path switched on (DESIGN.md §14):
the control, which each cell's comparison has to fail.  Without it, the
lines are the program's own readings.  The benchmark's runs never run the
control.  Needs the TPU chips the cell asks for, like ``run.py``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
REPO = HERE.parent
sys.path[:] = [p for p in sys.path if Path(p or ".").resolve() != HERE]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)

    sys.path[:0] = [str(REPO), str(REPO / "src")]
    from chipbench import harness

    spec = harness.cell_spec(args.workload)
    import jax
    chips = int(spec["cell"]["chips"])
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"chipbench: {args.workload} needs {chips} TPU chip(s)",
              file=sys.stderr)
        return 2
    harness.use_compile_cache(jax)
    for seed in args.seeds:
        lines = []
        out = harness.run_cell(jax, args.workload, seed, args.seconds,
                               False, devs[:chips], time.perf_counter(),
                               control=args.control, spec=spec,
                               log=lines.append)
        info = json.loads(lines[-1])["check_info"]
        print(json.dumps({"seed": seed, "control": args.control,
                          "compared": out["compared"], "info": info,
                          "attempted": out["attempted"],
                          "metrics": out["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
