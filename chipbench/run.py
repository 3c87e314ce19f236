#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` on the TPU chips of this machine.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

Set-up (data from the seed, the program, a warm-up of the window's exact
composition) counts as ``setup_s``; then the window runs for ``--seconds``
and its answers are checked against the plain references in
``chipbench/refs``.  The last line of standard output is one JSON object
(``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, and with
``--trace 1`` ``breakdown``; ``compared`` last, each number compared with
its limit).  The same numbers close standard error.  With ``--trace 0``
the metrics are the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics, read from a profiler trace of the window.

Without a TPU, or with fewer chips than the cell asks for, it exits with
code 2 and prints no result.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

T_START = time.perf_counter()
HERE = Path(__file__).resolve().parent
REPO = HERE.parent
# the script's own directory holds modules (trace.py, data.py) that must
# not shadow the standard library's; the package is imported from REPO
sys.path[:] = [p for p in sys.path if Path(p or ".").resolve() != HERE]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(REPO), str(REPO / "src")]
    from chipbench import harness

    try:
        spec = harness.cell_spec(args.workload)
    except (KeyError, FileNotFoundError) as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    import jax
    chips = int(spec["cell"]["chips"])
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"chipbench: {args.workload} needs {chips} TPU chip(s); JAX "
              f"found {len(devs)} {devs[0].platform} device(s)",
              file=sys.stderr)
        return 2
    harness.use_compile_cache(jax)
    out = harness.run_cell(jax, args.workload, args.seed, args.seconds,
                           bool(args.trace), devs[:chips], T_START,
                           spec=spec)
    for k, c in out["compared"].items():
        print(f"compared {k} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
