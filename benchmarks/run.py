"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (see each bench module's
docstring for what the derived column encodes, and EXPERIMENTS.md
§Paper-claims for how these map onto the paper's Section 7 numbers).

  PYTHONPATH=src python -m benchmarks.run [--quick] [--only kde,lra,...]
"""
from __future__ import annotations

import argparse
import sys
import time

BENCHES = {
    "kde": "benchmarks.bench_kde",                 # Table 1
    "sampling": "benchmarks.bench_sampling",       # fused engine vs seed
    "primitives": "benchmarks.bench_primitives",   # Table 2
    "lra": "benchmarks.bench_lra",                 # Figure 3
    "sparsify": "benchmarks.bench_sparsify",       # Figure 4 / §7.1
    "graph": "benchmarks.bench_graph",             # Thms 6.15 / 6.17
    "distributed": "benchmarks.bench_distributed", # sharded engine (§9)
    "eigen_spectrum": "benchmarks.bench_eigen_spectrum",  # Thms 5.22 / 5.17
    "attention": "benchmarks.bench_attention",     # framework integration
    "streaming": "benchmarks.bench_streaming",     # dynamic datasets (§12)
    "serve": "benchmarks.bench_serve",             # serving layer (§13)
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", type=str, default="")
    ap.add_argument("--skip", type=str, default="",
                    help="comma-separated modules to exclude")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else set(BENCHES)
    only -= set(args.skip.split(",")) if args.skip else set()
    from repro.launch.cache import use_compile_cache
    use_compile_cache()

    print("name,us_per_call,derived")
    failures = []
    for key, modname in BENCHES.items():
        if key not in only:
            continue
        t0 = time.time()
        try:
            mod = __import__(modname, fromlist=["run"])
            mod.run(quick=args.quick)
            print(f"# {key}: done in {time.time() - t0:.1f}s", flush=True)
        except Exception as e:  # keep going; report at the end
            failures.append((key, repr(e)))
            print(f"# {key}: FAILED {e!r}", flush=True)
    if failures:
        print(f"# {len(failures)} benchmark module(s) failed: {failures}")
        sys.exit(1)
    print("# all benchmarks complete")


if __name__ == "__main__":
    main()
