"""Which device operations of a trace belong to which layer.

The level-1 sweep kernels are the program's Pallas kernels that stream the
dataset: ``kde_sampler/kernel.py`` (block sums with and without the
in-pass block draw) and ``kde_rowsum/kernel.py`` (row sums and block
sums).  On the cells that read these metrics they are the only Pallas
kernels that run (the hash kernel serves hashed tenants alone).  The
TPU's compiler names a Pallas call's operation after the jitted function
around it, so an operation is matched by its name and by the text of its
stats (the HLO instruction with its custom-call target, and the source
op ``.../pallas_call``): it matches when either holds one of the
patterns.
"""
from __future__ import annotations

SWEEP = ("_sample_block_kernel", "_masked_blocksum_kernel",
         "_rowsum_kernel", "_blocksum_kernel", "tpu_custom_call",
         "pallas_call")


def matches(name: str, patterns, text: str = "") -> bool:
    """True when ``name`` or ``text`` contains one of ``patterns``."""
    return any(p in name or p in text for p in patterns)


def time_per_device(trace, patterns, lo, hi) -> dict:
    """Per device, the summed time in [lo, hi] of operations that match
    ``patterns`` (nanoseconds)."""
    from chipbench import trace as _trace
    out = {}
    for dev, evs in trace.ops.items():
        sel = [e for e in evs
               if matches(e[0], patterns, trace.op_text.get(e[0], ""))]
        out[dev] = sum(_trace.durations(sel, lo, hi).values())
    return out
