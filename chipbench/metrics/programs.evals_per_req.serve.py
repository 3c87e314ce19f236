"""Kernel evaluations the served programs realized per served request,
from the programs' counter words (``KernelGraphServable.
device_counters``) over the window: a count, not a speed."""


def reduce(ctx):
    rec = ctx["record"]
    served = rec["attempted"] - rec["failed"]
    if not served or not rec.get("evals"):
        return None
    return rec["evals"] / served
