"""Summed device time of the level-1 sweep kernels (``kernel_names.
SWEEP``) per sparsifier call, on the device that spent most (ms)."""
from chipbench import kernel_names


def reduce(ctx):
    tr, rec = ctx["trace"], ctx["record"]
    win = tr.window()
    if win is None or not rec.get("calls"):
        return None
    spent = max(kernel_names.time_per_device(
        tr, kernel_names.SWEEP, *win).values(), default=0.0)
    if spent <= 0:
        return None
    return spent / rec["calls"] / 1e6
