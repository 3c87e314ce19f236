"""Share of the level-1 sweep's roofline on the serving path (%): the
least time of the window's sweep work (``work/sweep.py``: each tick's
rows at 2 d flops a point, all in one pass over the dataset, since every
row is known when the tick starts) on the chip's published peaks, over the
summed device time of the sweep kernels (``kernel_names.SWEEP``)."""
from chipbench import kernel_names
from chipbench.harness import load_module, peaks


def reduce(ctx):
    tr = ctx["trace"]
    win, ticks = tr.window(), tr.spans_named("tick")
    if win is None or not ticks:
        return None
    spent = max(kernel_names.time_per_device(
        tr, kernel_names.SWEEP, *win).values(), default=0.0)
    if spent <= 0:
        return None
    sweep = load_module("work", "sweep")
    conf, mix = ctx["spec"]["config"], ctx["spec"]["traffic"]
    rows = sum(int(c["count"]) * int(c["width"]) for c in mix["clients"])
    work = sweep.tick_work(conf["points"], conf["dim"], rows)
    chip = peaks(ctx["devices"][0].device_kind)
    least = sweep.least_seconds(work, chip)["seconds"]
    return 100.0 * least * len(ticks) / (spent / 1e9)
