"""Host time of the serving front end's scatter per tick: the program's
``serve.scatter`` spans (``core/serving.py``: the counter note, the status
fan-out and the result slicing of each group) summed inside the window,
over the window's ticks (ms)."""
from chipbench import layers


def reduce(ctx):
    return layers.span_ms_per_tick(ctx, "serve.scatter")
