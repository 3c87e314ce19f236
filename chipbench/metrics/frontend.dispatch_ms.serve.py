"""Host time of the serving front end's dispatch per tick: the program's
``serve.dispatch`` spans (``core/serving.py``: the call into each group's
jitted ``batched_*`` program, until it returns) summed inside the window,
over the window's ticks (ms)."""
from chipbench import layers


def reduce(ctx):
    return layers.span_ms_per_tick(ctx, "serve.dispatch")
