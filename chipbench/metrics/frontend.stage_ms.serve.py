"""Host time of the serving front end's staging per tick: the program's
``serve.stage`` spans (``core/serving.py``: arena lookup, padding and
stacking of each group's payloads, its PRNG keys) summed inside the
window, over the window's ticks (ms)."""
from chipbench import layers


def reduce(ctx):
    return layers.span_ms_per_tick(ctx, "serve.stage")
