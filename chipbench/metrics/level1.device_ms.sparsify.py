"""Device time of the level-1 sweep per sparsifier call: the union of the
operations whose op-name path holds the program's ``level1`` scope (the
degree sweep's reads and the edge scan's), inside the window, on the
device that spent most, over the window's calls (ms)."""
from chipbench import layers


def reduce(ctx):
    return layers.scope_ms(ctx, "level1", ctx["record"].get("calls"))
