"""Device time of the sharded level-1 sweep per sparsifier call on the
mesh: the union of the operations whose op-name path holds the program's
``level1`` scope (each shard's exact block sums and the edge scan's
reads), inside the window, on the device that spent most, over the
window's calls (ms)."""
from chipbench import layers


def reduce(ctx):
    return layers.scope_ms(ctx, "level1", ctx["record"].get("calls"))
