"""Device time of the degree ring per sparsifier call on the mesh: the
union of the operations whose op-name path holds the program's
``degrees`` scope (the ring's fused pair sums and its collective
permutes), inside the window, on the device that spent most, over the
window's calls (ms)."""
from chipbench import layers


def reduce(ctx):
    return layers.scope_ms(ctx, "degrees", ctx["record"].get("calls"))
