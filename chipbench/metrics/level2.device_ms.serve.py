"""Device time of the level-2 draw per serving tick: the union of the
operations whose op-name path holds the program's ``level2`` scope (the
in-block row, the draw, ``prob_of``'s probability), inside the window,
on the device that spent most, over the window's ticks (ms)."""
from chipbench import layers


def reduce(ctx):
    return layers.scope_ms(ctx, "level2",
                           len(ctx["trace"].spans_named("tick")))
