"""Device time of the level-1 sweep per serving tick: the union of the
operations whose op-name path holds the program's ``level1`` scope
(Pallas sweep kernels and the jnp query sweep alike), inside the window,
on the device that spent most, over the window's ticks (ms)."""
from chipbench import layers


def reduce(ctx):
    return layers.scope_ms(ctx, "level1",
                           len(ctx["trace"].spans_named("tick")))
