"""Idle share of the devices over the sparsifier window: 1 - busy /
window, averaged over the devices used."""
from chipbench import trace as _trace


def reduce(ctx):
    return _trace.idle_share(ctx["trace"])
