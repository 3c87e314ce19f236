"""Host time of the sparsifier pipeline per call: the time inside the
program's ``sparsify.sampler``, ``sparsify.degrees`` and ``sparsify.graph``
spans (``core/sparsify.py``) in which no operation ran on any device,
inside the window, over the window's calls (ms); silent on a trace with
no device plane."""
from chipbench import layers

PHASES = ("sparsify.sampler", "sparsify.degrees", "sparsify.graph")


def reduce(ctx):
    tr, lay = ctx["trace"], layers.of(ctx)
    win, calls = tr.window(), ctx["record"].get("calls")
    if lay is None or win is None or not calls or not tr.ops or not any(
            lay.spans_named(p) for p in PHASES):
        return None
    return layers.idle_inside(lay, PHASES, *win) / calls / 1e6
