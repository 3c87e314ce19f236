"""Collective psums per sparsifier call on the mesh, from the programs'
counter words (``SparseGraph.device_psums``) over the window: a count,
one per edge batch on the §9 schedule, not a speed."""


def reduce(ctx):
    rec = ctx["record"]
    if not rec.get("calls") or rec.get("psums") is None:
        return None
    return rec["psums"] / rec["calls"]
