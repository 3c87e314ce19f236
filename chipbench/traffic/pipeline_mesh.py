"""Back-to-back calls of ``core.sparsify.spectral_sparsify`` on the mesh
engine (DESIGN.md §9): the ``pipeline_repeat`` loop with the dataset
sharded over a mesh of the cell's own devices, built from the
configuration's ``mesh`` (``shape``, ``axes``) and passed as ``mesh=``.

The window, the end-to-end metric and the check are ``pipeline_repeat``'s;
the record adds ``psums``, the collective psums the window's calls
report in their counter words (``SparseGraph.device_psums``): one per
edge batch of 1,024 on this schedule.  A program whose graphs carry no
psum count cannot show that schedule, and the loop refuses it before
any work.

The mesh engine has no bf16 option (DESIGN.md §14), so the control puts
the repo's bf16 kernel evaluator (``ref.kv_matrix(precision="bf16")``,
bf16 operands, f32 accumulation, bf16 ``exp``) in the place of the
f32 one for the calls' programs, which then compile apart from the f32
ones: every shard's level-1 sweep runs in bf16, as the one-chip
control's does.
"""
from __future__ import annotations

from chipbench import data
from chipbench.traffic import pipeline_repeat


class Loop(pipeline_repeat.Loop):
    """``pipeline_repeat.Loop`` over a mesh of ``devices``."""

    def __init__(self, jax, spec, seed, devices, control=False):
        super().__init__(jax, spec, seed, devices, control=control)
        self.devices = list(devices)

    def setup(self) -> None:
        from repro.core import sparsify
        from repro.core.kernels_fn import gaussian
        from repro.launch.mesh import make_mesh

        if not hasattr(sparsify.SparseGraph, "device_psums"):
            raise RuntimeError("spectral_sparsify reports no psum count "
                               "(SparseGraph.device_psums)")
        m = self.conf["mesh"]
        mesh = make_mesh(m["shape"], m["axes"], devices=self.devices)
        n = int(self.conf["points"])
        self.x = data.nested(n, self.s31)
        self.xd = self.jax.numpy.asarray(self.x)
        self.bw = data.median_bandwidth(self.jax, self.xd)
        self.t = int(self.conf["edges_per_point"]) * n
        ker = gaussian(self.bw)

        def call(i):
            return sparsify.spectral_sparsify(
                self.xd, ker, num_edges=self.t,
                estimator=self.conf["estimator"],
                exact_blocks=self.conf["exact_blocks"],
                seed=self.s31 + i, mesh=mesh)

        self.call = _bf16_level1(call) if self.control else call
        self.call(0)                                     # warm-up

    def window(self, seconds: float, span) -> dict:
        rec = super().window(seconds, span)
        rec["psums"] = sum(g.device_psums for g in rec["graphs"])
        return rec


def _bf16_level1(call):
    """``call`` with ``ref.kv_matrix`` evaluating in bf16 and the mesh
    engine's programs kept in a cache of their own (the f32 ones are
    keyed on the same static configuration): the control."""
    import functools

    from repro.kernels.kde_sampler import ref, sharded

    programs = {}

    def fn(i):
        kv, cache = ref.kv_matrix, sharded._PROGRAM_CACHE
        ref.kv_matrix = functools.partial(kv, precision="bf16")
        sharded._PROGRAM_CACHE = programs
        try:
            return call(i)
        finally:
            ref.kv_matrix, sharded._PROGRAM_CACHE = kv, cache
    return fn
