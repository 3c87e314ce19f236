"""Back-to-back calls of a Table-1 pipeline: ``core.sparsify.
spectral_sparsify`` on the configuration's point set, call ``i`` seeded
``seed + i`` (the warm-up is call 0).  Each call ends with its edge list
on the host.

After the window every edge of every call is checked against the
reference degrees ``deg(i) = sum_{j != i} k(x_i, x_j)`` of ``refs/kde.py``:

* ``weight_rel``: with exact level-1 reads the sampler draws edge (u, v)
  with probability ``k(u, v) / sum_i deg(i)`` (u by degree, v given u by
  kernel weight), so Algorithm 5.1 gives every edge the same weight
  ``sum_i deg(i) / (2 t)``; the widest relative gap to it.
* ``edge_dispersion``: which edges were drawn.  The rows fall into
  ``groups`` groups of equal size by a seeded permutation; over the window, the number of edge
  sources and the number of edge destinations in each group are each a
  multinomial count with the group's share of the total degree as its
  probability.  Pearson's chi-square over the groups, over its ``groups
  - 1`` degrees of freedom, reads about 1 for independent draws from
  that law (sd ``sqrt(2 / (groups - 1))``), and about 2 when half the
  batches repeat the others; the larger of the two (sources,
  destinations).
* ``bad_edges``: missing edges, self loops, rows outside the set,
  weights not finite and positive.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from chipbench import data
from chipbench.refs import kde as ref


class Loop:
    """The ``harness`` loop interface over repeated pipeline calls."""

    def __init__(self, jax, spec, seed, devices, control=False):
        self.jax = jax
        self.conf = spec["config"]
        self.mix = spec["traffic"]
        self.seed = int(seed)
        self.s31 = data.seed31(seed)
        self.control = control

    def setup(self) -> None:
        from repro.core import sparsify
        from repro.core.kernels_fn import gaussian

        n = int(self.conf["points"])
        self.x = data.nested(n, self.s31)
        self.xd = self.jax.numpy.asarray(self.x)
        self.bw = data.median_bandwidth(self.jax, self.xd)
        self.t = int(self.conf["edges_per_point"]) * n
        fn = sparsify.spectral_sparsify
        if self.control:
            fn = _with_bf16(sparsify)
        ker = gaussian(self.bw)

        def call(i):
            return fn(self.xd, ker, num_edges=self.t,
                      estimator=self.conf["estimator"],
                      exact_blocks=self.conf["exact_blocks"],
                      seed=self.s31 + i)

        self.call = call
        call(0)                                          # warm-up

    def trace_counts(self) -> dict:
        from repro.kernels.kde_sampler import ops
        return dict(ops.TRACE_COUNTS)

    def window(self, seconds: float, span) -> dict:
        graphs = []
        with span("window"):
            t0 = time.perf_counter()
            end = t0 + seconds
            i = 1
            while True:
                with span("call"):
                    graphs.append(self.call(i))
                i += 1
                now = time.perf_counter()
                if now >= end:
                    break
        return dict(graphs=graphs, calls=len(graphs), window_s=now - t0,
                    attempted=len(graphs), failed=0,
                    evals=sum(g.device_evals for g in graphs))

    def release(self) -> None:
        self.call = None
        gc.collect()

    def end_to_end(self, rec) -> dict:
        return {"sparsify_s": rec["window_s"] / rec["calls"]}

    def check(self, rec):
        n, t = len(self.x), self.t
        chk = self.mix["check"]
        deg = ref.degrees(self.jax, self.xd, 1.0 / self.bw ** 2)
        w_ref = deg.sum() / (2.0 * t)
        groups = int(chk["groups"])
        grp = np.random.default_rng([self.s31, 1]).permutation(n) % groups
        share = np.bincount(grp, weights=deg, minlength=groups) / deg.sum()
        bad, wrel = 0, 0.0
        ends = [np.zeros(groups), np.zeros(groups)]
        for g in rec["graphs"]:
            u, v, w = g.src, g.dst, g.weight
            bad += abs(len(u) - t) + int(np.sum(
                (u < 0) | (u >= n) | (v < 0) | (v >= n) | (u == v)
                | ~np.isfinite(w) | ~(w > 0)))
            if len(w):
                wrel = max(wrel, float(np.max(np.abs(w / w_ref - 1.0))))
            for c, e in zip(ends, (u, v)):
                e = e[(e >= 0) & (e < n)]
                c += np.bincount(grp[e], minlength=groups)
        disp = [float(np.sum((c - c.sum() * share) ** 2
                             / (c.sum() * share)) / (groups - 1))
                for c in ends]
        numbers = dict(bad_edges=bad, weight_rel=wrel,
                       edge_dispersion=max(disp))
        lim = chk["limits"]
        compared = {k: (v, lim[k]) for k, v in numbers.items()}
        info = dict(bandwidth=self.bw, src_dispersion=disp[0],
                    dst_dispersion=disp[1], calls=len(rec["graphs"]),
                    edges_checked=int(sum(len(g.src)
                                          for g in rec["graphs"])))
        return compared, info


def _with_bf16(sparsify):
    """``spectral_sparsify`` with the program's own bf16 level-1 path
    switched on (DESIGN.md §14): the control of the comparison."""
    import functools

    from repro.core.sampling.edge import NeighborSampler

    def fn(*a, **kw):
        orig = sparsify.NeighborSampler
        sparsify.NeighborSampler = functools.partial(NeighborSampler,
                                                     precision="bf16")
        try:
            return sparsify.spectral_sparsify(*a, **kw)
        finally:
            sparsify.NeighborSampler = orig
    return fn
