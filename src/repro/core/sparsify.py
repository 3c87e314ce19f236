"""Spectral sparsification of the kernel graph -- Algorithm 5.1 / Theorem 5.3.

Length-squared sampling of the edge-vertex incidence matrix H
(||H_{uv}||^2 = 2 k(u,v)) approximates leverage-score sampling up to the
condition number kappa(H)^2 <= 32/tau^3 (Lemma 5.6), so
t = O(n log n / (eps^2 tau^3)) sampled edges give a (1 +- eps) spectral
sparsifier (Lemma 5.5).

Per Algorithm 5.1 we do NOT use the perfect edge sampler -- we sample
u ~ p_hat (degrees), v ~ q_hat(.|u) (neighbor sampler), and reweight each
drawn edge by 1 / (t * (p_u q_uv + p_v q_vu)), querying the samplers for the
exact probabilities they used.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax.numpy as jnp
import numpy as np

from repro.core.kernels_fn import Kernel
from repro.core.sampling.edge import NeighborSampler, shared_level1_estimator
from repro.core.sampling.vertex import DegreeSampler
from repro.obs import metrics as _m


@dataclasses.dataclass
class SparseGraph:
    """Fixed-size COO edge list (undirected; i < j not enforced)."""
    n: int
    src: np.ndarray       # (m,) int64
    dst: np.ndarray       # (m,) int64
    weight: np.ndarray    # (m,) float64
    kde_queries: int = 0
    kernel_evals: int = 0
    device_evals: int = 0     # the same count, from the counter words
    device_psums: int = 0     # collective psums, from the counter words

    @property
    def num_edges(self) -> int:
        """Number of (possibly repeated) sampled edges."""
        return len(self.src)

    def laplacian_dense(self) -> np.ndarray:
        """Dense Laplacian (evaluation only)."""
        a = np.zeros((self.n, self.n))
        np.add.at(a, (self.src, self.dst), self.weight)
        np.add.at(a, (self.dst, self.src), self.weight)
        d = a.sum(axis=1)
        return np.diag(d) - a

    def adjacency_dense(self) -> np.ndarray:
        """Dense symmetric adjacency (evaluation only)."""
        a = np.zeros((self.n, self.n))
        np.add.at(a, (self.src, self.dst), self.weight)
        np.add.at(a, (self.dst, self.src), self.weight)
        return a

    def matvec(self, v: np.ndarray) -> np.ndarray:
        """L v without materializing L."""
        av = np.zeros_like(v)
        wsrc = self.weight * v[self.dst]
        wdst = self.weight * v[self.src]
        np.add.at(av, self.src, wsrc)
        np.add.at(av, self.dst, wdst)
        deg = np.zeros_like(v)
        np.add.at(deg, self.src, self.weight)
        np.add.at(deg, self.dst, self.weight)
        return deg * v - av


def spectral_sparsify(x, kernel: Kernel, num_edges: int,
                      estimator: str = "stratified", seed: int = 0,
                      batch: int = 1024, exact_blocks: bool = False,
                      samples_per_block: int = 16,
                      mesh=None) -> SparseGraph:
    """Algorithm 5.1 with edge budget ``num_edges`` (= t).

    Fully fused (DESIGN.md §6): ONE device dataset + level-1 structure is
    shared between degree preprocessing and the neighbor sampler, the
    degree CDF lives on device (float64-accumulated prefix, rounded to
    f32), and all edge batches -- steps (a)-(d) including the reverse
    probability q_vu and the reweighting -- run as one ``lax.scan``
    program with a single device->host transfer of the edge list.  With
    ``mesh=`` the same program runs sharded (DESIGN.md §9): the level-1
    state is mesh-resident and each edge batch performs one psum.

    With ``estimator="hash"`` BOTH the Algorithm 4.3 degree preprocessing
    and the per-edge level-1 reads run on the sub-linear hashed estimator
    (one shared bucket layout, DESIGN.md §10): total kernel evals drop
    from O((n + t) B s) to O((n + t)(max_bucket + num_far)).  On the
    ``mesh=`` path the hashed hybrid covers degrees only (the collective
    draws stay on the §9 blocked engine).
    """
    with _m.span("sparsify.call", edges=int(num_edges)):
        return _sparsify(x, kernel, int(num_edges), estimator, seed, batch,
                         exact_blocks, samples_per_block, mesh)


def _sparsify(x, kernel, t, estimator, seed, batch, exact_blocks,
              samples_per_block, mesh) -> SparseGraph:
    """The four phases of :func:`spectral_sparsify`, each under its span:
    sampler build, degree sweep, edge scan, graph assembly."""
    n = int(x.shape[0])
    with _m.span("sparsify.sampler"):
        nbr = NeighborSampler(x, kernel, mode="blocked", seed=seed + 2,
                              exact_blocks=exact_blocks,
                              samples_per_block=samples_per_block,
                              mesh=mesh,
                              level1="hash" if estimator == "hash"
                              and mesh is None else "blocked")
        # Degree preprocessing (Algorithm 4.3) against the sampler's own
        # level-1 structure whenever it implements the requested estimator
        # -- one KDE build and one preprocessing sweep over x, not two.
        # The sampler's structure is exact (ExactBlockKDE) iff
        # exact_blocks.
        est = shared_level1_estimator(nbr, estimator, seed=seed)
    with _m.span("sparsify.degrees"):
        deg = DegreeSampler(est, seed=seed + 1,
                            mesh=mesh if est is nbr.blocks else None)
        cdf, degs, total = deg.cdf_device, deg.degrees_device, deg.total
    # the mesh engine's schedule: one psum per edge batch (DESIGN.md §9)
    shards = nbr.blocks.engine.num_shards if mesh is not None else 1
    psums = max(-(-t // batch), 1) if mesh is not None else 0
    with _m.span("sparsify.edges", psums=psums, shards=shards):
        u, v, w, _, _ = nbr.edge_batches(cdf, degs, total, t, batch=batch)
    with _m.span("sparsify.graph"):
        g = SparseGraph(n, np.asarray(u, np.int64), np.asarray(v, np.int64),
                        np.asarray(w, np.float64))
        g.kernel_evals = nbr.evals + (0 if est is nbr.blocks else est.evals)
        est_words = getattr(est, "device_counters", None)
        g.device_evals, g.device_psums = (
            nbr.device_counters[k] + (est_words[k] if est_words is not None
                                      else 0) for k in ("evals", "psums"))
    # degree preprocessing + one forward level-1 read per drawn edge (the
    # reverse probability collapses onto the preprocessed degrees)
    drawn = ((t + batch - 1) // batch) * batch
    g.kde_queries = n + drawn
    return g


def resparsify(g: SparseGraph, num_edges: int, seed: int = 0) -> SparseGraph:
    """Second-stage size reduction (the paper invokes Lee-Sun to reach
    O(n/eps^2) edges; we re-apply length-squared sampling on the explicit
    graph, which needs no KDE queries -- same role, simpler machinery)."""
    rng = np.random.default_rng(seed)
    p = g.weight / g.weight.sum()
    idx = rng.choice(g.num_edges, size=num_edges, p=p, replace=True)
    w = g.weight[idx] / (num_edges * p[idx])
    return SparseGraph(g.n, g.src[idx], g.dst[idx], w,
                       kde_queries=g.kde_queries, kernel_evals=g.kernel_evals)


def incidence_row_norms(kernel: Kernel, x) -> np.ndarray:
    """||H_{uv}||^2 = 2 k(u, v) -- test helper for Lemma 5.6 invariants."""
    k = np.asarray(kernel.matrix(jnp.asarray(x)))
    iu = np.triu_indices(k.shape[0], 1)
    return 2.0 * k[iu]
