"""KDE data structures (Definition 1.1).

A KDE structure over a fixed dataset ``X`` answers queries
``KDE_X(y) ~= sum_{x in X} k(x, y)`` within ``(1 +- eps)`` multiplicative
error, assuming ``k(x, y) >= tau``.  The paper uses these strictly as black
boxes; everything in ``repro.core`` is written against this interface.

Backends
--------
* ``ExactKDE``      -- brute force oracle (the Pallas ``kde_rowsum`` kernel on
                       TPU; a blocked jnp sweep elsewhere).
* ``RSKDE``         -- uniform random sampling, the ``p = 1`` estimator the
                       paper describes in Section 3.1.
* ``StratifiedKDE`` -- beyond-paper variance reduction: the dataset is split
                       into contiguous blocks and each block contributes an
                       independent uniform subsample (same cost as RS, strictly
                       lower variance; on TPU every block is one VMEM tile).
* ``GridHBE``       -- practical hash-based estimator (``hbe.py``), host
                       per-query loop; kept as the oracle of
* ``HashedKDE``     -- the device-resident hashed estimator
                       (``hashed.py`` / ``kernels/kde_hash``): the same
                       KAP22 near/far decomposition as ONE jitted program
                       per query batch, O(max_bucket + num_far) kernel
                       evals per query (the paper's sub-linear black box).

All estimators count kernel evaluations (``.evals``) -- the paper's headline
cost metric in Section 7.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.kernels_fn import Kernel
from repro.kernels import platform as _platform
from repro.obs import counters as _c


@functools.partial(jax.jit, static_argnames=("pairwise",))
def _rowsum(pairwise, y, x):
    return jnp.sum(pairwise(y, x), axis=1)


@functools.partial(jax.jit, static_argnames=("kind", "inv_bw", "beta", "bn"))
def _bf16_rowsum(y, x, kind, inv_bw, beta, bn):
    """bf16 level-1 sweep reduced to row sums: the blocked column-tile scan
    of ``kv_block_sums_bf16`` (bf16 operand tiles, f32 accumulation) keeps
    peak memory at O(m * n / bn) instead of the full (m, n) value matrix."""
    from repro.kernels.kde_sampler.ref import kv_block_sums_bf16
    return jnp.sum(kv_block_sums_bf16(y, x, kind, inv_bw, beta, bn=bn),
                   axis=-1)


class KDEBase:
    """Common interface: query(y: (m, d)) -> (m,) estimated row sums.

    ``precision`` (DESIGN.md §14) selects the dtype policy of the level-1
    dataset sweeps: ``"f32"`` (default; bitwise-stable legacy path) or
    ``"bf16"`` (rounded operand tiles, f32 accumulators).  Level-2 rows,
    CDFs, and sampling probabilities always stay f32.
    """

    def __init__(self, x: jnp.ndarray, kernel: Kernel,
                 precision: str = "f32"):
        self.x = jnp.asarray(x, jnp.float32)
        # ||x_j||^2, computed once and reused by every L2-kernel query
        # (the level-1/level-2 reads never recompute dataset norms).
        self.x_sq = jnp.sum(self.x * self.x, axis=-1)
        self.kernel = kernel
        self.n = int(x.shape[0])
        self.d = int(x.shape[1])
        self.evals = 0  # number of kernel evaluations performed (analytic)
        # realized device-side totals (DESIGN.md §15.1), folded from the
        # counter words of every fused program this estimator runs
        self.device_counters = _c.HostTotals()
        self.precision = precision
        if precision != "f32":
            from repro.kernels.kde_sampler.ref import (check_precision,
                                                       static_pairwise)
            check_precision(precision, kernel.name, static_pairwise(kernel))

    def query(self, y: jnp.ndarray) -> jnp.ndarray:
        """(m, d) queries -> (m,) estimated row sums sum_j k(y_i, x_j)."""
        raise NotImplementedError

    def query1(self, y: jnp.ndarray) -> float:
        """Single-point convenience wrapper around ``query``."""
        return float(self.query(y[None, :])[0])


class ExactKDE(KDEBase):
    """Brute-force oracle; the Pallas kernel computes this on TPU
    (``use_pallas=None`` lets ``kernels.platform`` decide)."""

    def __init__(self, x, kernel: Kernel, chunk: int = 8192,
                 use_pallas: Optional[bool] = None, precision: str = "f32"):
        super().__init__(x, kernel, precision=precision)
        self.chunk = chunk
        self.use_pallas = _platform.resolve(use_pallas, None, kernel.name)[0]

    def query(self, y: jnp.ndarray) -> jnp.ndarray:
        """Exact row sums; m*n kernel evals per call."""
        y = jnp.asarray(y, jnp.float32)
        self.evals += y.shape[0] * self.n
        if self.use_pallas:
            from repro.kernels.kde_rowsum import ops as rs_ops
            return rs_ops.kde_rowsum(y, self.x, self.kernel,
                                     precision=self.precision)
        if self.precision != "f32":
            return _bf16_rowsum(y, self.x, self.kernel.name,
                                1.0 / self.kernel.bandwidth,
                                getattr(self.kernel, "beta", 1.0),
                                bn=min(self.chunk, 1024))
        out = jnp.zeros((y.shape[0],), jnp.float32)
        for lo in range(0, self.n, self.chunk):
            out = out + _rowsum(self.kernel.pairwise, y, self.x[lo:lo + self.chunk])
        return out


class RSKDE(KDEBase):
    """Random-sampling estimator (p = 1): n/|R| * sum_{x in R} k(x, y).

    ``num_samples = O(1/(tau * eps^2))`` per Section 3.1.
    """

    def __init__(self, x, kernel: Kernel, num_samples: int, seed: int = 0,
                 precision: str = "f32"):
        super().__init__(x, kernel, precision=precision)
        self.num_samples = min(int(num_samples), self.n)
        self._rng = np.random.default_rng(seed)

    def query(self, y: jnp.ndarray) -> jnp.ndarray:
        """(1 +- eps) row-sum estimates; m*num_samples evals per call."""
        y = jnp.asarray(y, jnp.float32)
        idx = self._rng.integers(0, self.n, size=self.num_samples)
        self.evals += y.shape[0] * self.num_samples
        sub = self.x[jnp.asarray(idx)]
        if self.precision != "f32":
            return _bf16_rowsum(y, sub, self.kernel.name,
                                1.0 / self.kernel.bandwidth,
                                getattr(self.kernel, "beta", 1.0),
                                bn=min(self.num_samples, 1024)) \
                * (self.n / self.num_samples)
        return _rowsum(self.kernel.pairwise, y, sub) * (self.n / self.num_samples)


class StratifiedKDE(KDEBase):
    """Blocked stratified sampling: per-block uniform subsamples.

    Unbiased: each block contributes |block| * mean(sampled kernel values) --
    the tail block scales by its *realized* sample count, so padded slots
    never inflate the estimate.  Variance is the within-block variance only
    -- strictly <= RS variance at equal sample count (law of total
    variance).  This is the TPU-native estimator: each block is a contiguous
    VMEM tile and the subsample is a strided load.

    ``block_sums`` is a single jitted device program (subsample indices are
    drawn with ``jax.random`` inside the trace); no per-block host loop.
    """

    def __init__(self, x, kernel: Kernel, block_size: int = 256,
                 samples_per_block: int = 16, seed: int = 0,
                 precision: str = "f32"):
        super().__init__(x, kernel, precision=precision)
        self.block_size = int(block_size)
        self.num_blocks = (self.n + self.block_size - 1) // self.block_size
        self.samples_per_block = min(int(samples_per_block), self.block_size)
        self._key = jax.random.PRNGKey(seed)

    def _block_bounds(self, b: int):
        lo = b * self.block_size
        return lo, min(lo + self.block_size, self.n)

    def _split(self) -> jnp.ndarray:
        self._key, k = jax.random.split(self._key)
        return k

    def _static_cfg(self) -> dict:
        from repro.kernels.kde_sampler.ref import static_pairwise
        return dict(kind=self.kernel.name, inv_bw=1.0 / self.kernel.bandwidth,
                    beta=getattr(self.kernel, "beta", 1.0),
                    pairwise=static_pairwise(self.kernel),
                    block_size=self.block_size,
                    num_blocks=self.num_blocks, n=self.n,
                    precision=self.precision)

    def block_sums(self, y: jnp.ndarray) -> jnp.ndarray:
        """(m, B) estimated per-block kernel sums -- the level-1 'tree' read."""
        from repro.kernels.kde_sampler import ops as sampler_ops
        y = jnp.asarray(y, jnp.float32)
        self.evals += y.shape[0] * self.num_blocks * self.samples_per_block
        bs, cw = sampler_ops.stratified_block_sums(
            y, self.x, self.x_sq, self._split(), s=self.samples_per_block,
            **self._static_cfg())
        self.device_counters.note(cw)
        return bs

    def query(self, y: jnp.ndarray) -> jnp.ndarray:
        """Stratified row-sum estimates; m*B*s evals per call."""
        return jnp.sum(self.block_sums(y), axis=-1)


class ExactBlockKDE(StratifiedKDE):
    """Exact per-block sums (one dense sweep); deterministic ``block_sums``.

    Used where the sparsifier needs *reproducible* sampling probabilities
    (Algorithm 5.1 computes the probability q_uv with which the sampler picks
    an edge; a deterministic level-1 read makes q exactly recomputable).

    On the Pallas path (the default on TPU, ``kernels.platform``) the
    sweep dispatches to the ``blocksum_pallas`` kernel; otherwise it is one
    jitted jnp program reusing the precomputed ``x_sq`` norms.
    """

    def __init__(self, x, kernel: Kernel, block_size: int = 256,
                 use_pallas: Optional[bool] = None, precision: str = "f32"):
        super().__init__(x, kernel, block_size=block_size,
                         samples_per_block=block_size, precision=precision)
        self.use_pallas = _platform.resolve(use_pallas, None, kernel.name)[0]

    def block_sums(self, y: jnp.ndarray) -> jnp.ndarray:
        """Exact (m, B) per-block sums; m*n evals per call."""
        y = jnp.asarray(y, jnp.float32)
        self.evals += y.shape[0] * self.n
        if self.use_pallas:
            from repro.kernels.kde_rowsum import ops as rs_ops
            bs = rs_ops.kde_blocksum(y, self.x, self.kernel,
                                     bn=self.block_size,
                                     precision=self.precision)
            # the word the jnp program would return: its counters are the
            # same static shape products
            self.device_counters.note(_c.word(evals=y.shape[0] * self.n,
                                              l1_reads=y.shape[0]))
            return bs
        from repro.kernels.kde_sampler import ops as sampler_ops
        bs, cw = sampler_ops.exact_block_sums(y, self.x, self.x_sq,
                                              **self._static_cfg())
        self.device_counters.note(cw)
        return bs


def make_estimator(name: str, x, kernel: Kernel, seed: int = 0,
                   tau: float = 0.05, eps: float = 0.5, **kw) -> KDEBase:
    """Factory.  ``rs``/``stratified`` budgets default to O(1/(tau eps^2)).

    All estimators accept ``precision="f32"|"bf16"`` (forwarded via ``kw``):
    the level-1 sweep dtype policy of DESIGN.md §14."""
    if name == "exact":
        return ExactKDE(x, kernel, **kw)
    if name == "rs":
        ns = kw.pop("num_samples", int(np.ceil(1.0 / (tau * eps * eps))))
        return RSKDE(x, kernel, num_samples=ns, seed=seed, **kw)
    if name == "stratified":
        return StratifiedKDE(x, kernel, seed=seed, **kw)
    if name == "exact_block":
        return ExactBlockKDE(x, kernel, **kw)
    if name == "grid_hbe":
        from repro.core.kde.hbe import GridHBE
        return GridHBE(x, kernel, seed=seed, **kw)
    if name == "hash":
        from repro.core.kde.hashed import HashedKDE
        return HashedKDE(x, kernel, seed=seed, **kw)
    if name == "robust":
        from repro.ft.guards import RobustEstimator
        return RobustEstimator(x, kernel, seed=seed, **kw)
    raise ValueError(f"unknown estimator {name!r}")
