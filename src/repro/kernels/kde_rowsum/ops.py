"""jit'd public wrappers for the kde_rowsum Pallas kernel.

Handles padding to block multiples: padded x rows are placed at +PAD_OFFSET
in every coordinate, which drives the squared distance to f32 ``inf`` and
therefore every supported kernel to exactly 0 -- including heavy-tailed
rational quadratic with small beta, where a merely-large finite distance
would leave a non-negligible value.  No masking is needed inside the kernel.

Tile sizes: the f32 default keeps the legacy (bm, bn) layout so results stay
bitwise stable across releases; under ``precision="bf16"`` unset tiles are
resolved by ``kernels.tuning.pallas_tiles`` (halved operand bytes let the
tuner widen the x tile for more reuse per HBM byte).  Tuned sizes are pure
functions of static shapes, so they land in the same jit program cache keys
as the rest of the static config.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.kernels_fn import Kernel
from repro.kernels import platform as _platform
from repro.kernels import tuning as _tuning
from repro.kernels.kde_rowsum import kernel as _k
from repro.kernels.kde_rowsum import ref as _ref

# ||pad||^2 = d * 1e60 overflows f32 -> d2 = inf -> k = 0 for every kind.
_PAD_OFFSET = 1.0e30


def _pad_rows(a: jnp.ndarray, mult: int, offset: float) -> jnp.ndarray:
    n = a.shape[0]
    rem = (-n) % mult
    if rem == 0:
        return a
    pad = jnp.full((rem, a.shape[1]), offset, a.dtype) + a[-1:]
    return jnp.concatenate([a, pad], axis=0)


def _resolve_tiles(m, n, d, bm, bn, precision, default_bm, default_bn):
    """(bm, bn) with unset sizes filled in: legacy defaults on the f32
    path (bitwise stability), tuner output on the bf16 path."""
    if bm is not None and bn is not None:
        return bm, bn
    if precision == "f32":
        return (default_bm if bm is None else bm,
                default_bn if bn is None else bn)
    tbm, tbn = _tuning.pallas_tiles(m, n, d, precision)
    return (tbm if bm is None else bm), (tbn if bn is None else bn)


@functools.partial(jax.jit, static_argnames=("kind", "inv_bw", "beta", "bm", "bn", "interpret", "precision"))
def _rowsum(q, x, kind, inv_bw, beta, bm, bn, interpret, precision="f32"):
    m = q.shape[0]
    qp = _pad_rows(q, bm, 0.0)  # extra query rows are dropped after the call
    xp = _pad_rows(x, bn, _PAD_OFFSET)
    out = _k.rowsum_pallas(qp, xp, kind, inv_bw, beta, bm=bm, bn=bn,
                           interpret=interpret, precision=precision)
    return out[:m]


def kde_rowsum(q, x, kernel: Kernel, bm: int | None = None,
               bn: int | None = None, interpret: bool | None = None,
               precision: str = "f32") -> jnp.ndarray:
    """KDE oracle: (m,) row sums of the kernel matrix block k(q, x)."""
    interpret = _platform.interpret_mode(interpret)
    beta = getattr(kernel, "beta", 1.0)
    inv_bw = 1.0 / kernel.bandwidth
    q = jnp.asarray(q, jnp.float32)
    x = jnp.asarray(x, jnp.float32)
    bm, bn = _resolve_tiles(q.shape[0], x.shape[0], q.shape[1], bm, bn,
                            precision, 128, 512)
    return _rowsum(q, x, kernel.name, inv_bw, beta, bm, bn, interpret,
                   precision)


@functools.partial(jax.jit, static_argnames=("kind", "inv_bw", "beta", "bm", "bn", "interpret", "precision"))
def _blocksum(q, x, kind, inv_bw, beta, bm, bn, interpret, precision="f32"):
    m = q.shape[0]
    qp = _pad_rows(q, bm, 0.0)
    xp = _pad_rows(x, bn, _PAD_OFFSET)
    out = _k.blocksum_pallas(qp, xp, kind, inv_bw, beta, bm=bm, bn=bn,
                             interpret=interpret, precision=precision)
    return out[:m]


def kde_blocksum(q, x, kernel: Kernel, bm: int = 128, bn: int = 256,
                 interpret: bool | None = None,
                 precision: str = "f32") -> jnp.ndarray:
    """Level-1 read: (m, ceil(n/bn)) per-block kernel sums.  ``bn`` is the
    semantic level-1 block size (it fixes the output width), so it is
    never autotuned."""
    interpret = _platform.interpret_mode(interpret)
    inv_bw = 1.0 / kernel.bandwidth
    return _blocksum(jnp.asarray(q, jnp.float32), jnp.asarray(x, jnp.float32),
                     kernel.name, inv_bw, getattr(kernel, "beta", 1.0), bm,
                     bn, interpret, precision)


# re-exported oracles for tests
rowsum_ref = _ref.rowsum_ref
blocksum_ref = _ref.blocksum_ref
