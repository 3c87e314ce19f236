"""Pallas TPU kernel: weighted bucket-gather kernel evaluation.

The hashed estimator's hot loop is "evaluate k(q_i, x_j) over each query's
gathered (bucket member + FAR sample) rows and weight them by per-slot HT
weights".  The gather itself is an XLA gather (dense (w, t, d) member
coordinates); this kernel fuses the kernel-value math and the weighting
over one (query tile, slot tile), keeping the (bm, tt, d) gathered rows in
VMEM for a single pass.  ``weighted_kv_pallas`` returns the (m, t)
weighted values: the hashed query sums them (and screens them for a
dominating HT sample), the hashed level-1 read scatters them into blocks
(DESIGN.md §10).

The kernel-value math is ``ref.rowwise_kv`` itself, so interpret-mode runs
reproduce the jnp oracle bitwise.  The slot axis is tiled only when a
whole (bm, t, d) row tile would not fit ``XR_TILE_BYTES`` of VMEM; slot
tiles are lane-dense (multiples of 128, padded slots carry weight 0).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.kde_hash import ref as _ref

#: VMEM budget of one staged (bm, tt, d) gathered-row tile (double-buffered
#: by the pipeliner, so twice this is resident)
XR_TILE_BYTES = 2 * 1024 * 1024


def slot_tile(bm: int, t: int, d: int) -> int:
    """Slot-axis tile width: all ``t`` slots when the row tile fits the
    budget, else the widest multiple of 128 that does.  The feature axis
    is the tile's lane axis, so VMEM holds it padded to 128 lanes."""
    row_bytes = bm * (-(-d // 128) * 128) * 4
    if t * row_bytes <= XR_TILE_BYTES:
        return t
    return max(128, XR_TILE_BYTES // row_bytes // 128 * 128)


def _weighted_kv_kernel(q_ref, w_ref, xr_ref, o_ref, *, kind, inv_bw, beta,
                        precision):
    kv = _ref.rowwise_kv(q_ref[...], xr_ref[...], kind, inv_bw, beta,
                         precision=precision)
    o_ref[...] = kv * w_ref[...]


def weighted_kv_pallas(q: jnp.ndarray, wgt: jnp.ndarray, xr: jnp.ndarray,
                       kind: str, inv_bw: float, beta: float = 1.0,
                       bm: int = 32, interpret: bool = False,
                       precision: str = "f32"):
    """q (m, d), wgt (m, t), xr (m, t, d) -> (m, t) weighted kernel values
    ``wgt_ij k(q_i, xr_ij)``; m must be a multiple of bm."""
    m, d = q.shape
    t = xr.shape[1]
    tt = slot_tile(bm, t, d)
    tp = -(-t // tt) * tt
    if tp != t:
        wgt = jnp.pad(wgt, ((0, 0), (0, tp - t)))
        xr = jnp.pad(xr, ((0, 0), (0, tp - t), (0, 0)))
    body = functools.partial(_weighted_kv_kernel, kind=kind, inv_bw=inv_bw,
                             beta=beta, precision=precision)
    out = pl.pallas_call(
        body,
        grid=(m // bm, tp // tt),
        in_specs=[pl.BlockSpec((bm, d), lambda i, j: (i, 0)),
                  pl.BlockSpec((bm, tt), lambda i, j: (i, j)),
                  pl.BlockSpec((bm, tt, d), lambda i, j: (i, j, 0))],
        out_specs=pl.BlockSpec((bm, tt), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, tp), jnp.float32),
        # one output tile per (query tile, slot tile), no cross-step
        # state: both axes pipeline the gathered-row copies
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(q, wgt, xr)
    return out[:, :t]
