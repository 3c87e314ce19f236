"""Pallas TPU kernel: blocked kernel-row-sums (the KDE hot spot).

Computes ``out[i] = sum_j k(q_i, x_j)`` (Definition 1.1 oracle) and the
per-block variant ``out[i, b] = sum_{j in block b} k(q_i, x_j)`` (the level-1
read of the depth-2 sampler, DESIGN.md §2).

Tiling: q tiles (bm, d) and x tiles (bn, d) stream HBM->VMEM; for L2 kernels
(gaussian / exponential / rational quadratic) the pairwise distances use the
MXU via the ||q||^2 + ||x||^2 - 2 q.x factorization (f32 operands at full
f32 contract precision); the L1 (laplacian) kernel has no matmul form, so
|q - x| is accumulated over d-chunks on the VPU.

Every block the chip sees is a lane-dense 2-D tile (the Mosaic (8, 128)
rule): per-row results live in ``(bm, LANES)`` tiles holding the value
broadcast across the lanes (the wrappers read lane 0), and per-block sums
are written into ``(bm, G)`` output tiles that cover G consecutive x-blocks
(``lane_group``): grid step j writes column ``j % G`` of the tile that
block index ``j // G`` revisits, so the x-block axis is "arbitrary"
(sequential revisit) and the query axis "parallel".

``precision="bf16"`` (DESIGN.md §14) rounds both operand tiles to bf16 --
halving the MXU operand bytes -- while the distance accumulation (MXU
``preferred_element_type``), the kernel transform, and every downstream sum
stay f32.  The norm terms are recomputed in f32 from the *rounded*
coordinates so the bf16 path is a pure function of the bf16 operands
(bitwise-matched by the jnp refs).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.kde_sampler.ref import _finish_l2_bf16, check_precision
from repro.obs import metrics as _m

_L2_KINDS = ("gaussian", "exponential", "rational_quadratic")

#: lane width of a TPU vreg: per-row outputs are (bm, LANES) tiles
LANES = 128

_HIGHEST = jax.lax.Precision.HIGHEST


def lane_group(num_blocks: int) -> tuple[int, int]:
    """(G, padded block count) for an (m, num_blocks) per-block output:
    one (bm, G) tile covers G consecutive x-blocks, G = num_blocks when it
    fits one lane tile, else LANES with the block axis padded to a LANES
    multiple (the pad columns stay 0 and are sliced off)."""
    if num_blocks <= LANES:
        return num_blocks, num_blocks
    return LANES, -(-num_blocks // LANES) * LANES


def put_column(o_ref, col, s):
    """Write the (bm, 1) column ``s`` into lane ``col`` of the (bm, G)
    output tile -- a lane-masked select, so every store is a full tile."""
    lane = jax.lax.broadcasted_iota(jnp.int32, o_ref.shape, 1)
    o_ref[...] = jnp.where(lane == col, s, o_ref[...])


def _tile_kernel_values(q, x, kind: str, inv_bw: float, beta: float,
                        d_chunk: int = 128, precision: str = "f32"):
    """(bm, bn) kernel values for one (q-tile, x-tile) pair."""
    if precision != "f32":
        check_precision(precision, kind, None)
        qb = q.astype(jnp.bfloat16)
        xb = x.astype(jnp.bfloat16)
        qf = qb.astype(jnp.float32)
        xf = xb.astype(jnp.float32)
        qq = jnp.sum(qf * qf, axis=1, keepdims=True)
        xx = jnp.sum(xf * xf, axis=1, keepdims=True).T
        cross = jax.lax.dot_general(qb, xb, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
        d2 = jnp.maximum(qq + xx - 2.0 * cross, 0.0)
        return _finish_l2_bf16(d2, kind, inv_bw, beta)
    if kind in _L2_KINDS:
        qq = jnp.sum(q * q, axis=1, keepdims=True)
        xx = jnp.sum(x * x, axis=1, keepdims=True).T
        cross = jax.lax.dot_general(q, x, (((1,), (1,)), ((), ())),
                                    precision=_HIGHEST,
                                    preferred_element_type=jnp.float32)
        d2 = jnp.maximum(qq + xx - 2.0 * cross, 0.0)
        if kind == "gaussian":
            return jnp.exp(-d2 * (inv_bw * inv_bw))
        if kind == "exponential":
            return jnp.exp(-jnp.sqrt(d2) * inv_bw)
        return (1.0 + d2 * (inv_bw * inv_bw)) ** (-beta)
    # laplacian: accumulate |q - x| over d-chunks (VPU path).
    d = q.shape[1]
    steps = (d + d_chunk - 1) // d_chunk
    acc = jnp.zeros((q.shape[0], x.shape[0]), jnp.float32)
    for s in range(steps):  # static unroll: d is a compile-time constant
        lo = s * d_chunk
        hi = min(lo + d_chunk, d)
        acc = acc + jnp.sum(
            jnp.abs(q[:, None, lo:hi] - x[None, :, lo:hi]), axis=-1)
    return jnp.exp(-acc * inv_bw)


def _rowsum_kernel(q_ref, x_ref, o_ref, acc_ref, *, kind, inv_bw, beta,
                   precision):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kv = _tile_kernel_values(q_ref[...], x_ref[...], kind, inv_bw, beta,
                             precision=precision)
    acc_ref[...] += jnp.sum(kv, axis=1, keepdims=True)

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        o_ref[...] = acc_ref[...]


def _blocksum_kernel(q_ref, x_ref, o_ref, *, kind, inv_bw, beta, precision,
                     group):
    col = pl.program_id(1) % group

    @pl.when(col == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    kv = _tile_kernel_values(q_ref[...], x_ref[...], kind, inv_bw, beta,
                             precision=precision)
    put_column(o_ref, col, jnp.sum(kv, axis=1, keepdims=True))


@_m.scope("level1")
def rowsum_pallas(q: jnp.ndarray, x: jnp.ndarray, kind: str, inv_bw: float,
                  beta: float = 1.0, bm: int = 128, bn: int = 512,
                  interpret: bool = False,
                  precision: str = "f32") -> jnp.ndarray:
    """q (m, d), x (n, d) -> (m,); m, n must be multiples of bm, bn."""
    m, d = q.shape
    n = x.shape[0]
    body = functools.partial(_rowsum_kernel, kind=kind, inv_bw=inv_bw,
                             beta=beta, precision=precision)
    out = pl.pallas_call(
        body,
        name="_rowsum_kernel",
        grid=(m // bm, n // bn),
        in_specs=[pl.BlockSpec((bm, d), lambda i, j: (i, 0)),
                  pl.BlockSpec((bn, d), lambda i, j: (j, 0))],
        out_specs=pl.BlockSpec((bm, LANES), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, LANES), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, LANES), jnp.float32)],
        # the row accumulator is a cross-j VMEM carry, so the x-block axis
        # must stay sequential; query tiles double-buffer in parallel
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(q, x)
    return out[:, 0]


@_m.scope("level1")
def blocksum_pallas(q: jnp.ndarray, x: jnp.ndarray, kind: str, inv_bw: float,
                    beta: float = 1.0, bm: int = 128, bn: int = 256,
                    interpret: bool = False,
                    precision: str = "f32") -> jnp.ndarray:
    """q (m, d), x (n, d) -> (m, n/bn) per-block sums (level-1 read)."""
    m, d = q.shape
    nb = x.shape[0] // bn
    group, nbp = lane_group(nb)
    body = functools.partial(_blocksum_kernel, kind=kind, inv_bw=inv_bw,
                             beta=beta, precision=precision, group=group)
    out = pl.pallas_call(
        body,
        name="_blocksum_kernel",
        grid=(m // bm, nb),
        in_specs=[pl.BlockSpec((bm, d), lambda i, j: (i, 0)),
                  pl.BlockSpec((bn, d), lambda i, j: (j, 0))],
        out_specs=pl.BlockSpec((bm, group), lambda i, j: (i, j // group)),
        out_shape=jax.ShapeDtypeStruct((m, nbp), jnp.float32),
        # consecutive x-blocks revisit one (bm, G) output tile
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(q, x)
    return out[:, :nb]
