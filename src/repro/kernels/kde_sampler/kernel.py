"""Pallas TPU kernel: fused level-1 read of the depth-2 neighbor sampler.

One pass over the dataset per query tile computes the masked per-block
kernel sums (self-kernel k(x, x) = 1 subtracted from each source's own
block, Alg 4.11 lines (c)/(d)) AND draws the block index by Gumbel-max over
``log(block_sum) + g`` -- so the sampler's block choice never materializes
an (m, B) matrix round-trip through the host (DESIGN.md §3).

Grid: (m/bm, B) with one x block per j-step.  The running Gumbel argmax,
the winning block's sum, and the total (= masked degree estimate) live in
(bm, LANES) VMEM scratch tiles and are flushed on the last j-step
(revisiting output pattern, identical to ``kde_rowsum``).  Per-block sums
and the Gumbel noise use the ``kde_rowsum`` lane-group layout: (bm, G)
tiles covering G consecutive blocks, column ``j % G`` per step.  The
own-block index arrives lane-broadcast as an (m, LANES) int32 tile.
Gumbel noise is drawn outside with ``jax.random`` and streamed in, so
compiled and interpret-mode runs are reproducible from one PRNGKey.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.kde_rowsum.kernel import (LANES, _tile_kernel_values,
                                             lane_group, put_column)
from repro.obs import metrics as _m

_FLOOR = 1e-12  # == ref.BLOCK_SUM_FLOOR


def _masked_sums(q_ref, own_ref, x_ref, j, kind, inv_bw, beta, precision):
    """(bm, 1) self-corrected, floored sums of x-block ``j``."""
    kv = _tile_kernel_values(q_ref[...], x_ref[...], kind, inv_bw, beta,
                             precision=precision)
    s = jnp.sum(kv, axis=1, keepdims=True)
    s = jnp.where(own_ref[:, :1] == j, s - 1.0, s)  # k(x, x) = 1 self mask
    return jnp.maximum(s, _FLOOR)


def _sample_block_kernel(q_ref, own_ref, g_ref, x_ref, blk_ref, pb_ref,
                         tot_ref, bs_ref, max_ref, arg_ref, best_ref,
                         acc_ref, *, kind, inv_bw, beta, precision, group):
    j = pl.program_id(1)
    col = j % group

    @pl.when(j == 0)
    def _():
        max_ref[...] = jnp.full_like(max_ref, -jnp.inf)
        arg_ref[...] = jnp.zeros_like(arg_ref)
        best_ref[...] = jnp.zeros_like(best_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(col == 0)
    def _():
        bs_ref[...] = jnp.zeros_like(bs_ref)

    s = _masked_sums(q_ref, own_ref, x_ref, j, kind, inv_bw, beta, precision)
    put_column(bs_ref, col, s)

    lane = jax.lax.broadcasted_iota(jnp.int32, g_ref.shape, 1)
    g = jnp.max(jnp.where(lane == col, g_ref[...], -jnp.inf), axis=1,
                keepdims=True)
    score = jnp.log(s) + g
    upd = score > max_ref[...]
    arg_ref[...] = jnp.where(upd, j, arg_ref[...])
    best_ref[...] = jnp.where(upd, s, best_ref[...])
    max_ref[...] = jnp.maximum(max_ref[...], score)
    acc_ref[...] += s

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        blk_ref[...] = arg_ref[...]
        tot_ref[...] = acc_ref[...]
        pb_ref[...] = best_ref[...] / acc_ref[...]


def _masked_blocksum_kernel(q_ref, own_ref, x_ref, bs_ref, *, kind, inv_bw,
                            beta, precision, group):
    j = pl.program_id(1)
    col = j % group

    @pl.when(col == 0)
    def _():
        bs_ref[...] = jnp.zeros_like(bs_ref)

    put_column(bs_ref, col, _masked_sums(q_ref, own_ref, x_ref, j, kind,
                                         inv_bw, beta, precision))


def _row_specs(bm, d, bn):
    """q tile, lane-broadcast own-block tile, x tile."""
    return (pl.BlockSpec((bm, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, LANES), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, d), lambda i, j: (j, 0)))


@_m.scope("level1")
def masked_blocksum_pallas(q: jnp.ndarray, x: jnp.ndarray, own: jnp.ndarray,
                           kind: str, inv_bw: float, beta: float = 1.0,
                           bm: int = 128, bn: int = 256,
                           interpret: bool = False,
                           precision: str = "f32") -> jnp.ndarray:
    """Masked level-1 block sums WITHOUT the in-pass block draw: the reverse
    probability read of the fused Algorithm 5.1 edge op (the sparsifier
    evaluates q(u | v) for already-drawn edges, so no Gumbel state is
    needed).  q (m, d), x (n, d), own (m, 1) int32 -> (m, n/bn) sums,
    self-corrected and floored exactly like ``sample_block_pallas``.
    m, n must be multiples of bm, bn; padded queries use own = -1."""
    m, d = q.shape
    nb = x.shape[0] // bn
    group, nbp = lane_group(nb)
    body = functools.partial(_masked_blocksum_kernel, kind=kind,
                             inv_bw=inv_bw, beta=beta, precision=precision,
                             group=group)
    bs = pl.pallas_call(
        body,
        name="_masked_blocksum_kernel",
        grid=(m // bm, nb),
        in_specs=list(_row_specs(bm, d, bn)),
        out_specs=pl.BlockSpec((bm, group), lambda i, j: (i, j // group)),
        out_shape=jax.ShapeDtypeStruct((m, nbp), jnp.float32),
        # consecutive x-blocks revisit one (bm, G) output tile
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(q, jnp.broadcast_to(own, (m, LANES)), x)
    return bs[:, :nb]


@_m.scope("level1")
def sample_block_pallas(q: jnp.ndarray, x: jnp.ndarray, own: jnp.ndarray,
                        gumbel: jnp.ndarray, kind: str, inv_bw: float,
                        beta: float = 1.0, bm: int = 128, bn: int = 256,
                        interpret: bool = False, precision: str = "f32"):
    """q (m, d), x (n, d), own (m, 1) int32, gumbel (m, n/bn) ->
    (blk (m,) int32, p_blk (m,), tot (m,), block_sums (m, n/bn)).
    m, n must be multiples of bm, bn; padded queries use own = -1."""
    m, d = q.shape
    nb = x.shape[0] // bn
    group, nbp = lane_group(nb)
    body = functools.partial(_sample_block_kernel, kind=kind, inv_bw=inv_bw,
                             beta=beta, precision=precision, group=group)
    q_spec, own_spec, x_spec = _row_specs(bm, d, bn)
    row = pl.BlockSpec((bm, LANES), lambda i, j: (i, 0))
    tile = pl.BlockSpec((bm, group), lambda i, j: (i, j // group))
    gp = jnp.pad(gumbel, ((0, 0), (0, nbp - nb)))
    blk, pb, tot, bs = pl.pallas_call(
        body,
        name="_sample_block_kernel",
        grid=(m // bm, nb),
        in_specs=[q_spec, own_spec, tile, x_spec],
        out_specs=[row, row, row, tile],
        out_shape=[jax.ShapeDtypeStruct((m, LANES), jnp.int32),
                   jax.ShapeDtypeStruct((m, LANES), jnp.float32),
                   jax.ShapeDtypeStruct((m, LANES), jnp.float32),
                   jax.ShapeDtypeStruct((m, nbp), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bm, LANES), jnp.float32),
                        pltpu.VMEM((bm, LANES), jnp.int32),
                        pltpu.VMEM((bm, LANES), jnp.float32),
                        pltpu.VMEM((bm, LANES), jnp.float32)],
        # the Gumbel argmax carries VMEM state across j, so the x-block
        # axis is "arbitrary" (sequential revisit); query tiles pipeline
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(q, jnp.broadcast_to(own, (m, LANES)), gp, x)
    return blk[:, 0], pb[:, 0], tot[:, 0], bs[:, :nb]
