"""Pure-jnp oracle + shared hashing math for the device hashed-KDE engine.

The KAP22/DEANN decomposition (Section 3.1 black-box slot) splits a KDE
query into an exact NEAR term over the query's random-shifted grid bucket
and a Horvitz-Thompson FAR term over uniform samples of the complement:

    KDE(y) = sum_{x in NEAR(y)} k(x, y)  +  (n/s) * sum_j k(x_{i_j}, y) *
                                             1{x_{i_j} not in NEAR(y)}

Unlike ``GridHBE``'s ratio correction, the HT weight ``n/s`` has a *known*
inclusion probability, so the FAR term is exactly unbiased for ANY bucket
assignment (including truncated buckets whose overflow members simply stay
FAR-eligible) and has no degenerate all-samples-collide case -- the
estimate is then 0, still unbiased over the draw.

Everything here is shared verbatim by ``ops.py`` (the jnp fallback path IS
these functions) and by the Pallas kernel body (``rowwise_kv`` runs inside
the kernel), so interpret-mode runs match the oracle bitwise.  The bucket
layout itself (``HashState``) is built once on the host by
``ops.build_hash_state`` and passed to every jitted program as a pytree of
device arrays -- bucket membership of a *dataset* point is a dense
``point_bucket`` gather, never a ``searchsorted``.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels.kde_sampler.ref import (BLOCK_SUM_FLOOR, _L2_KINDS,
                                           _finish_l2, _finish_l2_bf16,
                                           check_precision)

# Knuth's 2^32 golden-ratio multiplier; uint32 multiply-add wraps
# identically in numpy (host build) and jnp (device query hashing).
HASH_MULT = 2654435761


class HashState(NamedTuple):
    """Device-resident padded-bucket layout (one pytree, all arrays).

    ``members`` holds GLOBAL dataset row indices, ``max_bucket`` slots per
    bucket with slot >= counts[b] as sentinel padding; buckets larger than
    ``max_bucket`` store a seeded subsample and their overflow members stay
    FAR-eligible (the HT weight needs no correction for this).
    """

    dims: jnp.ndarray          # (h,)  int32  hashed coordinate subset
    shift: jnp.ndarray         # (h,)  f32    random grid shift
    keys: jnp.ndarray          # (U,)  uint32 sorted packed bucket keys
    members: jnp.ndarray       # (U, max_bucket) int32 global row indices
    counts: jnp.ndarray        # (U,)  int32  stored member count
    point_bucket: jnp.ndarray  # (n,)  int32  bucket id of each dataset row
    self_stored: jnp.ndarray   # (n,)  f32    1.0 iff the row is stored in
    #                                         its own bucket's slots
    truncated: jnp.ndarray = None  # (U,) bool  bucket overflowed max_bucket
    #                                (optional so older pickled/sharded
    #                                layouts keep working; None reads as
    #                                "no bucket truncated")
    overflow: jnp.ndarray = None   # (ov_cap,) int32 streaming overflow
    #                                region: row ids whose bucket is full or
    #                                whose grid cell is not in the frozen
    #                                ``keys`` (-1 = free slot).  Queries and
    #                                frontier reads sweep it EXACTLY (weight
    #                                1) until a lazy compaction folds it
    #                                back into the bucket layout
    #                                (DESIGN.md §12); None = static dataset.


def pack_codes(codes: jnp.ndarray) -> jnp.ndarray:
    """(m, h) int32 grid codes -> (m,) uint32 keys by wraparound
    multiply-add hashing (one multiplier pass per hashed dimension)."""
    h = jnp.zeros(codes.shape[0], jnp.uint32)
    mult = jnp.uint32(HASH_MULT)
    for j in range(codes.shape[1]):
        h = h * mult + codes[:, j].astype(jnp.uint32)
    return h


def query_codes(y, dims, shift, cell_width: float) -> jnp.ndarray:
    """(m, h) int32 grid codes of query rows under the random-shifted grid
    (float32 add + divide, bitwise identical to the host layout build)."""
    yh = jnp.take(y, dims, axis=1)
    return jnp.floor((yh + shift[None, :]) / cell_width).astype(jnp.int32)


def rowwise_kv(q, xr, kind: str, inv_bw: float, beta: float, pairwise=None,
               precision: str = "f32"):
    """Per-row kernel values k(q_i, xr_i_j): q (w, d), xr (w, t, d) ->
    (w, t), from the direct coordinate differences reduced over the
    feature (lane) axis.  This exact function runs inside the Pallas
    kernel body AND in the jnp oracles, so compiled (interpret) and oracle
    values agree bitwise.

    ``precision="bf16"`` rounds both operand rows to bf16 (DESIGN.md §14)
    and runs the identical f32 reduction on the rounded values; the HT
    weights applied downstream stay f32."""
    if precision != "f32":
        check_precision(precision, kind, pairwise)
        q = q.astype(jnp.bfloat16).astype(jnp.float32)
        xr = xr.astype(jnp.bfloat16).astype(jnp.float32)
    if kind in _L2_KINDS:
        d2 = jnp.sum(jnp.square(q[:, None, :] - xr), axis=-1)
        if precision != "f32":
            return _finish_l2_bf16(d2, kind, inv_bw, beta)
        return _finish_l2(d2, kind, inv_bw, beta)
    if kind == "laplacian":
        d1 = jnp.sum(jnp.abs(q[:, None, :] - xr), axis=-1)
        return jnp.exp(-d1 * inv_bw)
    return jax.vmap(lambda a, b: pairwise(a[None, :], b)[0])(q, xr)


# --------------------------------------------------------------------- #
# shared gathers: (rows to evaluate, HT weights) for queries / frontiers
# --------------------------------------------------------------------- #
def _far_collide(fidx, mem, mvalid):
    """(w, s) mask: far sample j of row i hits a stored NEAR member."""
    return jnp.any((fidx[:, :, None] == mem[:, None, :])
                   & mvalid[:, None, :], axis=-1)


def _overflow_cols(state: HashState, w: int):
    """Broadcast the (global) overflow region to per-row exact columns:
    (w, ov_cap) clipped row ids + (w, ov_cap) 0/1 validity weights.
    Returns ``(None, None)`` for static (overflow-free) states."""
    if state.overflow is None:
        return None, None
    ov = state.overflow
    ovvalid = (ov >= 0)[None, :]
    ovc = jnp.broadcast_to(jnp.maximum(ov, 0)[None, :], (w, ov.shape[0]))
    return ovc, jnp.broadcast_to(ovvalid, (w, ov.shape[0]))


def _far_hits_overflow(fidx, state: HashState):
    """(w, s) mask: far sample hits a live overflow row (those are already
    counted exactly by the overflow sweep)."""
    if state.overflow is None:
        return jnp.zeros(fidx.shape, bool)
    ov = state.overflow
    return jnp.any((fidx[:, :, None] == ov[None, None, :])
                   & (ov >= 0)[None, None, :], axis=-1)


def num_exact_cols(state: HashState) -> int:
    """Static count of exact (NEAR member + overflow) evaluation columns
    in the gathers below -- FAR columns start here."""
    mb = int(state.members.shape[1])
    return mb + (int(state.overflow.shape[0])
                 if state.overflow is not None else 0)


def query_gather(x, y, state: HashState, key, cell_width: float,
                 num_far: int, n: int):
    """Bucket lookup + FAR draw for arbitrary queries: hash ``y`` on
    device, find the bucket by one vectorized ``searchsorted`` over the
    sorted keys, and return the (w, max_bucket + num_far) evaluation rows
    ``xr``, their summation weights ``wgt`` (1 for valid NEAR slots,
    ``n/num_far`` for non-colliding FAR samples), the realized NEAR
    counts (Definition 1.1 eval accounting), and the per-row
    bucket-truncation flag (False everywhere for legacy states)."""
    qkey = pack_codes(query_codes(y, state.dims, state.shift, cell_width))
    b = jnp.clip(jnp.searchsorted(state.keys, qkey), 0,
                 state.keys.shape[0] - 1).astype(jnp.int32)
    hit = state.keys[b] == qkey
    cnt = jnp.where(hit, state.counts[b], 0)
    mem = state.members[b]
    mb = mem.shape[1]
    mvalid = jnp.arange(mb, dtype=jnp.int32)[None, :] < cnt[:, None]
    trunc = (hit & state.truncated[b] if state.truncated is not None
             else jnp.zeros(hit.shape, bool))
    ovc, ovvalid = _overflow_cols(state, y.shape[0])
    if ovc is not None:                    # streaming: extra exact sweep
        mem = jnp.concatenate([mem, ovc], axis=1)
        mvalid = jnp.concatenate([mvalid, ovvalid], axis=1)
    if num_far == 0:                       # static: NEAR-only estimate
        return mem, x[mem], mvalid.astype(jnp.float32), cnt, trunc
    fidx = jax.random.randint(key, (y.shape[0], num_far), 0, n)
    collide = (_far_collide(fidx, mem[:, :mb], mvalid[:, :mb])
               | _far_hits_overflow(fidx, state))
    cols = jnp.concatenate([mem, fidx], axis=1)
    wgt = jnp.concatenate(
        [mvalid.astype(jnp.float32),
         (float(n) / num_far) * (1.0 - collide.astype(jnp.float32))], axis=1)
    return cols, x[cols], wgt, cnt, trunc


def frontier_gather(x, src, state: HashState, key, num_far: int,
                    block_size: int, num_blocks: int, n: int):
    """Bucket lookup + STRATIFIED FAR draw for a frontier of DATASET
    indices (the level-1 read): the bucket id is a dense ``point_bucket``
    gather (no hashing, no searchsorted), and the FAR term draws
    ``num_far`` uniform slots PER BLOCK (a stratified draw, so every
    block's estimate is backed by a real sample -- a global FAR draw
    leaves most blocks at the 1e-12 floor and makes the sparsifier's
    importance weights heavy-tailed).  The HT weight is the constant
    ``block_size/num_far`` (slot-uniform inclusion; out-of-range tail
    slots and collisions with stored NEAR members or the query itself are
    masked to weight 0, which the constant weight keeps unbiased).  The
    fifth output is the per-row bucket-truncation flag."""
    w = src.shape[0]
    b = state.point_bucket[src]
    # streaming states mark rows with no frozen bucket (overflow rows in a
    # brand-new grid cell, dead slots) with point_bucket = -1: their NEAR
    # set is empty and the FAR/overflow terms carry the whole estimate
    nohit = b < 0
    bc = jnp.maximum(b, 0)
    cnt = jnp.where(nohit, 0, state.counts[bc])
    mem = state.members[bc]
    mb = mem.shape[1]
    mvalid = jnp.arange(mb, dtype=jnp.int32)[None, :] < cnt[:, None]
    trunc = (state.truncated[bc] & ~nohit if state.truncated is not None
             else jnp.zeros(b.shape, bool))
    ovc, ovvalid = _overflow_cols(state, w)
    if ovc is not None:                    # streaming: extra exact sweep
        mem = jnp.concatenate([mem, ovc], axis=1)
        mvalid = jnp.concatenate([mvalid, ovvalid], axis=1)
    base = jnp.arange(num_blocks, dtype=jnp.int32) * block_size
    off = jax.random.randint(key, (w, num_blocks, num_far), 0, block_size)
    fidx = (base[None, :, None] + off).reshape(w, num_blocks * num_far)
    dead = (_far_collide(fidx, mem[:, :mb], mvalid[:, :mb])
            | _far_hits_overflow(fidx, state) | (fidx == src[:, None])
            | (fidx >= n))
    fidx = jnp.minimum(fidx, n - 1)
    cols = jnp.concatenate([mem, fidx], axis=1)
    wgt = jnp.concatenate(
        [mvalid.astype(jnp.float32),
         (float(block_size) / num_far)
         * (1.0 - dead.astype(jnp.float32))], axis=1)
    return cols, x[cols], wgt, cnt, trunc


# --------------------------------------------------------------------- #
# oracles (the jnp fallback path of ops.py IS these functions)
# --------------------------------------------------------------------- #
def hashed_query_ref(x, y, state: HashState, key, kind: str, inv_bw: float,
                     beta: float, cell_width: float, num_far: int, n: int,
                     pairwise=None):
    """NEAR-exact + HT-FAR row-sum estimates: (m,) estimates and the (m,)
    realized NEAR eval counts.  One weighted kernel-value pass over the
    concatenated (member, far-sample) rows -- the identical summation
    order the Pallas kernel uses, so interpret-mode runs match bitwise."""
    _, xr, wgt, cnt, _ = query_gather(x, y, state, key, cell_width, num_far,
                                      n)
    kv = rowwise_kv(y, xr, kind, inv_bw, beta, pairwise)
    return jnp.sum(kv * wgt, axis=1), cnt


def hashed_block_sums_ref(x, src, state: HashState, key, kind: str,
                          inv_bw: float, beta: float, num_far: int,
                          block_size: int, num_blocks: int, n: int,
                          pairwise=None):
    """Hashed level-1 frontier read: (w, B) §2-contract block-sum
    estimates from O(max_bucket + B num_far) kernel evals per row.  NEAR
    members contribute exactly to their own blocks (a scatter-add over the
    member block ids); the stratified FAR samples are block-indexed by
    construction, so their HT-weighted values reduce with one reshape.
    The query's self kernel (k(x, x) = 1, the repo-wide contract) is
    subtracted from its own block iff stored (otherwise the FAR mask
    already excluded it), and every block is floored at 1e-12 exactly
    like ``ops.masked_block_sums``."""
    q = x[src]
    cols, xr, wgt, _, _ = frontier_gather(x, src, state, key, num_far,
                                          block_size, num_blocks, n)
    kv = rowwise_kv(q, xr, kind, inv_bw, beta, pairwise) * wgt
    return scatter_block_sums(kv, cols, src, state, num_far, block_size,
                              num_blocks)


def scatter_block_sums(kv, cols, src, state: HashState, num_far: int,
                       block_size: int, num_blocks: int):
    """Shared §2 finish of the hashed level-1 read (consumed verbatim by
    the ops path too, so oracle and fused programs cannot drift): scatter
    the weighted NEAR values into their blocks, reshape-reduce the
    block-indexed FAR values, subtract the self kernel from the own block
    iff stored, floor every block at 1e-12.  Streaming states contribute
    their overflow region as extra exact columns (already weight-masked by
    the gather), scattered by block exactly like NEAR members."""
    nex = num_exact_cols(state)
    w = src.shape[0]
    blk_near = (cols[:, :nex] // block_size).astype(jnp.int32)
    bs = kv[:, nex:].reshape(w, num_blocks, num_far).sum(-1)
    bs = bs.at[jnp.arange(w, dtype=jnp.int32)[:, None], blk_near].add(
        kv[:, :nex])
    own = (src // block_size).astype(jnp.int32)
    corr = jnp.arange(num_blocks, dtype=jnp.int32)[None, :] == own[:, None]
    bs = jnp.where(corr, bs - state.self_stored[src][:, None], bs)
    return jnp.maximum(bs, BLOCK_SUM_FLOOR)


def sharded_hashed_query_ref(x_pad, y, shard_states, key, kind: str,
                             inv_bw: float, beta: float, cell_width: float,
                             num_far: int, n: int, shard_size: int,
                             pairwise=None):
    """Single-device oracle of ``sharded.ShardedHashTable.query``: every
    shard looks up its OWN bucket table (each shard hashed its own rows),
    draws ``num_far`` uniforms over its ``shard_size`` row slots with the
    per-shard ``fold_in(key, p)`` discipline (sentinel rows sit at the far
    offset, so their kernel values are exactly 0 and the HT weight is
    ``shard_size/num_far``), and the estimate is the plain sum of the
    per-shard NEAR+FAR partials -- what ONE psum produces on the mesh.
    Streaming shard states carry a per-shard ``overflow`` region of row
    ids owned by that shard; its live entries join the shard's exact
    sweep (weight 1) and are masked out of its FAR draw, mirroring the
    flat ``query_gather`` contract.  Returns (estimates, NEAR counts);
    ints match the device program bitwise, floats to f32 tolerance
    (psum reorders the accumulation)."""
    num_shards = len(shard_states)
    m = y.shape[0]
    est = jnp.zeros((m,), jnp.float32)
    cnt = jnp.zeros((m,), jnp.int32)
    for p in range(num_shards):
        st = shard_states[p]
        qkey = pack_codes(query_codes(y, st.dims, st.shift, cell_width))
        b = jnp.clip(jnp.searchsorted(st.keys, qkey), 0,
                     st.keys.shape[0] - 1).astype(jnp.int32)
        hit = st.keys[b] == qkey
        c = jnp.where(hit, st.counts[b], 0)
        mem = st.members[b]
        mb = mem.shape[1]
        mvalid = jnp.arange(mb, dtype=jnp.int32)[None, :] < c[:, None]
        ovc, ovvalid = _overflow_cols(st, m)
        if ovc is not None:                # streaming: extra exact sweep
            mem_cat = jnp.concatenate([mem, ovc], axis=1)
            wexact = jnp.concatenate(
                [mvalid.astype(jnp.float32), ovvalid.astype(jnp.float32)],
                axis=1)
        else:
            mem_cat = mem
            wexact = mvalid.astype(jnp.float32)
        if num_far == 0:                   # static: NEAR-only estimate
            cols, wgt = mem_cat, wexact
        else:
            kk = jax.random.fold_in(key, p)
            fidx = (p * shard_size
                    + jax.random.randint(kk, (m, num_far), 0, shard_size))
            collide = (_far_collide(fidx, mem, mvalid)
                       | _far_hits_overflow(fidx, st))
            cols = jnp.concatenate([mem_cat, fidx], axis=1)
            wgt = jnp.concatenate(
                [wexact,
                 (float(shard_size) / num_far)
                 * (1.0 - collide.astype(jnp.float32))], axis=1)
        kv = rowwise_kv(y, x_pad[cols], kind, inv_bw, beta, pairwise)
        est = est + jnp.sum(kv * wgt, axis=1)
        cnt = cnt + c
    return est, cnt
