"""Device-resident hashed-KDE engine: layout build + jitted programs.

``build_hash_state`` runs ONCE on the host: hash every dataset row with a
random-shifted grid (the KAP22/DEANN scheme of ``core.kde.hbe``), sort by
packed key, and freeze the buckets into the static padded layout of
``ref.HashState`` -- ``max_bucket`` slots per bucket, sentinel padding,
global row indices.  After that every query is ONE jitted device program:

* ``hashed_query``      -- (m,) NEAR-exact + HT-FAR row-sum estimates plus
  the realized NEAR eval counts; O(max_bucket + num_far) kernel evals per
  query instead of the dense backends' O(n) (Definition 1.1 / §3.1).
* ``hashed_block_sums`` -- (w, B) §2-contract level-1 block-sum estimates
  for a frontier of dataset indices (bucket membership is a dense
  ``point_bucket`` gather; the FAR term is a stratified per-block draw so
  no block is left at the floor); the ``level1="hash"`` read of the
  depth-2 sampler (DESIGN.md §10).

Both dispatch the weighted kernel-value pass to the Pallas bucket kernel
on the TPU path and run the ``ref.py`` oracle math elsewhere; interpret
mode matches the oracle bitwise.  ``TRACE_COUNTS`` is shared with
``kde_sampler.ops`` so the no-retrace tests cover these programs too.
"""
from __future__ import annotations

import inspect

import jax
import jax.numpy as jnp
import numpy as np

from repro.ft import guards as _g
from repro.kernels.kde_hash import kernel as _k
from repro.kernels.kde_hash import ref as _ref
from repro.kernels.kde_sampler import ops as _sops
from repro.kernels.kde_sampler.ref import BLOCK_SUM_FLOOR, BUILTIN_KINDS
from repro.obs import counters as _c

TRACE_COUNTS = _sops.TRACE_COUNTS

_STATIC = frozenset((
    "kind", "inv_bw", "beta", "pairwise", "cell_width", "num_far", "n",
    "block_size", "num_blocks", "use_pallas", "interpret", "bm",
    # precision selects the weighted-pass eval dtype (DESIGN.md §14):
    # "f32" (default, bitwise-stable) or "bf16" (rounded operand rows,
    # f32 weights/accumulators/scatters)
    "precision"))


def _jit(fn):
    names = tuple(p for p in inspect.signature(fn).parameters if p in _STATIC)
    return jax.jit(fn, static_argnames=names)


def default_cell_width(kernel) -> float:
    """The ``GridHBE`` default: two bandwidths per grid cell, so NEAR
    buckets cover the region where Table-1 kernels carry most mass."""
    return 2.0 * float(kernel.bandwidth)


def draw_grid(rng, d: int, num_hash_dims: int, cell_width: float):
    """Draw the random-shifted grid (hash-dim subset + per-dim shift) with
    the exact ``GridHBE(seed=...)`` RNG call order -- the ONE place this
    discipline lives (``build_hash_state`` and the sharded table both call
    it, so equal seeds always mean the identical grid)."""
    dims = rng.choice(d, size=min(int(num_hash_dims), d),
                      replace=False).astype(np.int32)
    shift = rng.uniform(0.0, cell_width, size=len(dims)).astype(np.float32)
    return dims, shift


def grid_keys(xn: np.ndarray, dims, shift, cell_width: float) -> np.ndarray:
    """(k,) uint32 packed grid keys of rows ``xn`` (float32 shift/floor
    arithmetic bitwise-equal to the device-side ``ref.query_codes``)."""
    codes = np.floor((xn[:, dims] + shift) / cell_width).astype(np.int32)
    keys = np.zeros(len(xn), np.uint32)
    for j in range(codes.shape[1]):
        keys = keys * np.uint32(_ref.HASH_MULT) + codes[:, j].astype(np.uint32)
    return keys


def bucket_table(keys: np.ndarray, rows: np.ndarray, max_bucket: int, rng):
    """Freeze the buckets of one key slice into the padded layout:
    (sorted unique keys, (U, max_bucket) member table of GLOBAL row ids,
    stored counts, concatenated stored row ids, per-bucket truncation
    flags).  Oversized buckets store a seeded subsample; overflow members
    stay FAR-eligible -- the flags let queries report that truncation
    happened (``guards.BUCKET_OVERFLOW``)."""
    order = np.argsort(keys, kind="stable")
    sk = keys[order]
    uniq, counts_full = np.unique(sk, return_counts=True)
    starts = np.concatenate([[0], np.cumsum(counts_full)[:-1]])
    mb = int(max_bucket)
    members = np.zeros((max(len(uniq), 1), mb), np.int32)
    counts = np.zeros(max(len(uniq), 1), np.int32)
    counts[:len(uniq)] = np.minimum(counts_full, mb)
    truncated = np.zeros(max(len(uniq), 1), bool)
    truncated[:len(uniq)] = counts_full > mb
    stored = [np.zeros(0, np.int64)]
    for b in range(len(uniq)):
        seg = rows[order[starts[b]:starts[b] + counts_full[b]]]
        if counts_full[b] > mb:
            seg = rng.choice(seg, size=mb, replace=False)
        members[b, :len(seg)] = seg
        stored.append(seg)
    return uniq, members, counts, np.concatenate(stored), truncated


def build_hash_state(x, kernel, cell_width: float | None = None,
                     num_hash_dims: int = 8, max_bucket: int = 256,
                     seed: int = 0, live=None, overflow_cap: int = 0):
    """Host-side layout build (once per dataset): returns
    ``(HashState, cell_width)``.

    The RNG call order (hash-dim choice, then shift, then per-bucket
    overflow subsampling) matches ``GridHBE(seed=...)``, so a ``GridHBE``
    built with the same seed/width hashes with the identical grid --
    bucket membership agrees up to the packed-key width (uint32 here,
    uint64 there; a collision would merely merge two cells, and the
    HT-corrected estimator stays unbiased under ANY bucket assignment).
    Buckets larger than ``max_bucket`` store a seeded subsample; overflow
    members remain FAR-eligible.

    Streaming extensions (DESIGN.md §12): ``live`` masks the padded rows
    actually hashed -- dead (sentinel) slots get ``point_bucket = -1``
    and never enter a bucket; ``overflow_cap > 0`` attaches an (empty)
    overflow region of that static capacity, the landing zone
    :class:`HashPatcher` appends mutated rows into between compactions.
    """
    xn = np.asarray(x, np.float32)
    n, d = xn.shape
    rng = np.random.default_rng(seed)
    w = float(cell_width if cell_width is not None
              else default_cell_width(kernel))
    dims, shift = draw_grid(rng, d, num_hash_dims, w)
    if live is None:
        rows = np.arange(n, dtype=np.int64)
    else:
        rows = np.where(np.asarray(live, bool))[0].astype(np.int64)
    keys = grid_keys(xn[rows], dims, shift, w)
    uniq, members, counts, stored_rows, truncated = bucket_table(
        keys, rows, max_bucket, rng)
    stored = np.zeros(n, bool)
    stored[stored_rows] = True
    point_bucket = np.full(n, -1, np.int32)
    point_bucket[rows] = np.searchsorted(uniq, keys).astype(np.int32)
    state = _ref.HashState(
        dims=jnp.asarray(dims),
        shift=jnp.asarray(shift),
        keys=jnp.asarray(uniq),
        members=jnp.asarray(members),
        counts=jnp.asarray(counts),
        point_bucket=jnp.asarray(point_bucket),
        self_stored=jnp.asarray(stored.astype(np.float32)),
        truncated=jnp.asarray(truncated),
        overflow=(jnp.full((int(overflow_cap),), -1, jnp.int32)
                  if overflow_cap else None))
    return state, w


def _weighted_kv(q, xr, wgt, *, kind, inv_bw, beta, pairwise, use_pallas,
                 interpret, bm, precision="f32"):
    """(m, t) weighted kernel values: the Pallas bucket kernel on the
    Pallas path (padded to a ``bm`` query multiple), the shared
    ``ref.rowwise_kv`` math elsewhere -- bitwise-identical results in
    interpret mode."""
    if use_pallas and kind in BUILTIN_KINDS:
        m = q.shape[0]
        rem = (-m) % bm
        if rem:
            q = jnp.pad(q, ((0, rem), (0, 0)))
            wgt = jnp.pad(wgt, ((0, rem), (0, 0)))
            xr = jnp.pad(xr, ((0, rem), (0, 0), (0, 0)))
        return _k.weighted_kv_pallas(q, wgt, xr, kind, inv_bw, beta, bm=bm,
                                     interpret=interpret,
                                     precision=precision)[:m]
    return _ref.rowwise_kv(q, xr, kind, inv_bw, beta, pairwise,
                           precision=precision) * wgt


@_jit
def hashed_query(x, y, state, key, *, kind, inv_bw, beta, pairwise,
                 cell_width, num_far, n, use_pallas=False, interpret=False,
                 bm=32, precision="f32"):
    """(m,) row-sum estimates + (m,) realized NEAR eval counts + a counter
    word -- the Definition 1.1 read at O(max_bucket + num_far) evals
    per query.  The word's status slot flags bucket truncation,
    out-of-range member indices (JAX gathers clamp, so corruption is
    otherwise silent), and a Horvitz-Thompson FAR sample dominating the
    estimate (per element, against ``REPRO_HT_FRAC``)."""
    TRACE_COUNTS["hashed_query"] += 1
    cols, xr, wgt, cnt, trunc = _ref.query_gather(x, y, state, key,
                                                  cell_width, num_far, n)
    corrupt = jnp.any((cols < 0) | (cols >= n))
    kv = _weighted_kv(y, xr, wgt, kind=kind, inv_bw=inv_bw, beta=beta,
                      pairwise=pairwise, use_pallas=use_pallas,
                      interpret=interpret, bm=bm, precision=precision)
    est = jnp.sum(kv, axis=1)
    far = kv[:, _ref.num_exact_cols(state):]
    heavy = (jnp.any(far > _g.ht_frac()
                     * jnp.maximum(jnp.abs(est)[:, None], 1e-30))
             if num_far > 0 else jnp.asarray(False))
    st = _g.merge(_g.flag_if(corrupt, _g.STATE_CORRUPT),
                  _g.flag_if(jnp.any(trunc), _g.BUCKET_OVERFLOW),
                  _g.flag_if(heavy, _g.HT_HEAVY),
                  _g.result_status(est))
    # realized gather width per query row (ref.query_gather): max_bucket
    # NEAR slots + the overflow sweep + num_far HT samples
    m = y.shape[0]
    ov = (int(state.overflow.shape[0])
          if state.overflow is not None else 0)
    mb = int(state.members.shape[1])
    cw = _c.word(status=st, evals=m * (mb + ov + num_far), l1_reads=m,
                 far_samples=m * num_far, overflow=m * ov)
    return est, cnt, cw


def _hashed_block_sums(x, src, state, key, *, kind, inv_bw, beta, pairwise,
                       num_far, block_size, num_blocks, n, use_pallas,
                       interpret, bm, precision="f32"):
    """Traceable core of ``hashed_block_sums`` (called from inside the
    fused sampler programs of ``kde_sampler.ops``).  Returns
    ``(block sums, status)``."""
    q = x[src]
    cols, xr, wgt, _, trunc = _ref.frontier_gather(x, src, state, key,
                                                   num_far, block_size,
                                                   num_blocks, n)
    kv = _weighted_kv(q, xr, wgt, kind=kind, inv_bw=inv_bw, beta=beta,
                      pairwise=pairwise, use_pallas=use_pallas,
                      interpret=interpret, bm=bm, precision=precision)
    bs = _ref.scatter_block_sums(kv, cols, src, state, num_far,
                                 block_size, num_blocks)
    st = _g.merge(_g.flag_if(jnp.any((cols < 0) | (cols >= n)),
                             _g.STATE_CORRUPT),
                  _g.flag_if(jnp.any(trunc), _g.BUCKET_OVERFLOW),
                  _g.sums_status(bs, BLOCK_SUM_FLOOR))
    return bs, st


@_jit
def hashed_block_sums(x, src, state, key, *, kind, inv_bw, beta, pairwise,
                      num_far, block_size, num_blocks, n, use_pallas=False,
                      interpret=False, bm=32, precision="f32"):
    """(w, B) §2-contract level-1 estimates of a dataset frontier from
    O(max_bucket + B num_far) evals per row: exact NEAR scatter +
    ``num_far`` stratified FAR slots per block (the ``level1="hash"``
    read; DESIGN.md §10).  Returns ``(block sums, counter word)``."""
    TRACE_COUNTS["hashed_block_sums"] += 1
    bs, st = _hashed_block_sums(x, src, state, key, kind=kind, inv_bw=inv_bw,
                                beta=beta, pairwise=pairwise,
                                num_far=num_far, block_size=block_size,
                                num_blocks=num_blocks, n=n,
                                use_pallas=use_pallas, interpret=interpret,
                                bm=bm, precision=precision)
    # realized gather width per frontier row (ref.frontier_gather):
    # max_bucket NEAR slots + the overflow sweep + B*num_far FAR slots
    w = src.shape[0]
    ov = (int(state.overflow.shape[0])
          if state.overflow is not None else 0)
    mb = int(state.members.shape[1])
    far = int(num_blocks) * int(num_far)
    cw = _c.word(status=st, evals=w * (mb + ov + far), l1_reads=w,
                 far_samples=w * far, overflow=w * ov)
    return bs, cw


# --------------------------------------------------------------------- #
# batched multi-tenant serving entry points (DESIGN.md §13)
# --------------------------------------------------------------------- #
def stack_hash_states(states):
    """Stack equal-shape ``HashState`` pytrees along a new leading tenant
    axis for the batched multi-tenant query path.  All layouts must agree
    in every array shape and dtype (bucket count, ``max_bucket``, padded
    row count, overflow capacity, hash dims) -- the serving layer keys its
    batch groups by exactly this shape signature, so unequal tenants never
    share a group.  Raises ``ValueError`` on a mismatch rather than
    silently padding: phantom padded buckets would change the FAR
    complement every Horvitz-Thompson draw sees."""
    if not states:
        raise ValueError("stack_hash_states needs at least one state")
    leaves0, treedef0 = jax.tree_util.tree_flatten(states[0])
    for s in states[1:]:
        leaves, treedef = jax.tree_util.tree_flatten(s)
        if treedef != treedef0 or any(
                a.shape != b.shape or a.dtype != b.dtype
                for a, b in zip(leaves, leaves0)):
            raise ValueError(
                "HashState layouts differ in shape/dtype -- serve these "
                "tenants in separate batch groups")
    return jax.tree_util.tree_map(lambda *a: jnp.stack(a), *states)


@_jit
def batched_hashed_query(xa, tidx, y, state, keys, *, kind, inv_bw, beta,
                         pairwise, cell_width, num_far, n, use_pallas=False,
                         interpret=False, bm=32, precision="f32"):
    """R hashed Definition 1.1 query requests across stacked tenants in
    ONE program: ``xa (T, n, d)`` stacked tenant rows, ``state`` a
    :func:`stack_hash_states` pytree, ``y (R, q, d)`` padded query points,
    ``keys (R, 2)`` per-request PRNG keys.  Returns (estimates (R, q),
    NEAR eval counts (R, q), per-request counter words (R, obs.WIDTH)) --
    each lane is ``hashed_query`` on its own tenant and key, so estimates
    match the sequential single-tenant calls."""
    TRACE_COUNTS["batched_hashed_query"] += 1

    def one(ti, y_r, key_r):
        hs = jax.tree_util.tree_map(lambda a: _sops.tenant_slice(a, ti),
                                    state)
        return hashed_query(_sops.tenant_slice(xa, ti), y_r, hs, key_r,
                            kind=kind, inv_bw=inv_bw, beta=beta,
                            pairwise=pairwise,
                            cell_width=cell_width, num_far=num_far, n=n,
                            use_pallas=use_pallas, interpret=interpret,
                            bm=bm, precision=precision)

    return jax.vmap(one)(tidx, y, keys)


# --------------------------------------------------------------------- #
# streaming patches (DESIGN.md §12)
# --------------------------------------------------------------------- #
@jax.jit
def _apply_hash_patch(members, counts, point_bucket, self_stored, overflow,
                      bidx, brows, bcnt, pidx, pb, ss, ovidx, ovval):
    """Jitted scatter of a host-computed hash patch: rewrite the touched
    bucket rows wholesale (host already deduplicated them) plus the
    touched per-point and overflow entries.  O(touched) device work, no
    rehash, no sort, no collectives."""
    return (members.at[bidx].set(brows),
            counts.at[bidx].set(bcnt),
            point_bucket.at[pidx].set(pb),
            self_stored.at[pidx].set(ss),
            overflow.at[ovidx].set(ovval))


class HashPatcher:
    """Incremental ``HashState`` maintenance for a mutating dataset.

    Keeps host numpy mirrors of the (host-built anyway) bucket tables and
    patches them in O(m) per mutation batch; the device state is updated
    by ONE jitted scatter over the touched entries.  The placement policy
    (DESIGN.md §12):

    * insert whose grid cell exists in the frozen ``keys`` and whose
      bucket has free slots -> splice into the bucket at its slot-sorted
      position (rows arrive tail-first from ``DynamicDataset``, so the
      patched member table stays bitwise equal to a fresh rebuild);
    * otherwise -> append to the **overflow region**, which every query /
      frontier read sweeps exactly (weight 1) until :meth:`needs_rebuild`
      tells the owner to compact (rebuild via ``build_hash_state``);
    * delete -> left-shift out of its bucket (or clear its overflow slot);
      the row's coordinates are already at the sentinel offset, so even a
      missed removal would contribute exactly 0 mass.

    Saturated overflow sets ``guards.OVERFLOW_SATURATED`` in :attr:`flags`
    and forces :attr:`needs_rebuild`; touching an RNG-subsampled
    (truncated) bucket stays *correct* but loses bitwise rebuild parity,
    which :attr:`exact_parity` records.
    """

    def __init__(self, state, cell_width: float):
        if state.overflow is None:
            raise ValueError("HashPatcher needs a state built with "
                             "overflow_cap > 0")
        self.cell_width = float(cell_width)
        self.dims = np.asarray(state.dims)
        self.shift = np.asarray(state.shift)
        self.keys = np.asarray(state.keys)           # frozen, sorted
        self.members = np.array(state.members, np.int32, copy=True)
        self.counts = np.array(state.counts, np.int32, copy=True)
        self.point_bucket = np.array(state.point_bucket, np.int32,
                                     copy=True)
        self.self_stored = np.array(state.self_stored, np.float32,
                                    copy=True)
        self.truncated = (np.array(state.truncated, bool, copy=True)
                          if state.truncated is not None
                          else np.zeros(len(self.keys), bool))
        self.overflow = np.array(state.overflow, np.int32, copy=True)
        self.max_bucket = int(self.members.shape[1])
        self.flags = 0
        self.needs_rebuild = False
        self.exact_parity = True

    @property
    def overflow_fill(self) -> int:
        """Occupied overflow slots (monitoring / compaction policy)."""
        return int((self.overflow >= 0).sum())

    def _remove(self, slot: int, touched_b: set, touched_ov: set) -> None:
        b = int(self.point_bucket[slot])
        if self.self_stored[slot] > 0.0:
            if b >= 0:                      # stored in its bucket's slots
                cnt = int(self.counts[b])
                row = self.members[b]
                pos = np.where(row[:cnt] == slot)[0]
                if pos.size:
                    p = int(pos[0])
                    row[p:cnt - 1] = row[p + 1:cnt]
                    row[cnt - 1] = 0
                    self.counts[b] = cnt - 1
                    touched_b.add(b)
                    if self.truncated[b]:
                        self.exact_parity = False
            pos = np.where(self.overflow == slot)[0]
            if pos.size:                    # stored in the overflow region
                self.overflow[pos[0]] = -1
                touched_ov.add(int(pos[0]))
        elif b >= 0 and self.truncated[b]:
            # an unstored member of a truncated bucket: nothing to remove,
            # but a rebuild would resample the smaller bucket
            self.exact_parity = False
        self.point_bucket[slot] = -1
        self.self_stored[slot] = 0.0

    def _insert(self, slot: int, row_x: np.ndarray, touched_b: set,
                touched_ov: set) -> None:
        key = grid_keys(row_x[None, :], self.dims, self.shift,
                        self.cell_width)[0]
        pos = int(np.searchsorted(self.keys, key))
        hit = pos < len(self.keys) and self.keys[pos] == key
        b = pos if hit else -1
        if hit and int(self.counts[b]) < self.max_bucket \
                and not self.truncated[b]:
            cnt = int(self.counts[b])
            row = self.members[b]
            at = int(np.searchsorted(row[:cnt], slot))
            row[at + 1:cnt + 1] = row[at:cnt]
            row[at] = slot
            self.counts[b] = cnt + 1
            self.point_bucket[slot] = b
            self.self_stored[slot] = 1.0
            touched_b.add(b)
            return
        free = np.where(self.overflow < 0)[0]
        if free.size == 0:
            self.flags |= _g.OVERFLOW_SATURATED
            self.needs_rebuild = True
            return
        self.overflow[free[0]] = slot
        touched_ov.add(int(free[0]))
        # NEAR reads of this row still see its cell's exact members (if
        # the cell has a frozen bucket); the row itself is swept via the
        # overflow region, so its self kernel IS stored-exactly
        self.point_bucket[slot] = b
        self.self_stored[slot] = 1.0
        self.exact_parity = False

    def apply(self, state, slots, old_x, new_x, old_live, new_live):
        """Patch the mirrors for one coalesced mutation batch and return
        the updated device ``HashState`` (or ``state`` unchanged with
        :attr:`needs_rebuild` set when the overflow region saturates --
        the caller must compact before serving another query)."""
        slots = np.asarray(slots, np.int64)
        old_live = np.asarray(old_live, bool)
        new_live = np.asarray(new_live, bool)
        new_x = np.asarray(new_x, np.float32)
        touched_b: set = set()
        touched_ov: set = set()
        touched_p = [int(s) for s in slots]
        for i, s in enumerate(slots):
            s = int(s)
            if old_live[i]:
                self._remove(s, touched_b, touched_ov)
            if new_live[i]:
                self._insert(s, new_x[i], touched_b, touched_ov)
        if self.needs_rebuild:
            return state
        bidx = np.fromiter(sorted(touched_b), np.int32,
                           count=len(touched_b))
        ovidx = np.fromiter(sorted(touched_ov), np.int32,
                            count=len(touched_ov))
        pidx = np.asarray(touched_p, np.int32)
        members, counts, point_bucket, self_stored, overflow = \
            _apply_hash_patch(
                state.members, state.counts, state.point_bucket,
                state.self_stored, state.overflow,
                jnp.asarray(bidx), jnp.asarray(self.members[bidx]),
                jnp.asarray(self.counts[bidx]),
                jnp.asarray(pidx), jnp.asarray(self.point_bucket[pidx]),
                jnp.asarray(self.self_stored[pidx]),
                jnp.asarray(ovidx), jnp.asarray(self.overflow[ovidx]))
        return state._replace(members=members, counts=counts,
                              point_bucket=point_bucket,
                              self_stored=self_stored, overflow=overflow)
