"""Mesh-resident hashed-KDE table (DESIGN.md §10, sharded schedule).

Each shard owns a contiguous run of dataset rows (the §9 layout: ``n``
rows padded to ``P * shard_size`` with far-offset sentinel rows) and
hashes ITS OWN rows into a local bucket table under the one global
(dims, shift) grid -- a global grid cell's members are partitioned across
shards, so the union of local NEAR sets is exactly the flat engine's NEAR
set.  One query batch is:

1. every shard hashes the replicated queries, looks the keys up in its
   LOCAL sorted table, and evaluates its NEAR members exactly
   (``O(max_bucket)`` rows) -- no collective;
2. every shard draws ``num_far`` uniforms over its OWN ``shard_size`` row
   slots (``fold_in(key, p)`` discipline; sentinel rows have kernel value
   exactly 0) and applies the local HT weight ``shard_size/num_far`` --
   no collective;
3. ONE ``psum`` of the (estimate partial, NEAR-count partial) pair makes
   the Definition 1.1 estimates replicated.

Exactly one psum and zero ppermute per query batch (asserted via
``kde_sampler.sharded.collective_counts``); no dataset row ever moves
between shards.  Oracle: ``ref.sharded_hashed_query_ref`` (identical
key discipline; ints bitwise, floats to f32 tolerance).
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.ft import guards as _g
from repro.kernels.kde_hash import ops as _ops
from repro.kernels.kde_hash import ref as _ref
from repro.kernels.kde_rowsum.ops import _PAD_OFFSET
from repro.kernels.kde_sampler.ref import static_pairwise
from repro.kernels.kde_sampler.sharded import _flat_index
from repro.obs import counters as _c

TRACE_COUNTS = _ops.TRACE_COUNTS

_PROGRAM_CACHE: dict = {}

# Sorted-key padding: lookups of a real key can never land on a pad slot
# (pad counts are 0 anyway, so even the astronomically unlikely real
# 0xFFFFFFFF key only ever reads an empty bucket).
_PAD_KEY = np.uint32(0xFFFFFFFF)


def _pad_pow2(a: np.ndarray, fill) -> np.ndarray:
    """Pad a write list's leading dim to the next power of two with no-op
    entries (owner -1 / out-of-range slot), so mutation-batch program
    shapes come from a tiny static set and patches rarely retrace."""
    k = max(int(a.shape[0]), 1)
    target = 1 << (k - 1).bit_length()
    if a.shape[0] == target:
        return a
    pad_shape = (target - a.shape[0],) + a.shape[1:]
    return np.concatenate([a, np.full(pad_shape, fill, a.dtype)], axis=0)


@dataclasses.dataclass(frozen=True)
class _TableSpec:
    """Static configuration of a sharded hash table -- the only thing the
    cached program closures capture (never device arrays)."""

    mesh: Mesh
    axes: tuple
    num_shards: int
    n: int
    shard_size: int
    num_far: int
    cell_width: float
    kind: str
    inv_bw: float
    beta: float
    pairwise: object
    ov_cap: int = 0      # per-shard streaming overflow slots (0 = static)


class ShardedHashTable:
    """Per-shard bucket tables + the one-psum collective query program.

    Construction hashes each shard's rows on the host (same grid as the
    flat ``ops.build_hash_state``) and places the stacked ``(P, U, mb)``
    tables sharded over the mesh; ``query`` is a jitted ``shard_map``
    program cached at module level by static config (Section 3.1 query
    semantics, one psum per batch).
    """

    def __init__(self, mesh: Mesh, x, kernel, *, cell_width: float | None
                 = None, num_hash_dims: int = 8, max_bucket: int = 256,
                 num_far_samples: int = 64,
                 data_axes: Sequence[str] = ("data",), seed: int = 0,
                 live=None, overflow_cap: int = 0):
        axes = tuple(data_axes)
        num_shards = 1
        for a in axes:
            num_shards *= int(mesh.shape[a])
        xn = np.asarray(x, np.float32)
        n, d = xn.shape
        shard_size = -(-n // num_shards)
        rng = np.random.default_rng(seed)
        w = float(cell_width if cell_width is not None
                  else _ops.default_cell_width(kernel))
        dims, shift = _ops.draw_grid(rng, d, num_hash_dims, w)
        mb = int(max_bucket)
        live_h = None if live is None else np.asarray(live, bool)
        per_shard = []
        any_trunc = False
        for p in range(num_shards):
            lo, hi = p * shard_size, min((p + 1) * shard_size, n)
            if live_h is None:
                rows = np.arange(lo, hi, dtype=np.int64)
            else:               # streaming: only hash the LIVE local rows
                rows = lo + np.where(live_h[lo:hi])[0].astype(np.int64)
            uniq, members, counts, _, trunc = _ops.bucket_table(
                _ops.grid_keys(xn[rows], dims, shift, w), rows, mb, rng)
            any_trunc = any_trunc or bool(trunc.any())
            per_shard.append((uniq, members, counts, trunc))
        ov_cap = int(overflow_cap)
        u_pad = max(max(len(s[0]) for s in per_shard), 1)
        keys_s = np.full((num_shards, u_pad), _PAD_KEY, np.uint32)
        members_s = np.zeros((num_shards, u_pad, mb), np.int32)
        counts_s = np.zeros((num_shards, u_pad), np.int32)
        trunc_s = np.zeros((num_shards, u_pad), bool)
        overflow_s = np.full((num_shards, max(ov_cap, 1)), -1, np.int32)
        states = []
        for p, (uniq, members, counts, trunc) in enumerate(per_shard):
            keys_s[p, :len(uniq)] = uniq
            members_s[p, :len(uniq)] = members[:len(uniq)]
            counts_s[p, :len(uniq)] = counts
            trunc_s[p, :len(uniq)] = trunc[:len(uniq)]
            states.append(_ref.HashState(
                dims=jnp.asarray(dims), shift=jnp.asarray(shift),
                keys=jnp.asarray(keys_s[p]),
                members=jnp.asarray(members_s[p]),
                counts=jnp.asarray(counts_s[p]),
                point_bucket=None, self_stored=None,
                truncated=jnp.asarray(trunc_s[p]),
                overflow=(jnp.asarray(overflow_s[p])
                          if ov_cap else None)))
        # single-device twins of the per-shard tables, for the ref oracle
        self.shard_states = states
        # host mirrors, patched in place by ``patch_rows`` (DESIGN.md §12)
        self._keys_h = keys_s
        self._members_h = members_s
        self._counts_h = counts_s
        self._trunc_h = trunc_s
        self._overflow_h = overflow_s
        self._dims_h = dims
        self._shift_h = shift
        self.flags = 0
        self.needs_rebuild = False
        self.exact_parity = True
        self.spec = _TableSpec(
            mesh=mesh, axes=axes, num_shards=num_shards, n=n,
            shard_size=shard_size, num_far=int(num_far_samples),
            cell_width=w, kind=kernel.name,
            inv_bw=1.0 / kernel.bandwidth,
            beta=float(getattr(kernel, "beta", 1.0)),
            pairwise=static_pairwise(kernel), ov_cap=ov_cap)
        self.n = n
        self.d = d
        self.num_shards = num_shards
        self.shard_size = shard_size
        self.max_bucket = mb
        self.num_far = int(num_far_samples)
        # Table-level overflow bit, frozen at build time: shard-local
        # per-query truncation hits would need a second collective to
        # replicate, so the sharded path reports the coarser "some bucket
        # somewhere was truncated" flag instead (one-psum budget intact).
        self._truncated = any_trunc
        n_pad = num_shards * shard_size
        pad = n_pad - n
        if pad:
            sent = jnp.full((pad, d), _PAD_OFFSET, jnp.float32) \
                + jnp.asarray(xn[-1:])
            xp = jnp.concatenate([jnp.asarray(xn), sent], axis=0)
        else:
            xp = jnp.asarray(xn)
        # every gather in the query program is shard-local (members and
        # FAR draws only ever touch the executing shard's own rows), so
        # the dataset lives sharded -- O(n d / P) per device; the
        # unplaced twin is kept for the ref oracle only.
        self.x_pad = xp
        sh = NamedSharding(mesh, P(axes))
        self.x_sh = jax.device_put(xp, sh)
        self._keys = jax.device_put(jnp.asarray(keys_s), sh)
        self._members = jax.device_put(jnp.asarray(members_s), sh)
        self._counts = jax.device_put(jnp.asarray(counts_s), sh)
        self.overflow_cap = ov_cap
        # always shaped (P, max(ov_cap, 1)) so the program signature is
        # uniform; the static ``spec.ov_cap == 0`` branch never reads it
        self._overflow = jax.device_put(jnp.asarray(overflow_s), sh)
        self._dims = jax.device_put(jnp.asarray(dims),
                                    NamedSharding(mesh, P()))
        self._shift = jax.device_put(jnp.asarray(shift),
                                     NamedSharding(mesh, P()))

    def _program(self):
        sp = self.spec
        if sp not in _PROGRAM_CACHE:
            mesh, axes = sp.mesh, sp.axes

            def body(keys_l, members_l, counts_l, ov_l, dims, shift, x_l,
                     y, key):
                pidx = _flat_index(mesh, axes)
                keys_l, members_l, counts_l = (keys_l[0], members_l[0],
                                               counts_l[0])
                qkey = _ref.pack_codes(
                    _ref.query_codes(y, dims, shift, sp.cell_width))
                b = jnp.clip(jnp.searchsorted(keys_l, qkey), 0,
                             keys_l.shape[0] - 1).astype(jnp.int32)
                hit = keys_l[b] == qkey
                cnt = jnp.where(hit, counts_l[b], 0)
                mem = members_l[b]
                mb = mem.shape[1]
                m = y.shape[0]
                mvalid = (jnp.arange(mb, dtype=jnp.int32)[None, :]
                          < cnt[:, None])
                if sp.ov_cap:   # streaming: shard-local exact overflow sweep
                    ov = ov_l[0]
                    mem_cat = jnp.concatenate(
                        [mem, jnp.broadcast_to(
                            jnp.maximum(ov, 0)[None, :],
                            (m, sp.ov_cap))], axis=1)
                    wexact = jnp.concatenate(
                        [mvalid.astype(jnp.float32),
                         jnp.broadcast_to((ov >= 0)[None, :],
                                          (m, sp.ov_cap))
                         .astype(jnp.float32)], axis=1)
                else:
                    mem_cat = mem
                    wexact = mvalid.astype(jnp.float32)
                if sp.num_far == 0:        # static: NEAR-only estimate
                    cols, wgt = mem_cat, wexact
                else:
                    kk = jax.random.fold_in(key, pidx)
                    fidx = pidx * sp.shard_size + jax.random.randint(
                        kk, (m, sp.num_far), 0, sp.shard_size)
                    collide = _ref._far_collide(fidx, mem, mvalid)
                    if sp.ov_cap:
                        ov = ov_l[0]
                        collide = collide | jnp.any(
                            (fidx[:, :, None] == ov[None, None, :])
                            & (ov >= 0)[None, None, :], axis=-1)
                    cols = jnp.concatenate([mem_cat, fidx], axis=1)
                    wgt = jnp.concatenate(
                        [wexact,
                         (float(sp.shard_size) / sp.num_far)
                         * (1.0 - collide.astype(jnp.float32))], axis=1)
                # all referenced rows are the shard's own: gather from the
                # LOCAL slice (member-pad slots point at global row 0 --
                # clamped here and masked by their 0 weight)
                cols_l = jnp.clip(cols - pidx * sp.shard_size, 0,
                                  sp.shard_size - 1)
                kv = _ref.rowwise_kv(y, x_l[cols_l], sp.kind, sp.inv_bw,
                                     sp.beta, sp.pairwise)
                part = jnp.sum(kv * wgt, axis=1)
                # one psum: the NEAR counts ride along as f32 (exact, far
                # below 2^24)
                tot = jax.lax.psum(
                    jnp.stack([part, cnt.astype(jnp.float32)]), axes)
                return tot[0], tot[1].astype(cnt.dtype)

            def outer(*args):
                TRACE_COUNTS["sharded_hashed_query"] += 1
                return jax.shard_map(body, mesh=mesh,
                                     in_specs=(P(axes), P(axes), P(axes),
                                               P(axes), P(), P(), P(axes),
                                               P(), P()),
                                     out_specs=(P(), P()),
                                     check_vma=False)(*args)
            _PROGRAM_CACHE[sp] = jax.jit(outer)
        return _PROGRAM_CACHE[sp]

    def query(self, y, key):
        """(m,) replicated row-sum estimates + (m,) NEAR eval counts + a
        counter word: local NEAR lookup + local FAR partials, then
        exactly ONE psum (Definition 1.1 over the sharded hashed table;
        PSUMS slot = 1).  The word is assembled host-side from
        replicated/static values only -- build-time bucket overflow, the
        static per-shard HT weight bound, non-finite estimates, and the
        static per-shard gather width -- so the collective schedule is
        untouched."""
        est, cnt = self._program()(
            self._keys, self._members, self._counts, self._overflow,
            self._dims, self._shift, self.x_sh,
            jnp.asarray(y, jnp.float32), key)
        sp = self.spec
        heavy = (sp.num_far > 0
                 and float(sp.shard_size) / sp.num_far > _g.ht_bound())
        st = _g.merge(
            _g.flag_if(jnp.asarray(self._truncated), _g.BUCKET_OVERFLOW),
            _g.flag_if(jnp.asarray(heavy), _g.HT_HEAVY),
            _g.flag_if(jnp.asarray(bool(self.flags
                                        & _g.OVERFLOW_SATURATED)),
                       _g.OVERFLOW_SATURATED),
            _g.result_status(est))
        m = int(jnp.shape(y)[0])
        mb = int(self._members.shape[-1])
        per_row = sp.num_shards * (mb + sp.ov_cap + sp.num_far)
        cw = _c.fold_status(
            _c.word(evals=m * per_row, l1_reads=m,
                    far_samples=m * sp.num_shards * sp.num_far,
                    overflow=m * sp.num_shards * sp.ov_cap, psums=1), st)
        return est, cnt, cw

    # ------------------------------------------------------------------ #
    # streaming patches (DESIGN.md §12)
    # ------------------------------------------------------------------ #
    def _patch_program(self):
        """The jitted zero-collective mutation program: every shard applies
        only the bucket / overflow / row writes it owns (``mode='drop'``
        discards the rest), so a mutation batch adds NO collective to the
        one-psum-per-query schedule -- jaxpr-assertable via
        ``kde_sampler.sharded.collective_counts``."""
        sp = self.spec
        full = (sp, "patch")
        if full not in _PROGRAM_CACHE:
            mesh, axes = sp.mesh, sp.axes

            def body(members_l, counts_l, ov_l, x_l, bp, bu, brow, bcnt,
                     ovp, ovpos, ovval, slots, rows):
                pidx = _flat_index(mesh, axes)
                u_cap = members_l.shape[1]
                ul = jnp.where(bp == pidx, bu, u_cap)
                members_l = members_l.at[0, ul].set(brow, mode="drop")
                counts_l = counts_l.at[0, ul].set(bcnt, mode="drop")
                pl = jnp.where(ovp == pidx, ovpos, ov_l.shape[1])
                ov_l = ov_l.at[0, pl].set(ovval, mode="drop")
                lidx = slots - pidx * sp.shard_size
                lidx = jnp.where((lidx >= 0) & (lidx < sp.shard_size),
                                 lidx, sp.shard_size)
                x_l = x_l.at[lidx].set(rows, mode="drop")
                return members_l, counts_l, ov_l, x_l

            def outer(*args):
                TRACE_COUNTS["sharded_hash_patch"] += 1
                return jax.shard_map(body, mesh=mesh,
                                     in_specs=(P(axes), P(axes), P(axes),
                                               P(axes)) + (P(),) * 9,
                                     out_specs=(P(axes),) * 4,
                                     check_vma=False)(*args)
            _PROGRAM_CACHE[full] = jax.jit(outer)
        return _PROGRAM_CACHE[full]

    def _lookup(self, p: int, row_x: np.ndarray):
        """(bucket pos, hit) of a coordinate row in shard ``p``'s frozen
        sorted key table."""
        key = _ops.grid_keys(row_x[None, :], self._dims_h, self._shift_h,
                             self.spec.cell_width)[0]
        u = int(np.searchsorted(self._keys_h[p], key))
        u = min(u, self._keys_h.shape[1] - 1)
        return u, bool(self._keys_h[p, u] == key)

    def _remove_host(self, p: int, slot: int, row_x, touched_b, touched_ov,
                     undo_b, undo_ov) -> None:
        u, hit = self._lookup(p, row_x)
        if hit:
            cnt = int(self._counts_h[p, u])
            row = self._members_h[p, u]
            pos = np.where(row[:cnt] == slot)[0]
            if pos.size:
                if (p, u) not in undo_b:
                    undo_b[(p, u)] = (row.copy(), cnt)
                at = int(pos[0])
                row[at:cnt - 1] = row[at + 1:cnt]
                row[cnt - 1] = 0
                self._counts_h[p, u] = cnt - 1
                touched_b.add((p, u))
                if self._trunc_h[p, u]:
                    self.exact_parity = False
                return
        pos = np.where(self._overflow_h[p] == slot)[0]
        if pos.size:
            at = int(pos[0])
            if (p, at) not in undo_ov:
                undo_ov[(p, at)] = int(self._overflow_h[p, at])
            self._overflow_h[p, at] = -1
            touched_ov.add((p, at))
            return
        # unstored member of a truncated bucket (or a never-hashed row):
        # nothing to remove, but a rebuild would resample -- record it
        self.exact_parity = False

    def _insert_host(self, p: int, slot: int, row_x, touched_b, touched_ov,
                     undo_b, undo_ov) -> bool:
        u, hit = self._lookup(p, row_x)
        if hit and int(self._counts_h[p, u]) < self.max_bucket \
                and not self._trunc_h[p, u]:
            cnt = int(self._counts_h[p, u])
            row = self._members_h[p, u]
            if (p, u) not in undo_b:
                undo_b[(p, u)] = (row.copy(), cnt)
            at = int(np.searchsorted(row[:cnt], slot))
            row[at + 1:cnt + 1] = row[at:cnt]
            row[at] = slot
            self._counts_h[p, u] = cnt + 1
            touched_b.add((p, u))
            return True
        free = np.where(self._overflow_h[p] < 0)[0]
        if free.size == 0:
            return False                        # shard overflow saturated
        at = int(free[0])
        if (p, at) not in undo_ov:
            undo_ov[(p, at)] = int(self._overflow_h[p, at])
        self._overflow_h[p, at] = slot
        touched_ov.add((p, at))
        self.exact_parity = False
        return True

    def patch_rows(self, slots, old_x, new_x, old_live, new_live) -> bool:
        """Apply one COALESCED mutation batch (``dataset.coalesce_mutations``
        output: first-touch old, last-touch new per slot) to the sharded
        table: the flat :class:`ops.HashPatcher` placement policy per
        shard -- splice into the owning shard's frozen bucket when it has
        room, else that shard's overflow region -- followed by ONE
        zero-collective device scatter of the touched bucket rows,
        overflow slots, and dataset rows.  Mutations never cross shards
        (a slot's owner is ``slot // shard_size``), so query gathers stay
        shard-local.  Returns ``False`` (mirrors restored, device state
        untouched, ``needs_rebuild`` set, ``OVERFLOW_SATURATED`` flagged)
        when any shard's overflow region is full -- the owner must
        rebuild before the next batch."""
        if self.spec.ov_cap == 0:
            raise ValueError("patch_rows needs a table built with "
                             "overflow_cap > 0")
        sp = self.spec
        slots = np.asarray(slots, np.int64)
        old_x = np.asarray(old_x, np.float32)
        new_x = np.asarray(new_x, np.float32)
        old_live = np.asarray(old_live, bool)
        new_live = np.asarray(new_live, bool)
        touched_b: set = set()
        touched_ov: set = set()
        undo_b: dict = {}
        undo_ov: dict = {}
        saturated = False
        for i, s in enumerate(slots):
            s = int(s)
            p = s // sp.shard_size
            if old_live[i]:
                self._remove_host(p, s, old_x[i], touched_b, touched_ov,
                                  undo_b, undo_ov)
            if new_live[i]:
                if not self._insert_host(p, s, new_x[i], touched_b,
                                         touched_ov, undo_b, undo_ov):
                    saturated = True
                    break
        if saturated:
            for (p, u), (row, cnt) in undo_b.items():
                self._members_h[p, u] = row
                self._counts_h[p, u] = cnt
            for (p, at), val in undo_ov.items():
                self._overflow_h[p, at] = val
            self.flags |= _g.OVERFLOW_SATURATED
            self.needs_rebuild = True
            return False
        bw = sorted(touched_b)
        ow = sorted(touched_ov)
        bp = _pad_pow2(np.asarray([b[0] for b in bw], np.int32), -1)
        bu = _pad_pow2(np.asarray([b[1] for b in bw], np.int32), 0)
        brow = _pad_pow2(
            np.asarray([self._members_h[b] for b in bw],
                       np.int32).reshape(-1, self.max_bucket), 0)
        bcnt = _pad_pow2(np.asarray([self._counts_h[b] for b in bw],
                                    np.int32), 0)
        ovp = _pad_pow2(np.asarray([o[0] for o in ow], np.int32), -1)
        ovpos = _pad_pow2(np.asarray([o[1] for o in ow], np.int32), 0)
        ovval = _pad_pow2(np.asarray([self._overflow_h[o] for o in ow],
                                     np.int32), 0)
        n_pad = sp.num_shards * sp.shard_size
        wslots = _pad_pow2(slots.astype(np.int32), n_pad)
        wrows = _pad_pow2(new_x, 0.0)
        self._members, self._counts, self._overflow, self.x_sh = \
            self._patch_program()(
                self._members, self._counts, self._overflow, self.x_sh,
                jnp.asarray(bp), jnp.asarray(bu), jnp.asarray(brow),
                jnp.asarray(bcnt), jnp.asarray(ovp), jnp.asarray(ovpos),
                jnp.asarray(ovval), jnp.asarray(wslots),
                jnp.asarray(wrows))
        self.x_pad = self.x_pad.at[jnp.asarray(slots.astype(np.int32))] \
            .set(jnp.asarray(new_x))
        for p in sorted({b[0] for b in bw} | {o[0] for o in ow}):
            self.shard_states[p] = self.shard_states[p]._replace(
                members=jnp.asarray(self._members_h[p]),
                counts=jnp.asarray(self._counts_h[p]),
                overflow=jnp.asarray(self._overflow_h[p]))
        return True

    @property
    def overflow_fill(self) -> int:
        """Occupied overflow slots across all shards (compaction policy)."""
        return int((self._overflow_h >= 0).sum())
