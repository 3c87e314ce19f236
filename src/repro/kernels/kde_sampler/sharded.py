"""Mesh-resident fused depth-2 sampling engine (DESIGN.md §9).

``ShardedBlocks`` is the multi-device twin of the single-device engine in
``ops.py``: the level-1 block structure lives sharded over a mesh (each
shard owns a contiguous run of dataset rows, padded with the far-offset
sentinel used everywhere else in this repo so every shard holds the same
number of whole blocks), and one depth-2 draw is a two-stage collective
program:

1. every shard computes its *local* masked block sums ``S_b^(p)`` (w, B_p)
   and a speculative local candidate -- block by inverse CDF over the local
   sums, level-2 row gathered from the shard's own ``(B_p, bs, d)`` block
   views, in-block draw -- all from replicated uniforms;
2. ONE ``psum`` of the one-hot payload ``(t_p, nb_p, S_b * p_in)`` makes
   the per-shard totals and candidates replicated, and the owning shard is
   picked by inverse CDF over the totals (the hierarchical decomposition
   ``p(shard) * p(block | shard) * p(col | block)`` of the flat categorical
   -- identical distribution to the single-device draw).

The realized probability returned is ``S_b * p_in / sum_p t_p`` -- exactly
the flat engine's ``(S_b / sum S) * p_in``.  Per draw batch the collective
schedule is exactly one ``psum`` and zero ``ppermute`` (asserted by
``collective_counts`` in tests); no stage ever moves dataset rows between
shards, so the O(n d / P) block views and the O(w n / P) level-1 sweeps are
the only per-device memory/compute.

Layout: ``n`` rows are padded to ``P * shard_size`` where ``shard_size``
is ``ceil(n / P)`` rounded up to a whole number of ``block_size`` blocks.
Padding sits at the global tail, so dataset indices are unchanged, global
block ``b`` covers rows ``[b * bs, (b+1) * bs)`` exactly as on one device,
and the extra all-sentinel blocks carry zero mass (they are excluded from
the 1e-12 floor, so they can never be drawn).

All entry points consume ``jax.random`` keys with the same split
discipline as their pure-jnp oracles in ``ref.py`` (ints must agree
bit-for-bit, floats to f32 tolerance).  ``ops.TRACE_COUNTS`` is shared, so
the no-retrace tests cover the sharded programs too.  Compiled programs
are cached at module level keyed on the full static config (mesh, layout,
kernel) -- dataset arrays are always call arguments, so successive
pipeline constructions over the same mesh share every program.

Every public program returns an ``obs.counters`` ``(WIDTH,)`` counter
word in the status position (DESIGN.md §15.1).  The words are assembled
OUTSIDE the shard_map programs -- counter slots are trace-time constants
from static shard shapes, status is the program's replicated post-psum
scalar -- so widening provably adds ZERO collectives (``psum_total`` per
draw batch is pinned by ``collective_counts`` in tests); the ``PSUMS``
slot records the §9 collective budget each call realizes.  Counts are
*global* realized work summed over shards, including the sentinel
padding shards sweep (device-realized evals, which on padded meshes
exceed the host's analytic per-row counts).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.extend.core import ClosedJaxpr
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.ft import guards as _g
from repro.kernels.kde_rowsum.ops import _PAD_OFFSET
from repro.kernels.kde_sampler import ops as _ops
from repro.kernels.kde_sampler import ref as _ref
from repro.obs import counters as _c
from repro.obs import metrics as _m

TRACE_COUNTS = _ops.TRACE_COUNTS

_COLLECTIVES = ("psum", "ppermute", "all_gather", "all_to_all",
                "reduce_scatter", "pmax", "pmin")

# jitted shard_map programs, keyed by (engine spec, program name,
# per-program statics) -- shared across ShardedBlocks instances.  The
# closures capture only the stateless _EngineSpec, never device arrays.
_PROGRAM_CACHE: dict = {}


def collective_counts(fn, *args, **kwargs):
    """Count collective primitive binds in ``fn``'s jaxpr (recursing into
    scan/while/call sub-jaxprs).  Each bind counts once regardless of loop
    trip count, so the result is the collective schedule *per draw batch*
    of a scanned program -- the object DESIGN.md §9 pins down."""
    jaxpr = jax.make_jaxpr(fn)(*args, **kwargs)
    acc: dict = {}

    def visit(jx):
        for eqn in jx.eqns:
            name = eqn.primitive.name
            if any(name.startswith(c) for c in _COLLECTIVES):
                acc[name] = acc.get(name, 0) + 1
            for v in eqn.params.values():
                if isinstance(v, ClosedJaxpr):
                    visit(v.jaxpr)
                elif hasattr(v, "eqns"):
                    visit(v)
                elif isinstance(v, (tuple, list)):
                    for w in v:
                        if isinstance(w, ClosedJaxpr):
                            visit(w.jaxpr)
    visit(jaxpr.jaxpr)
    acc["psum_total"] = sum(v for k, v in acc.items() if k.startswith("psum"))
    acc["ppermute_total"] = sum(v for k, v in acc.items()
                                if k.startswith("ppermute"))
    return acc


def _flat_index(mesh: Mesh, axes: Sequence[str]):
    """Flattened (row-major over ``axes``) shard index inside a shard_map
    body -- matches how ``P(axes)`` linearizes the shards."""
    idx = jnp.int32(0)
    for a in axes:
        idx = idx * int(mesh.shape[a]) + jax.lax.axis_index(a)
    return idx


@dataclasses.dataclass(frozen=True)
class _EngineSpec:
    """Static configuration + shard-local math of a sharded engine.

    Stateless (no device arrays), hashable, and the ONLY thing program
    closures capture -- so module-level program caching never pins a
    dataset, and two engines with equal specs share compiled programs.
    """

    mesh: Mesh
    axes: tuple
    num_shards: int
    n: int
    d: int
    block_size: int
    shard_size: int
    blocks_per_shard: int
    samples_per_block: int
    exact: bool
    kind: str
    inv_bw: float
    beta: float
    pairwise: object

    # ------------------------------------------------------------------ #
    # shard-local building blocks (called inside shard_map bodies)
    # ------------------------------------------------------------------ #
    def _local_block_sizes(self, pidx):
        """(B_p,) number of *real* (non-sentinel) rows per local block."""
        gbase = pidx * self.shard_size + jnp.arange(
            self.blocks_per_shard, dtype=jnp.int32) * self.block_size
        return jnp.clip(self.n - gbase, 0, self.block_size)

    @_m.scope("level1")
    def _raw_sums(self, q, x_l, xsq_l, key, pidx):
        """Uncorrected, unfloored stratified local block sums (the raw
        Definition 1.1 read -- estimators apply their own corrections)."""
        w = q.shape[0]
        bl, bs = self.blocks_per_shard, self.block_size
        s = self.samples_per_block
        kk = jax.random.fold_in(key, pidx)
        base = jnp.arange(bl, dtype=jnp.int32) * bs
        u = jax.random.uniform(kk, (bl, bs))
        pos = base[:, None] + jnp.arange(bs, dtype=jnp.int32)[None, :]
        valid = (pidx * self.shard_size + pos) < self.n
        u = jnp.where(valid, u, jnp.inf)
        _, order = jax.lax.top_k(-u, s)
        idx = jnp.take_along_axis(pos, order, axis=1)
        sel_valid = jnp.take_along_axis(valid, order, axis=1)
        flat = idx.reshape(-1)
        kv = _ref.kv_matrix(q, x_l[flat], xsq_l[flat], self.kind,
                            self.inv_bw, self.beta, self.pairwise)
        kv = kv.reshape(w, bl, s) * sel_valid[None]
        sizes_f = self._local_block_sizes(pidx).astype(jnp.float32)
        s_b = jnp.minimum(sizes_f, float(s))
        return kv.sum(-1) * (sizes_f / jnp.maximum(s_b, 1.0))[None, :]

    @_m.scope("level1")
    def _local_sums(self, q, own, x_l, xsq_l, key, pidx):
        """Masked §2-contract level-1 sums of the local shard: (w, B_p)
        with the self-kernel subtracted from each query's own block, real
        blocks floored at 1e-12, all-sentinel blocks pinned to 0.  The
        self-kernel is the repo-wide Kernel contract k(x, x) = 1 --
        identical to ``ops._masked_block_sums`` (bitwise parity)."""
        w = q.shape[0]
        bl, bs = self.blocks_per_shard, self.block_size
        if self.exact:
            kv = _ref.kv_matrix(q, x_l, xsq_l, self.kind, self.inv_bw,
                                self.beta, self.pairwise)
            sums = kv.reshape(w, bl, bs).sum(-1)
        else:
            sums = self._raw_sums(q, x_l, xsq_l, key, pidx)
        gblk = pidx * bl + jnp.arange(bl, dtype=jnp.int32)
        corr = gblk[None, :] == own[:, None]
        sums = jnp.where(corr, sums - 1.0, sums)
        real = self._local_block_sizes(pidx) > 0
        return jnp.where(real[None, :], jnp.maximum(sums,
                                                    _ref.BLOCK_SUM_FLOOR),
                         0.0)

    @_m.scope("level2")
    def _local_draw(self, src, q, qsq, sums_l, key, x_l, xsq_l, pidx):
        """One two-stage collective draw (the §9 schedule: exactly one
        psum).  Returns (nb, prob, T, status) replicated, T = global
        degree estimate sum_p t_p.  The status word is computed from the
        post-psum replicated values only (totals, probabilities), so the
        flags add ZERO collectives and are identical on every shard."""
        w = src.shape[0]
        bl, bs = self.blocks_per_shard, self.block_size
        k_shard, k_blk, k_in = jax.random.split(key, 3)
        # the all-sentinel blocks at the tail weigh 0: the pick never
        # lands there, however the sums round (ref.inverse_cdf_pick)
        blk_l, t_l = _ref.inverse_cdf_pick(
            sums_l, jax.random.uniform(k_blk, (w,)))
        s_b = jnp.take_along_axis(sums_l, blk_l[:, None], axis=1)[:, 0]
        xb = x_l.reshape(bl, bs, self.d)[blk_l]
        xbsq = xsq_l.reshape(bl, bs)[blk_l]
        kv = _ref.kv_rows(q, xb, qsq, xbsq, self.kind, self.inv_bw,
                          self.beta, self.pairwise)
        gcols = (pidx * self.shard_size + blk_l[:, None] * bs
                 + jnp.arange(bs, dtype=jnp.int32)[None, :])
        live = (gcols < self.n) & (gcols != src[:, None])
        kv = jnp.where(live, kv, 0.0)
        nb_l, pin = _ref.level2_draw(kv, live, jnp.minimum(gcols, self.n - 1),
                                     jax.random.uniform(k_in, (w,)))
        qnum = s_b * pin
        # ONE f32 array carries the whole one-hot payload (psum binds once
        # per array): the neighbor travels as its in-shard offset, exact in
        # f32 because shard_size < 2^24 (checked at construction), and
        # adding the other shards' zeros leaves every entry bit-exact
        local = (nb_l - pidx * self.shard_size).astype(jnp.float32)
        oh = (jnp.arange(self.num_shards) == pidx).astype(jnp.float32)
        payload = jnp.stack([t_l, qnum, local], axis=-1)          # (w, 3)
        allp = jax.lax.psum(payload[:, None, :] * oh[None, :, None],
                            self.axes)                            # (w, P, 3)
        t_all, q_all = allp[..., 0], allp[..., 1]
        owner, tot = _ref.inverse_cdf_pick(
            t_all, jax.random.uniform(k_shard, (w,)))
        nb = (owner * self.shard_size + jnp.take_along_axis(
            allp[..., 2], owner[:, None], axis=1)[:, 0].astype(jnp.int32))
        prob = jnp.take_along_axis(q_all, owner[:, None], axis=1)[:, 0] \
            / jnp.maximum(tot, 1e-30)
        num_real = -(-self.n // self.block_size)
        st = _g.merge(_g.totals_status(tot, num_real, _ref.BLOCK_SUM_FLOOR),
                      _g.result_status(prob))
        return nb, prob, tot, st

    def _local_sample_exact(self, src, q, qsq, sums_l, key, x_l, xsq_l,
                            x_rep, pidx, rounds, slack):
        """Theorem 4.12 rejection rounds on the sharded draw -- the same
        accept/reject math as ``ops._sample_exact_core`` with the global
        degree estimate coming from each draw's psum'd totals.  Returns
        (cur, status, fallback count); the acceptance mask is computed
        from replicated values, so the counters need no collective."""
        keys = jax.random.split(key, 2 * rounds + 1)
        cur, _, zs, st = self._local_draw(src, q, qsq, sums_l, keys[0], x_l,
                                          xsq_l, pidx)
        accepted = jnp.zeros(src.shape[0], bool)
        for r in range(rounds):
            cand, qd, _, st_r = self._local_draw(src, q, qsq, sums_l,
                                                 keys[2 * r + 1], x_l, xsq_l,
                                                 pidx)
            st = st | st_r
            kuv = _ref.kv_pairs(q, x_rep[cand], self.kind, self.inv_bw,
                                self.beta, self.pairwise)
            ratio = kuv / jnp.maximum(slack * qd * zs, 1e-30)
            u = jax.random.uniform(keys[2 * r + 2], (src.shape[0],))
            acc = (~accepted) & (u < jnp.minimum(ratio, 1.0))
            cur = jnp.where(acc, cand, cur)
            accepted |= acc
        fallbacks = jnp.sum(~accepted).astype(jnp.int32)
        st = st | _g.flag_if(fallbacks > 0, _g.REJECT_EXHAUSTED)
        return cur, st, fallbacks


class ShardedBlocks:
    """Sharded level-1 block structure + fused collective draw programs.

    Construction pads and places the dataset once (one sharded copy for
    the level-1 sweeps and block views, one replicated copy for frontier
    coordinate gathers); every method is a jitted ``shard_map`` program
    cached at module level by static config, so repeated same-shape calls
    -- across instances too -- never retrace.
    """

    def __init__(self, mesh: Mesh, x, kernel, *, block_size: int,
                 samples_per_block: int = 16, exact: bool = False,
                 data_axes: Sequence[str] = ("data",)):
        axes = tuple(data_axes)
        num_shards = 1
        for a in axes:
            num_shards *= int(mesh.shape[a])
        x = jnp.asarray(x, jnp.float32)
        n, d = int(x.shape[0]), int(x.shape[1])
        bs = int(block_size)
        per = -(-n // num_shards)                             # ceil(n / P)
        shard_size = -(-per // bs) * bs
        if shard_size >= 1 << 24:
            raise ValueError(f"shard_size {shard_size} >= 2^24: the draw "
                             "payload carries in-shard offsets in f32")
        self.spec = _EngineSpec(
            mesh=mesh, axes=axes, num_shards=num_shards, n=n, d=d,
            block_size=bs, shard_size=shard_size,
            blocks_per_shard=shard_size // bs,
            samples_per_block=min(int(samples_per_block), bs),
            exact=bool(exact), kind=kernel.name,
            inv_bw=1.0 / kernel.bandwidth,
            beta=float(getattr(kernel, "beta", 1.0)),
            pairwise=_ref.static_pairwise(kernel))
        self.mesh = mesh
        self.axes = axes
        self.num_shards = num_shards
        self.n = n
        self.d = d
        self.block_size = bs
        self.shard_size = shard_size
        self.blocks_per_shard = self.spec.blocks_per_shard
        self.num_blocks_pad = num_shards * self.spec.blocks_per_shard
        self.num_blocks = -(-n // bs)                         # real blocks
        self.samples_per_block = self.spec.samples_per_block
        self.exact = bool(exact)
        self.n_pad = num_shards * shard_size
        pad = self.n_pad - n
        if pad:
            sent = jnp.full((pad, d), _PAD_OFFSET, jnp.float32) + x[-1:]
            xp = jnp.concatenate([x, sent], axis=0)
        else:
            xp = x
        xsq = jnp.sum(xp * xp, axis=-1)
        self.x_sh = jax.device_put(xp, NamedSharding(mesh, P(axes)))
        self.x_sq_sh = jax.device_put(xsq, NamedSharding(mesh, P(axes)))
        self.x_rep = jax.device_put(xp, NamedSharding(mesh, P()))
        self.x_sq_rep = jax.device_put(xsq, NamedSharding(mesh, P()))

    # ------------------------------------------------------------------ #
    # program builders (cached at module level per static config)
    # ------------------------------------------------------------------ #
    def _build(self, name, body, in_specs, out_specs):
        mesh = self.mesh   # bind locally: the cached closure must capture
                           # only statics, never self (and its arrays)

        def outer(*args):
            TRACE_COUNTS[name] += 1
            # check_vma=False: the replication checker cannot follow a
            # psum-in-scan-body carry; replication of the outputs is pinned
            # by the ref-oracle tests instead.
            return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, check_vma=False)(*args)
        return jax.jit(outer)

    def _program(self, key, factory):
        full = (self.spec, key)
        if full not in _PROGRAM_CACHE:
            _PROGRAM_CACHE[full] = factory()
        return _PROGRAM_CACHE[full]

    def _sharded_args(self):
        return self.x_sh, self.x_sq_sh, self.x_rep, self.x_sq_rep

    def _specs4(self):
        ax = self.axes
        return (P(ax), P(ax), P(), P())

    def _l1_evals(self, w: int) -> int:
        """Global realized level-1 kernel evals of one frontier sweep:
        every shard sweeps its whole padded slice (exact) or its
        ``B_p * s`` stratified subsample -- trace-time constant."""
        if self.exact:
            return w * self.n_pad
        return w * self.num_blocks_pad * self.samples_per_block

    # ------------------------------------------------------------------ #
    # public fused programs
    # ------------------------------------------------------------------ #
    def _patch_program(self):
        """The jitted zero-collective mutation program (exposed so tests
        can jaxpr-assert its collective schedule)."""
        sp = self.spec

        def factory():
            def body(x_l, xsq_l, x_rep, xsq_rep, slots, rows):
                pidx = _flat_index(sp.mesh, sp.axes)
                rows_sq = jnp.sum(rows * rows, axis=-1)
                # each shard scatters ONLY its own rows: non-local slots
                # map to the out-of-range local index and are dropped
                lidx = slots - pidx * sp.shard_size
                lidx = jnp.where((lidx >= 0) & (lidx < sp.shard_size),
                                 lidx, sp.shard_size)
                x_l = x_l.at[lidx].set(rows, mode="drop")
                xsq_l = xsq_l.at[lidx].set(rows_sq, mode="drop")
                x_rep = x_rep.at[slots].set(rows)
                xsq_rep = xsq_rep.at[slots].set(rows_sq)
                return x_l, xsq_l, x_rep, xsq_rep
            return self._build("sharded_patch_rows", body,
                               self._specs4() + (P(), P()),
                               self._specs4())
        return self._program("patch_rows", factory)

    def patch_rows(self, slots, rows):
        """Scatter a mutation batch into the mesh-resident dataset copies
        (DESIGN.md §12): each shard patches its own rows, the replicated
        frontier copy is patched in place on every device -- ZERO new
        collectives per mutation batch, so the §9 one-psum-per-draw
        schedule is untouched.  Derived level-1 caches are the caller's
        to patch or drop (``ops.patch_block_sums`` / the §4 cache).
        Returns a zero-eval counter word (scatters are not kernel
        evals)."""
        fn = self._patch_program()
        self.x_sh, self.x_sq_sh, self.x_rep, self.x_sq_rep = fn(
            *self._sharded_args(), jnp.asarray(slots, jnp.int32),
            jnp.asarray(rows, jnp.float32))
        return _c.word()

    def masked_block_sums(self, src, key):
        """Global §2-contract level-1 sums of a frontier: ``(sums, word)``
        with sums (w, B_pad) sharded along columns, no collective at all
        (sampling needs only the psum of totals, which each draw performs
        itself).  The counter word is assembled host-side from static
        shard shapes plus the non-finite check of the returned sums."""
        sp = self.spec

        def factory():
            def body(x_l, xsq_l, x_rep, xsq_rep, src, key):
                pidx = _flat_index(sp.mesh, sp.axes)
                q = x_rep[src]
                return sp._local_sums(q, (src // sp.block_size)
                                      .astype(jnp.int32), x_l, xsq_l,
                                      key, pidx)
            return self._build("sharded_masked_block_sums", body,
                               self._specs4() + (P(), P()),
                               P(None, self.axes))
        fn = self._program("masked_block_sums", factory)
        w = int(jnp.shape(src)[0])
        sums = fn(*self._sharded_args(), jnp.asarray(src, jnp.int32), key)
        cw = _c.fold_status(
            _c.word(evals=self._l1_evals(w), l1_reads=w),
            _g.nonfinite_status(sums))
        return sums, cw

    def fused_sample(self, src, key):
        """One depth-2 collective draw: (nb, prob, global level-1 sums,
        counter word) -- the sharded twin of ``ops.fused_sample`` (and
        the §4 cache producer).  The status is post-psum replicated and
        the counters are static, so the §9 one-psum schedule is
        unchanged (PSUMS slot = 1)."""
        sp = self.spec

        def factory():
            def body(x_l, xsq_l, x_rep, xsq_rep, src, key):
                pidx = _flat_index(sp.mesh, sp.axes)
                q = x_rep[src]
                qsq = xsq_rep[src]
                k_l1, k_rest = jax.random.split(key)
                sums_l = sp._local_sums(q, (src // sp.block_size)
                                        .astype(jnp.int32), x_l, xsq_l,
                                        k_l1, pidx)
                nb, prob, _, st = sp._local_draw(src, q, qsq, sums_l,
                                                 k_rest, x_l, xsq_l, pidx)
                return nb, prob, sums_l, st
            return self._build("sharded_fused_sample", body,
                               self._specs4() + (P(), P()),
                               (P(), P(), P(None, self.axes), P()))
        fn = self._program("fused_sample", factory)
        w = int(jnp.shape(src)[0])
        nb, prob, sums, st = fn(*self._sharded_args(),
                                jnp.asarray(src, jnp.int32), key)
        cw = _c.fold_status(
            _c.word(evals=self._l1_evals(w)
                    + w * self.block_size * self.num_shards,
                    l1_reads=w, draws=w, psums=1), st)
        return nb, prob, sums, cw

    def sample_from_block_sums(self, src, sums, key):
        """Depth-2 collective draw reusing cached global level-1 sums
        (the §4 caching contract: no dataset re-sweep).  Returns
        (nb, prob, counter word) -- PSUMS slot = 1, no level-1 evals."""
        sp = self.spec

        def factory():
            def body(x_l, xsq_l, x_rep, xsq_rep, src, sums_l, key):
                pidx = _flat_index(sp.mesh, sp.axes)
                nb, prob, _, st = sp._local_draw(
                    src, x_rep[src], xsq_rep[src], sums_l, key, x_l, xsq_l,
                    pidx)
                return nb, prob, st
            return self._build("sharded_sample_from_block_sums", body,
                               self._specs4() + (P(), P(None, self.axes),
                                                 P()),
                               (P(), P(), P()))
        fn = self._program("sample_cached", factory)
        w = int(jnp.shape(src)[0])
        nb, prob, st = fn(*self._sharded_args(), jnp.asarray(src, jnp.int32),
                          sums, key)
        cw = _c.fold_status(
            _c.word(evals=w * self.block_size * self.num_shards,
                    draws=w, psums=1), st)
        return nb, prob, cw

    def prob_of_from_block_sums(self, src, dst, sums):
        """q(dst | src) from cached global sums.  The global (w, B_pad)
        sums are directly addressable, so this is the single-device
        ``ops.prob_of_from_block_sums`` on the padded replicated dataset
        -- an O(w bs) read, no collective."""
        sp = self.spec
        return _ops.prob_of_from_block_sums(
            self.x_rep, self.x_sq_rep, jnp.asarray(src, jnp.int32),
            jnp.asarray(dst, jnp.int32), sums, kind=sp.kind,
            inv_bw=sp.inv_bw, beta=sp.beta, pairwise=sp.pairwise,
            block_size=sp.block_size, n=sp.n)

    def sample_exact(self, src, sums, key, *, rounds: int, slack: float):
        """Theorem 4.12 rejection-exact draw from cached global sums.
        Returns (cur, counter word, fallback count) -- PSUMS slot =
        ``rounds + 1`` (one psum per realized draw)."""
        sp = self.spec

        def factory():
            def body(x_l, xsq_l, x_rep, xsq_rep, src, sums_l, key):
                pidx = _flat_index(sp.mesh, sp.axes)
                return sp._local_sample_exact(
                    src, x_rep[src], xsq_rep[src], sums_l, key, x_l, xsq_l,
                    x_rep, pidx, rounds, slack)
            return self._build("sharded_sample_exact", body,
                               self._specs4() + (P(), P(None, self.axes),
                                                 P()),
                               (P(), P(), P()))
        fn = self._program(("sample_exact", rounds, float(slack)), factory)
        w = int(jnp.shape(src)[0])
        cur, st, fb = fn(*self._sharded_args(), jnp.asarray(src, jnp.int32),
                         sums, key)
        # level-2 draws on every shard + the replicated accept-ratio
        # kv_pairs each rejection round computes on all shards
        cw = _c.fold_status(
            _c.word(evals=(rounds + 1) * w * self.block_size
                    * self.num_shards + rounds * w * self.num_shards,
                    draws=(rounds + 1) * w, retries=fb,
                    psums=rounds + 1), st)
        return cur, cw, fb

    def walk_scan(self, starts, keys, *, rounds: int = 0, slack: float = 2.0,
                  record_path: bool = False):
        """T walk steps under ``lax.scan`` inside one shard_map program:
        the frontier is replicated scan carry, every step one two-stage
        draw (exactly one psum per step).  Returns (end, path, counter
        word, fallbacks): the per-step status bits and rejection-fallback
        counts fold into the carry (replicated, zero extra collectives);
        the word's counters are static per-step costs scaled by the step
        count (PSUMS = steps, or steps * (rounds + 1) on the
        rejection-exact path)."""
        sp = self.spec

        def factory():
            def body(x_l, xsq_l, x_rep, xsq_rep, starts, keys):
                pidx = _flat_index(sp.mesh, sp.axes)

                def step(carry, k):
                    cur, st, fb = carry
                    k_l1, k_rs = jax.random.split(k)
                    q = x_rep[cur]
                    qsq = xsq_rep[cur]
                    sums_l = sp._local_sums(
                        q, (cur // sp.block_size).astype(jnp.int32), x_l,
                        xsq_l, k_l1, pidx)
                    if rounds > 0:
                        nxt, st_k, fb_k = sp._local_sample_exact(
                            cur, q, qsq, sums_l, k_rs, x_l, xsq_l, x_rep,
                            pidx, rounds, slack)
                        fb = fb + fb_k
                    else:
                        nxt, _, _, st_k = sp._local_draw(
                            cur, q, qsq, sums_l, k_rs, x_l, xsq_l, pidx)
                    return (nxt, st | st_k, fb), \
                        (nxt if record_path else None)

                (end, st, fb), path = jax.lax.scan(
                    step, (starts, jnp.uint32(0), jnp.int32(0)), keys)
                return end, path, st, fb

            out_path = P() if record_path else None
            return self._build("sharded_walk_scan", body,
                               self._specs4() + (P(), P()),
                               (P(), out_path, P(), P()))
        fn = self._program(("walk_scan", rounds, float(slack),
                            bool(record_path)), factory)
        end, path, st, fb = fn(*self._sharded_args(),
                               jnp.asarray(starts, jnp.int32), keys)
        w = int(jnp.shape(starts)[0])
        steps = int(jnp.shape(keys)[0])
        draws_per = (rounds + 1) if rounds > 0 else 1
        per_step = (self._l1_evals(w)
                    + draws_per * w * self.block_size * self.num_shards
                    + rounds * w * self.num_shards)
        cw = _c.fold_status(
            _c.word(evals=steps * per_step, l1_reads=steps * w,
                    draws=steps * draws_per * w, retries=fb,
                    psums=steps * draws_per), st)
        return end, path, cw, fb

    def edge_batch_scan(self, cdf, degs, inv_total, inv_t, keys, *,
                        batch: int):
        """All Algorithm 5.1 edge batches as one scanned collective
        program -- u by replicated inverse CDF over the device degree
        prefix, v | u by the two-stage draw (one psum per batch), the
        collapsed reverse probability and reweighting replicated.  The
        last output is the counter word of the whole scan (status
        or-folded over batches, PSUMS = number of batches)."""
        sp = self.spec

        def factory():
            @_m.scope("edge_scan")
            def body(x_l, xsq_l, x_rep, xsq_rep, cdf, degs, inv_total,
                     inv_t, keys):
                pidx = _flat_index(sp.mesh, sp.axes)
                # every batch's u-draw depends on its key alone, so all of
                # them run before the scan, as one inverse-CDF lookup: the
                # loop body keeps the level-1 sweep and the collective draw
                # (the same values as drawing u in the loop)
                k_u, k_fwd = jnp.moveaxis(jax.vmap(jax.random.split)(keys),
                                          1, 0)
                us = _ref.inverse_cdf_index(cdf, jax.vmap(
                    lambda k: jax.random.uniform(k, (batch,)))(k_u))

                def step(st, xs):
                    u, k_fwd = xs
                    q = x_rep[u]
                    qsq = xsq_rep[u]
                    k_l1, k_rest = jax.random.split(k_fwd)
                    sums_l = sp._local_sums(q, (u // sp.block_size)
                                            .astype(jnp.int32), x_l,
                                            xsq_l, k_l1, pidx)
                    v, q_uv, _, st_b = sp._local_draw(u, q, qsq, sums_l,
                                                      k_rest, x_l, xsq_l,
                                                      pidx)
                    kuv = _ref.kv_pairs(q, x_rep[v], sp.kind, sp.inv_bw,
                                        sp.beta, sp.pairwise)
                    q_vu = kuv / jnp.maximum(degs[v], _ref.BLOCK_SUM_FLOOR)
                    q_edge = inv_total * (degs[u] * q_uv + kuv)
                    wgt = kuv * inv_t / jnp.maximum(q_edge, 1e-30)
                    st = st | st_b | _g.result_status(wgt, q_vu)
                    return st, (u, v, wgt, q_uv, q_vu)

                st, out = jax.lax.scan(step, jnp.uint32(0), (us, k_fwd))
                return out + (st,)
            return self._build("sharded_edge_batch_scan", body,
                               self._specs4() + (P(), P(), P(), P(), P()),
                               (P(), P(), P(), P(), P(), P()))
        fn = self._program(("edge_batch_scan", int(batch)), factory)
        out = fn(*self._sharded_args(), jnp.asarray(cdf),
                 jnp.asarray(degs), jnp.float32(inv_total),
                 jnp.float32(inv_t), keys)
        *data, st = out
        steps = int(jnp.shape(keys)[0])
        # per batch: one level-1 sweep + the speculative level-2 rows on
        # every shard + the replicated k(u, v) pair eval per shard
        per_batch = (self._l1_evals(batch)
                     + batch * self.block_size * self.num_shards
                     + batch * self.num_shards)
        cw = _c.fold_status(
            _c.word(evals=steps * per_batch, l1_reads=steps * batch,
                    draws=steps * batch, psums=steps), st)
        return tuple(data) + (cw,)

    def triangle_edge_scan(self, u, v, degs, keys):
        """Theorem 6.17's per-edge inner loop sharded: orientation
        replicated, ONE local level-1 read of the oriented v frontier
        (keys[0]) shared by every draw, then a scan over keys[1:] of
        two-stage draws (one psum each) with the ordering mask and the
        in-program reweighting.  The last output is the counter word
        (PSUMS = number of draws)."""
        sp = self.spec

        def factory():
            def body(x_l, xsq_l, x_rep, xsq_rep, u, v, degs, keys):
                pidx = _flat_index(sp.mesh, sp.axes)
                prec = _ref.degree_precedes(degs, u, v)
                uu = jnp.where(prec, u, v)
                vv = jnp.where(prec, v, u)
                q = x_rep[vv]
                qsq = xsq_rep[vv]
                kuv = _ref.kv_pairs(x_rep[uu], q, sp.kind, sp.inv_bw,
                                    sp.beta, sp.pairwise)
                sums_l = sp._local_sums(q, (vv // sp.block_size)
                                        .astype(jnp.int32), x_l, xsq_l,
                                        keys[0], pidx)

                def step(carry, k):
                    acc, st = carry
                    w, _, _, st_k = sp._local_draw(vv, q, qsq, sums_l, k,
                                                   x_l, xsq_l, pidx)
                    valid = _ref.degree_precedes(degs, vv, w) & (w != uu)
                    kuw = _ref.kv_pairs(x_rep[uu], x_rep[w], sp.kind,
                                        sp.inv_bw, sp.beta, sp.pairwise)
                    return (acc + jnp.where(valid, kuv * kuw, 0.0),
                            st | st_k), None

                (acc, st), _ = jax.lax.scan(
                    step, (jnp.zeros_like(kuv), jnp.uint32(0)), keys[1:])
                num_draws = keys.shape[0] - 1
                w_hat = acc * degs[vv] / num_draws
                return uu, vv, w_hat, _g.merge(st, _g.result_status(w_hat))
            return self._build("sharded_triangle_edge_scan", body,
                               self._specs4() + (P(), P(), P(), P()),
                               (P(), P(), P(), P()))
        fn = self._program("triangle_edge_scan", factory)
        uu, vv, w_hat, st = fn(*self._sharded_args(),
                               jnp.asarray(u, jnp.int32),
                               jnp.asarray(v, jnp.int32),
                               jnp.asarray(degs), keys)
        m = int(jnp.shape(u)[0])
        num_draws = int(jnp.shape(keys)[0]) - 1
        # one shared level-1 read + per-shard k(u, v) pairs + per draw the
        # per-shard level-2 rows and k(u, w) pairs
        cw = _c.fold_status(
            _c.word(evals=self._l1_evals(m) + m * self.num_shards
                    + num_draws * (m * self.block_size * self.num_shards
                                   + m * self.num_shards),
                    l1_reads=m, draws=num_draws * m, psums=num_draws), st)
        return uu, vv, w_hat, cw

    # ------------------------------------------------------------------ #
    # KDE-structure reads (the Definition 1.1 surface)
    # ------------------------------------------------------------------ #
    def kde_query(self, y, key):
        """Row-sum estimates of replicated queries: ``((m,), word)`` --
        local sweep (or local stratified block sums) + one psum,
        Definition 1.1 over the sharded dataset (PSUMS slot = 1)."""
        sp = self.spec

        def factory():
            def body(x_l, xsq_l, y, key):
                pidx = _flat_index(sp.mesh, sp.axes)
                if sp.exact:
                    kv = _ref.kv_matrix(y, x_l, xsq_l, sp.kind, sp.inv_bw,
                                        sp.beta, sp.pairwise)
                    part = kv.sum(axis=1)
                else:
                    part = sp._raw_sums(y, x_l, xsq_l, key, pidx).sum(
                        axis=1)
                return jax.lax.psum(part, sp.axes)
            return self._build("sharded_kde_query", body,
                               (P(self.axes), P(self.axes), P(), P()), P())
        fn = self._program("kde_query", factory)
        est = fn(self.x_sh, self.x_sq_sh, jnp.asarray(y, jnp.float32), key)
        m = int(jnp.shape(y)[0])
        cw = _c.fold_status(
            _c.word(evals=self._l1_evals(m), l1_reads=m, psums=1),
            _g.nonfinite_status(est))
        return est, cw

    def kernel_rows(self, q):
        """Exact (m, n) kernel rows against the sharded dataset -- the FKV
        sketch / CP17 column reads, computed shard-local and returned
        with a counter word (no collective; evals count the padded
        sweep each shard realizes)."""
        sp = self.spec

        def factory():
            def body(x_l, xsq_l, q):
                return _ref.kv_matrix(q, x_l, xsq_l, sp.kind, sp.inv_bw,
                                      sp.beta, sp.pairwise)
            return self._build("sharded_kernel_rows", body,
                               (P(self.axes), P(self.axes), P()),
                               P(None, self.axes))
        fn = self._program("kernel_rows", factory)
        out = fn(self.x_sh, self.x_sq_sh, jnp.asarray(q, jnp.float32))
        out = out[:, :self.n]
        m = int(jnp.shape(q)[0])
        cw = _c.fold_status(_c.word(evals=m * self.n_pad),
                            _g.nonfinite_status(out))
        return out, cw

    def degrees_ring(self, kernel):
        """Algorithm 4.3 over the sharded dataset: the ring-permute
        all-to-all accumulation (O(n^2 / P) work and O(shard^2) memory per
        device), minus the kernel's *actual* per-point diagonal.  Returns
        the ((n,) degree vector, counter word) -- the ring uses ppermute
        only, so the PSUMS slot is 0."""
        def factory():
            body = _ring_degrees_body(kernel, self.axes, self.num_shards)
            return self._build("sharded_degrees_ring", body,
                               (P(self.axes),), P(self.axes))
        fn = self._program("degrees_ring", factory)
        deg = fn(self.x_sh)[:self.n]
        cw = _c.fold_status(_c.word(evals=self.n_pad * self.n_pad),
                            _g.nonfinite_status(deg))
        return deg, cw


def _ring_degrees_body(kernel, axes, size: int):
    """Shared ring-accumulation body for Algorithm 4.3: every shard visits
    every other shard exactly once over the flattened ring, then subtracts
    the kernel's actual per-point diagonal k(x_i, x_i) (NOT a hardcoded
    1.0 -- custom kernels with non-unit diagonals get unbiased degrees;
    Table-1 kernels have an exactly-unit diagonal, kept as the constant
    to avoid float noise)."""
    perm = [(i, (i + 1) % size) for i in range(size)]
    axis = axes[0] if len(axes) == 1 else axes
    unit_diag = kernel.name in _ref.BUILTIN_KINDS

    @_m.scope("degrees")
    def body(x_l):
        def step(carry, _):
            acc, blk = carry
            acc = acc + jnp.sum(kernel.pairwise(x_l, blk), axis=1)
            blk = jax.lax.ppermute(blk, axis, perm=perm)
            return (acc, blk), None

        acc0 = jnp.sum(x_l, axis=1) * 0.0
        (acc, _), _ = jax.lax.scan(step, (acc0, x_l), None, length=size)
        return acc - (1.0 if unit_diag else kernel.pairs(x_l, x_l))
    return body


# --------------------------------------------------------------------- #
# builders for caller-sharded datasets (the `core.kde.distributed` API)
# --------------------------------------------------------------------- #
def make_kde_query(mesh: Mesh, kernel, data_axes: Sequence[str] = ("data",)):
    """Definition 1.1 over a caller-sharded dataset: jitted
    f(y replicated, x sharded) -> (m,) row sums, local sweep + one psum."""
    axes = tuple(data_axes)

    def body(y, x_l):
        part = jnp.sum(kernel.pairwise(y, x_l), axis=1)
        return jax.lax.psum(part, axes)

    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P(), P(axes)),
                                 out_specs=P()))


def make_block_sums(mesh: Mesh, kernel, num_blocks_per_shard: int,
                    data_axes: Sequence[str] = ("data",)):
    """Level-1 block sums over a caller-sharded dataset, ragged-safe:
    shards whose row count does not divide ``num_blocks_per_shard`` are
    padded in-body with the far-offset sentinel rows (kernel values are
    exactly 0), so the reshape never crashes and tail blocks sum only
    their real rows.  Returns jitted f(y, x[, own]) -> (m, shards * B);
    with ``own`` (each query's global block index, or -1) the §2 sampling
    contract is applied: the self kernel k(y, y) = 1 (the repo-wide
    Kernel contract, matching the single-device engine bitwise)
    subtracted from the own block and every real block floored at
    1e-12."""
    axes = tuple(data_axes)

    def local(y, x_l, own):
        m = y.shape[0]
        ns = x_l.shape[0]
        bs_l = -(-ns // num_blocks_per_shard)
        pad = num_blocks_per_shard * bs_l - ns
        if pad:
            sent = jnp.full((pad, x_l.shape[1]), _PAD_OFFSET,
                            x_l.dtype) + x_l[-1:]
            x_l = jnp.concatenate([x_l, sent], axis=0)
        kv = kernel.pairwise(y, x_l)
        sums = kv.reshape(m, num_blocks_per_shard, bs_l).sum(-1)
        if own is None:
            return sums
        pidx = _flat_index(mesh, axes)
        gblk = pidx * num_blocks_per_shard + jnp.arange(
            num_blocks_per_shard, dtype=jnp.int32)
        corr = gblk[None, :] == own[:, None]
        sums = jnp.where(corr, sums - 1.0, sums)
        base = jnp.arange(num_blocks_per_shard, dtype=jnp.int32) * bs_l
        real = jnp.clip(ns - base, 0, bs_l) > 0
        return jnp.where(real[None, :],
                         jnp.maximum(sums, _ref.BLOCK_SUM_FLOOR), 0.0)

    raw = jax.jit(jax.shard_map(lambda y, x_l: local(y, x_l, None), mesh=mesh,
                                in_specs=(P(), P(axes)),
                                out_specs=P(None, axes)))
    masked = jax.jit(jax.shard_map(local, mesh=mesh,
                                   in_specs=(P(), P(axes), P()),
                                   out_specs=P(None, axes)))

    def f(y, x, own=None):
        if own is None:
            return raw(y, x)
        return masked(y, x, jnp.asarray(own, jnp.int32))

    return f


def make_degree_ring(mesh: Mesh, kernel,
                     data_axes: Sequence[str] = ("data",)):
    """Algorithm 4.3 over a caller-sharded dataset: jitted f(x sharded) ->
    degrees sharded the same way, via the flattened-ring ppermute schedule
    with the actual-diagonal correction."""
    axes = tuple(data_axes)
    size = 1
    for a in axes:
        size *= int(mesh.shape[a])
    body = _ring_degrees_body(kernel, axes, size)
    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P(axes),),
                                 out_specs=P(axes)))


# --------------------------------------------------------------------- #
# standalone sharded programs (no block structure needed)
# --------------------------------------------------------------------- #
@functools.lru_cache(maxsize=32)
def _noisy_power_program(mesh: Mesh, axes, num_samples: int, cols_per: int):
    num = 1
    for a in axes:
        num *= int(mesh.shape[a])
    t_pad = num * cols_per

    def body(ksub_l, v0, keys):
        pidx = _flat_index(mesh, axes)
        off = pidx * cols_per
        t = v0.shape[0]

        def step(carry, k):
            v, st = carry
            absv = jnp.abs(v)
            z = jnp.sum(absv)
            cdf = jnp.cumsum(absv)
            u = jax.random.uniform(k, (num_samples,)) * jnp.maximum(z, 1e-30)
            idx = jnp.clip(jnp.searchsorted(cdf, u, side="right"),
                           0, t - 1).astype(jnp.int32)
            sel = (idx >= off) & (idx < off + cols_per)
            lidx = jnp.clip(idx - off, 0, cols_per - 1)
            contrib = jnp.sign(v[idx]) * z / num_samples * sel
            w_p = ksub_l[:, lidx] @ contrib
            w = jax.lax.psum(w_p, axes)
            nw = jnp.linalg.norm(w)
            ok = (nw > 0.0) & (z > 0.0)
            st = st | _g.flag_if(~ok, _g.ZERO_MASS) | _g.nonfinite_status(w)
            return (jnp.where(ok, w / jnp.maximum(nw, 1e-30), v), st), None

        (v, st), _ = jax.lax.scan(step, (v0, jnp.uint32(0)), keys)
        # pad v to the column-padded width so the last shard's slice is
        # never clamped out of alignment
        vp = jnp.pad(v, (0, t_pad - t))
        av = jax.lax.psum(
            ksub_l @ jax.lax.dynamic_slice(vp, (off,), (cols_per,)), axes)
        lam = v @ av
        return lam, v, _g.merge(st, _g.result_status(lam, v))

    def outer(ksub_sh, v0, keys):
        TRACE_COUNTS["sharded_noisy_power_scan"] += 1
        return jax.shard_map(body, mesh=mesh,
                             in_specs=(P(None, axes), P(), P()),
                             out_specs=(P(), P(), P()),
                             check_vma=False)(ksub_sh, v0, keys)
    return jax.jit(outer)


def sharded_noisy_power(mesh: Mesh, ksub, v0, keys, *, num_samples: int,
                        data_axes: Sequence[str] = ("data",)):
    """BIMW21 noisy power method with the t x t submatrix sharded over
    columns: the importance draw and renormalization are replicated, the
    sampled matvec is a local masked gather + partial matvec + ONE psum
    per iteration (the §9 collective budget).  Same math and key stream
    as ``ops.noisy_power_scan`` (per-shard partial sums reorder the float
    accumulation, so floats agree to f32 tolerance, not bitwise).
    Returns ``(lam, v, counter word)``; slot 0 folds the stalled-iterate
    (zero mass) and non-finite flags across all iterations, DRAWS counts
    the importance draws, PSUMS the one-per-iteration matvec psums plus
    the final Rayleigh-quotient psum."""
    axes = tuple(data_axes)
    num = 1
    for a in axes:
        num *= int(mesh.shape[a])
    t = int(ksub.shape[0])
    t_pad = -(-t // num) * num
    ksub = jnp.asarray(ksub, jnp.float32)
    if t_pad != t:
        ksub = jnp.pad(ksub, ((0, 0), (0, t_pad - t)))
    ksub_sh = jax.device_put(ksub, NamedSharding(mesh, P(None, axes)))
    fn = _noisy_power_program(mesh, axes, int(num_samples), t_pad // num)
    lam, v, st = fn(ksub_sh, jnp.asarray(v0, jnp.float32), keys)
    iters = int(jnp.shape(keys)[0])
    cw = _c.fold_status(
        _c.word(draws=iters * int(num_samples), psums=iters + 1), st)
    return lam, v, cw
