"""Device-resident fused depth-2 neighbor sampling engine (DESIGN.md §3).

One walk step = ONE compiled program: level-1 masked block sums (Pallas on
TPU, jnp sweep elsewhere -- ``kernels.platform`` decides), Gumbel-max
block draw, level-2 exact in-block row, and the in-block categorical
draw -- no host sync between stages.
``jax.random`` keys drive all randomness, so every path is jit-compatible
and reproducible.

Public entry points (all jitted; static config is passed by keyword):

* ``stratified_block_sums`` / ``exact_block_sums`` -- vectorized level-1
  reads used by ``core.kde.base`` (the stratified path masks padded tail
  samples out of the sum and scales by the *realized* per-block sample
  count, fixing the seed's padding bias).
* ``fused_sample``            -- full depth-2 step; also returns the masked
  level-1 sums so callers can cache them (DESIGN.md §4).
* ``sample_from_block_sums``  -- depth-2 step reusing cached level-1 sums.
* ``prob_of_from_block_sums`` -- q(dst | src) from cached level-1 sums.
* ``fused_sample_exact``      -- Theorem 4.12 rejection rounds, one program.
* ``walk_scan``               -- T walk steps under ``lax.scan``; the
  frontier never leaves the device (``record_path=False`` skips the
  (T, w) path stack entirely).
* ``fused_edge_batch``        -- one Algorithm 5.1 edge batch: u ~ degrees
  (inverse CDF over a device prefix array), v | u, reverse probability,
  and the importance weight, all in one program (DESIGN.md §6).
* ``edge_batch_scan``         -- ALL edge batches of a sparsifier call as
  one ``lax.scan`` program (one dispatch, one transfer out).
* ``kernel_rows``             -- exact batched kernel rows for the FKV /
  CP17 low-rank pipeline (Section 5.2).
* ``batched_fused_sample`` / ``batched_walk_scan`` / ``batched_prob_of``
  / ``batched_kde_query``    -- the multi-tenant serving entry points
  (DESIGN.md §13): vmap over a request axis with per-request PRNG keys,
  per-request status words, and a stacked tenant arena; on a one-tenant
  arena the exact Pallas draw, ``prob_of`` and query reads pack all
  requests' rows into ONE level-1 pass (``level1_passes`` counts the
  passes).

Every sampling / application program additionally returns a ``(obs.WIDTH,)``
uint32 **counter word** (``repro.obs.counters``, DESIGN.md §15): slot 0 is
the PR-6 status bitmask (``repro.ft.guards``) -- cheap in-program
reductions over values the program already computed (NaN/Inf sums,
zero-mass rows at the ``BLOCK_SUM_FLOOR``, rejection exhaustion, CG
non-convergence) -- and slots 1+ count the realized device work (kernel
evals, level-1 reads, draws, rejection retries, FAR samples).  The
counters are trace-time constants derived from static shapes (plus the
data-dependent rejection-retry count), so the word costs nothing at run
time and adds zero collectives; scan programs fold per-step words through
their carries.  Flags stay advisory; consumers escalate via
``guards.raise_on_status`` under ``REPRO_CHECKS=1`` (DESIGN.md §11) and
reconcile the eval counters against the host-side ``.evals`` accounting.

``TRACE_COUNTS`` increments only while a function is being traced --
tests use it to certify that repeated calls hit the compiled path.
"""
from __future__ import annotations

import collections
import functools
import inspect

import jax
import jax.numpy as jnp

from repro.ft import guards as _g
from repro.kernels import tuning as _tuning
from repro.kernels.kde_rowsum import kernel as _rk
from repro.kernels.kde_rowsum.ops import _PAD_OFFSET, _pad_rows
from repro.kernels.kde_sampler import kernel as _k
from repro.kernels.kde_sampler import ref as _ref
from repro.obs import counters as _c
from repro.obs import metrics as _m

TRACE_COUNTS = collections.Counter()


def _l1_cols(level1, exact, num_blocks, s, n, num_far, hstate):
    """(cols, far, overflow) realized PER FRONTIER ROW by one level-1
    read -- the static shape products the counter words are built from,
    mirroring the host accounting in ``core.sampling.edge`` exactly:
    hashed reads sweep ``max_bucket + overflow_cap`` exact columns plus
    ``B * num_far`` stratified FAR slots (``ref.frontier_gather``),
    blocked reads sweep ``n`` (exact) or ``B * s`` (stratified)."""
    if level1 == "hash":
        mb = int(hstate.members.shape[1])
        ov = (int(hstate.overflow.shape[0])
              if hstate.overflow is not None else 0)
        far = int(num_blocks) * int(num_far)
        return mb + ov + far, far, ov
    return (int(n) if exact else int(num_blocks) * int(s)), 0, 0

# Static (hashable) configuration forwarded to every jitted entry point.
# ``level1`` selects the frontier read: "blocked" (the §2 depth-2 block
# structure) or "hash" (the kde_hash padded-bucket estimator, whose
# ``HashState`` arrays ride along as the ``hstate`` operand pytree and
# whose FAR budget is the ``num_far`` static -- DESIGN.md §10).
# ``precision`` selects the level-1 eval dtype policy (DESIGN.md §14):
# "f32" (default, bitwise-stable) or "bf16" (rounded operand tiles, f32
# accumulators/CDFs; level-2 rows and pairwise corrections stay f32).
_STATIC = frozenset((
    "kind", "inv_bw", "beta", "pairwise", "block_size", "num_blocks",
    "n", "s", "exact", "use_pallas", "interpret", "bm", "rounds", "slack",
    "batch", "record_path", "iters", "num_samples", "level1", "num_far",
    "precision"))


def _jit(fn):
    """jit with the subset of _STATIC names this function actually takes."""
    names = tuple(p for p in inspect.signature(fn).parameters if p in _STATIC)
    return jax.jit(fn, static_argnames=names)


# --------------------------------------------------------------------- #
# level-1: (m, B) block-sum reads
# --------------------------------------------------------------------- #
@_jit
@_m.scope("level1")
def stratified_block_sums(y, x, x_sq, key, *, kind, inv_bw, beta, pairwise,
                          block_size, num_blocks, n, s, precision="f32"):
    """Per-block uniform-subsample estimates of the block sums, (m, B).

    Each block contributes ``size_b / s_b * sum(sampled kernel values)``
    where ``s_b = min(s, size_b)`` counts only *real* (non-padded) samples:
    the tail block is no longer inflated by duplicated pad indices.  The
    subsample *draw* is precision-independent; only the gathered kernel
    evals honor ``precision``.  Returns ``(block sums, counter word)``.
    """
    TRACE_COUNTS["stratified_block_sums"] += 1
    m = y.shape[0]

    def _word(bs):
        return _c.word(status=_g.nonfinite_status(bs),
                       evals=m * num_blocks * s, l1_reads=m)

    base = jnp.arange(num_blocks, dtype=jnp.int32) * block_size
    u = jax.random.uniform(key, (num_blocks, block_size))
    if n == num_blocks * block_size:
        # tail-free fast path (static shape property): every slot is valid,
        # so the pad masking/clamping passes are skipped entirely.  The
        # subsample draw consumes the identical randomness, so estimates
        # match the general path bit-for-bit.
        _, order = jax.lax.top_k(-u, s)           # (B, s) w/o replacement
        flat = (base[:, None] + order).reshape(-1)
        kv = _ref.kv_matrix(y, x[flat], x_sq[flat], kind, inv_bw, beta,
                            pairwise, precision=precision)
        bs = kv.reshape(m, num_blocks, s).sum(-1) * (block_size / float(s))
        return bs, _word(bs)
    pos = base[:, None] + jnp.arange(block_size, dtype=jnp.int32)[None, :]
    valid_pos = pos < n
    u = jnp.where(valid_pos, u, jnp.inf)          # invalid slots sort last
    _, order = jax.lax.top_k(-u, s)               # (B, s) w/o replacement
    idx = jnp.take_along_axis(pos, order, axis=1)
    sel_valid = jnp.take_along_axis(valid_pos, order, axis=1)
    idx = jnp.minimum(idx, n - 1)
    flat = idx.reshape(-1)
    kv = _ref.kv_matrix(y, x[flat], x_sq[flat], kind, inv_bw, beta, pairwise,
                        precision=precision)
    kv = kv.reshape(m, num_blocks, s) * sel_valid[None]
    sizes = jnp.minimum(n - base, block_size).astype(jnp.float32)
    s_b = jnp.minimum(sizes, float(s))
    bs = kv.sum(-1) * (sizes / jnp.maximum(s_b, 1.0))[None, :]
    return bs, _word(bs)


@_jit
@_m.scope("level1")
def exact_block_sums(y, x, x_sq, *, kind, inv_bw, beta, pairwise,
                     block_size, num_blocks, n, precision="f32"):
    """Exact (m, B) block sums: one dense vectorized sweep, zero host loops.
    The bf16 policy swaps in the blocked column-tile scan (f32 accumulator,
    O(m * tile) peak memory) instead of materializing the (m, n) matrix.
    Returns ``(block sums, counter word)``."""
    TRACE_COUNTS["exact_block_sums"] += 1
    m = y.shape[0]

    def _word(bs):
        return _c.word(status=_g.nonfinite_status(bs), evals=m * n,
                       l1_reads=m)

    if precision == "bf16":
        _ref.check_precision(precision, kind, pairwise)
        bs = _ref.kv_block_sums_bf16(y, x, kind, inv_bw, beta,
                                     bn=block_size)
        return bs, _word(bs)
    kv = _ref.kv_matrix(y, x, x_sq, kind, inv_bw, beta, pairwise)
    pad = num_blocks * block_size - n
    if pad:
        kv = jnp.pad(kv, ((0, 0), (0, pad)))
    bs = kv.reshape(m, num_blocks, block_size).sum(-1)
    return bs, _word(bs)


@_m.scope("level1")
def _pallas_pad(x, src, bm, block_size):
    """Shared Pallas preamble: query rows padded to a bm multiple, own-block
    indices padded with the -1 sentinel, dataset padded to a block_size
    multiple at the far offset (kernel values ~0)."""
    rem = (-src.shape[0]) % bm
    q = _pad_rows(x[src], bm, 0.0)
    own = jnp.pad((src // block_size).astype(jnp.int32), (0, rem),
                  constant_values=-1)[:, None]
    xp = _pad_rows(x, block_size, _PAD_OFFSET)
    return q, own, xp, rem


@_m.scope("level1")
def _sample_block_rows(x, src, gumbel, *, kind, inv_bw, beta, block_size,
                       interpret, bm, precision):
    """One fused Pallas level-1 pass (block sums + in-pass Gumbel-max
    draw) over the frontier rows ``src (m,)`` with their ``gumbel (m, B)``
    noise: the rows fill the pass's ``bm``-row query tiles in order.
    Returns (blk, p_blk, block sums) of the m real rows."""
    m = src.shape[0]
    q, own, xp, rem = _pallas_pad(x, src, bm, block_size)
    blk, pb, _, bs = _k.sample_block_pallas(
        q, xp, own, jnp.pad(gumbel, ((0, rem), (0, 0))), kind, inv_bw, beta,
        bm=bm, bn=block_size, interpret=interpret, precision=precision)
    return blk[:m], pb[:m], bs[:m]


@_m.scope("level1")
def _masked_rows(x, src, *, kind, inv_bw, beta, block_size, interpret, bm,
                 precision):
    """One Pallas masked-blocksum pass (no Gumbel state) over the frontier
    rows ``src (m,)``: their (m, B) own-block corrected, floored sums."""
    m = src.shape[0]
    q, own, xp, _ = _pallas_pad(x, src, bm, block_size)
    return _k.masked_blocksum_pallas(q, xp, own, kind, inv_bw, beta, bm=bm,
                                     bn=block_size, interpret=interpret,
                                     precision=precision)[:m]


@_m.scope("level1")
def _masked_block_sums(x, x_sq, src, key, *, kind, inv_bw, beta, pairwise,
                       block_size, num_blocks, n, s, exact, precision="f32"):
    """Level-1 sums for a frontier of dataset indices, own-block corrected
    (k(x, x) = 1 subtracted) and floored -- the cacheable object."""
    q = x[src]
    # inner counter words are discarded: the public program boundary
    # (masked_block_sums / fused_sample / ...) rebuilds the counts from
    # the same static shapes, so nothing is double-counted
    if exact:
        bs, _ = exact_block_sums(q, x, x_sq, kind=kind, inv_bw=inv_bw,
                                 beta=beta, pairwise=pairwise,
                                 block_size=block_size,
                                 num_blocks=num_blocks, n=n,
                                 precision=precision)
    else:
        bs, _ = stratified_block_sums(q, x, x_sq, key, kind=kind,
                                      inv_bw=inv_bw, beta=beta,
                                      pairwise=pairwise,
                                      block_size=block_size,
                                      num_blocks=num_blocks, n=n, s=s,
                                      precision=precision)
    own = (src // block_size).astype(jnp.int32)
    corr = jnp.arange(num_blocks, dtype=jnp.int32)[None, :] == own[:, None]
    bs = jnp.where(corr, bs - 1.0, bs)
    return jnp.maximum(bs, _ref.BLOCK_SUM_FLOOR)


@_jit
def masked_block_sums(x, x_sq, src, key, hstate=None, *, kind, inv_bw, beta,
                      pairwise, block_size, num_blocks, n, s, exact,
                      use_pallas=False, interpret=False, bm=128,
                      level1="blocked", num_far=64, precision="f32"):
    """Level-1 frontier read; dispatches to the Pallas masked-blocksum
    kernel (no Gumbel state) on the exact+Pallas path, or to the hashed
    read when ``level1="hash"``.  Returns ``(block sums, counter word)``."""
    TRACE_COUNTS["masked_block_sums"] += 1
    bs, st = _masked_sums_any(x, x_sq, src, key, hstate, kind=kind,
                              inv_bw=inv_bw, beta=beta, pairwise=pairwise,
                              block_size=block_size, num_blocks=num_blocks,
                              n=n, s=s, exact=exact, use_pallas=use_pallas,
                              interpret=interpret, bm=bm, level1=level1,
                              num_far=num_far, precision=precision)
    w = src.shape[0]
    cols, far, ov = _l1_cols(level1, exact, num_blocks, s, n, num_far,
                             hstate)
    return bs, _c.word(status=st, evals=w * cols, l1_reads=w,
                       far_samples=w * far, overflow=w * ov)


# --------------------------------------------------------------------- #
# level-2: exact in-block rows
# --------------------------------------------------------------------- #
def _block_views(x, x_sq, block_size):
    """See ``ref.block_views`` -- shared with the oracles."""
    return _ref.block_views(x, x_sq, block_size)


@_m.scope("level2")
def _level2_kv(x, x_sq, views, src, blk, *, kind, inv_bw, beta, pairwise,
               block_size, n):
    """See ``ref.level2_row`` -- shared with the oracles."""
    return _ref.level2_row(x, x_sq, views, src, blk, kind, inv_bw, beta,
                           block_size, n, pairwise)


_level2_draw = _m.scope("level2")(_ref.level2_draw)


_choose_block = _ref.choose_block


@_m.scope("level2")
def _sample_core(x, x_sq, views, src, bs, key, *, kind, inv_bw, beta,
                 pairwise, block_size, n):
    """(block draw -> level-2 row -> neighbor draw) from given level-1 sums.
    Delegates to ``ref.sample_from_sums`` so every fused program and its
    oracle consume the identical key stream and math."""
    return _ref.sample_from_sums(x, x_sq, views, src, bs, key, kind, inv_bw,
                                 beta, block_size, n, pairwise)


@_m.scope("level2")
def _walk_sample_core(x, x_sq, views, src, bs, key, *, kind, inv_bw, beta,
                      pairwise, block_size, n, num_blocks):
    """``sample_from_sums`` with the two-level inverse-CDF draws
    (``ref.grouped_inverse_cdf``) at both depths -- the walk-resident-cache
    step's hot path, where the flat (w, B) and (w, bs) cumsums were the
    dominant n-scaling cost.  Same key-split discipline and sampling law
    as ``_sample_core``; the realized index can differ from the flat
    search only by fp regrouping of the partial sums."""
    k_blk, k_in = jax.random.split(key)
    blk, pb = _ref.choose_block_grouped(bs, k_blk, _ref.cdf_group(num_blocks))
    kv, live, cols_c = _ref.level2_row(x, x_sq, views, src, blk, kind,
                                       inv_bw, beta, block_size, n, pairwise)
    nb, pin = _ref.level2_draw_grouped(kv, live, cols_c,
                                       jax.random.uniform(k_in,
                                                          (src.shape[0],)),
                                       _ref.cdf_group(block_size))
    return nb, pb * pin


def _pallas_noise(k_rest, w, num_blocks):
    """The fused Pallas draw's randomness for a w-frontier, from the second
    half of its key split: the (w, B) Gumbel noise of the in-pass block
    draw and the (w,) uniforms of the level-2 draw."""
    k_g, k_in = jax.random.split(k_rest)
    with jax.named_scope("level1"):
        gumbel = jax.random.gumbel(k_g, (w, num_blocks))
    return gumbel, jax.random.uniform(k_in, (w,))


def _pallas_level2(x, x_sq, views, src, blk, pb, bs, u, *, kind, inv_bw,
                   beta, pairwise, block_size, n):
    """The level-2 half of the fused Pallas draw: the drawn block's exact
    row, the in-block draw with uniforms ``u``, the realized probability
    and the status of the read and the draw.  Returns (nb, prob, status)."""
    kv, live, cols_c = _level2_kv(x, x_sq, views, src, blk, kind=kind,
                                  inv_bw=inv_bw, beta=beta, pairwise=pairwise,
                                  block_size=block_size, n=n)
    nb, pin = _level2_draw(kv, live, cols_c, u)
    prob = pb * pin
    st = _g.merge(_g.sums_status(bs, _ref.BLOCK_SUM_FLOOR),
                  _g.result_status(prob))
    return nb, prob, st


def _fused_sample(x, x_sq, src, key, hstate=None, *, kind, inv_bw, beta,
                  pairwise, block_size, num_blocks, n, s, exact, use_pallas,
                  interpret, bm, level1="blocked", num_far=64,
                  precision="f32", views=None):
    if views is None:
        views = _block_views(x, x_sq, block_size)
    w = src.shape[0]
    k_l1, k_rest = jax.random.split(key)
    if level1 == "hash":
        bs, st = _masked_sums_any(x, x_sq, src, k_l1, hstate=hstate,
                                  kind=kind, inv_bw=inv_bw, beta=beta,
                                  pairwise=pairwise, block_size=block_size,
                                  num_blocks=num_blocks, n=n, s=s,
                                  exact=exact, use_pallas=use_pallas,
                                  interpret=interpret, bm=bm, level1=level1,
                                  num_far=num_far, precision=precision)
        nb, prob = _sample_core(x, x_sq, views, src, bs, k_rest, kind=kind,
                                inv_bw=inv_bw, beta=beta, pairwise=pairwise,
                                block_size=block_size, n=n)
        st = _g.merge(st, _g.result_status(prob))
    elif exact and use_pallas:
        # Fully fused level-1: block sums + Gumbel-max draw in one Pallas pass.
        gumbel, u = _pallas_noise(k_rest, w, num_blocks)
        blk, pb, bs = _sample_block_rows(
            x, src, gumbel, kind=kind, inv_bw=inv_bw, beta=beta,
            block_size=block_size, interpret=interpret, bm=bm,
            precision=precision)
        nb, prob, st = _pallas_level2(x, x_sq, views, src, blk, pb, bs, u,
                                      kind=kind, inv_bw=inv_bw, beta=beta,
                                      pairwise=pairwise,
                                      block_size=block_size, n=n)
    else:
        bs = _masked_block_sums(x, x_sq, src, k_l1, kind=kind, inv_bw=inv_bw,
                                beta=beta, pairwise=pairwise,
                                block_size=block_size, num_blocks=num_blocks,
                                n=n, s=s, exact=exact, precision=precision)
        nb, prob = _sample_core(x, x_sq, views, src, bs, k_rest, kind=kind,
                                inv_bw=inv_bw, beta=beta, pairwise=pairwise,
                                block_size=block_size, n=n)
        st = _g.merge(_g.sums_status(bs, _ref.BLOCK_SUM_FLOOR),
                      _g.result_status(prob))
    # one level-1 read of the w-frontier + w exact level-2 rows -- the
    # host accounting in NeighborSampler.sample, verbatim
    cols, far, ov = _l1_cols(level1, exact, num_blocks, s, n, num_far,
                             hstate)
    cw = _c.word(status=st, evals=w * (cols + block_size), l1_reads=w,
                 draws=w, far_samples=w * far, overflow=w * ov)
    return nb, prob, bs, cw


@_jit
def fused_sample(x, x_sq, src, key, hstate=None, *, kind, inv_bw, beta,
                 pairwise, block_size, num_blocks, n, s, exact, use_pallas,
                 interpret, bm, level1="blocked", num_far=64,
                 precision="f32"):
    """One depth-2 sampling step: (neighbors, realized probs, level-1 sums,
    counter word)."""
    TRACE_COUNTS["fused_sample"] += 1
    return _fused_sample(x, x_sq, src, key, hstate, kind=kind, inv_bw=inv_bw,
                         beta=beta, pairwise=pairwise, block_size=block_size,
                         num_blocks=num_blocks, n=n, s=s, exact=exact,
                         use_pallas=use_pallas, interpret=interpret, bm=bm,
                         level1=level1, num_far=num_far, precision=precision)


@_jit
def sample_from_block_sums(x, x_sq, src, bs, key, *, kind, inv_bw, beta,
                           pairwise, block_size, n):
    """Depth-2 step reusing cached level-1 sums (no dataset re-sweep).
    Returns (neighbors, realized probs, counter word)."""
    TRACE_COUNTS["sample_from_block_sums"] += 1
    views = _block_views(x, x_sq, block_size)
    nb, prob = _sample_core(x, x_sq, views, src, bs, key, kind=kind,
                            inv_bw=inv_bw, beta=beta, pairwise=pairwise,
                            block_size=block_size, n=n)
    st = _g.merge(_g.sums_status(bs, _ref.BLOCK_SUM_FLOOR),
                  _g.result_status(prob))
    w = src.shape[0]
    return nb, prob, _c.word(status=st, evals=w * block_size, draws=w)


@_m.scope("level2")
def _prob_core(x, x_sq, views, src, dst, bs, *, kind, inv_bw, beta, pairwise,
               block_size, n):
    """q(dst | src) from given level-1 sums of the src frontier.  Mirrors
    ``ref.level2_draw``'s zero-row guard: if dst's block row underflows to
    all zeros the sampler draws uniformly over the live columns, so the
    probability reported here is the matching 1/|live| -- not 0."""
    blk = (dst // block_size).astype(jnp.int32)
    pb = jnp.take_along_axis(bs, blk[:, None], axis=1)[:, 0] / bs.sum(axis=1)
    kv, live, _ = _level2_kv(x, x_sq, views, src, blk, kind=kind,
                             inv_bw=inv_bw, beta=beta, pairwise=pairwise,
                             block_size=block_size, n=n)
    col = (dst - blk * block_size)[:, None]
    kd = jnp.take_along_axis(kv, col, axis=1)[:, 0]
    rowsum = kv.sum(axis=1)
    live_d = jnp.take_along_axis(live, col, axis=1)[:, 0]
    pin_fallback = live_d / jnp.maximum(live.sum(axis=1), 1.0)
    pin = jnp.where(rowsum > 0.0, kd / jnp.maximum(rowsum, 1e-30),
                    pin_fallback)
    return pb * pin


@_jit
def prob_of_from_block_sums(x, x_sq, src, dst, bs, *, kind, inv_bw, beta,
                            pairwise, block_size, n):
    """q(dst | src) the sampler assigns, from cached level-1 sums.
    Returns ``(probs, counter word)``."""
    TRACE_COUNTS["prob_of_from_block_sums"] += 1
    views = _block_views(x, x_sq, block_size)
    prob = _prob_core(x, x_sq, views, src, dst, bs, kind=kind, inv_bw=inv_bw,
                      beta=beta, pairwise=pairwise, block_size=block_size,
                      n=n)
    st = _g.merge(_g.sums_status(bs, _ref.BLOCK_SUM_FLOOR),
                  _g.result_status(prob))
    return prob, _c.word(status=st, evals=src.shape[0] * block_size)


# --------------------------------------------------------------------- #
# fused Algorithm 5.1 edge batches + batched LRA sketch rows
# --------------------------------------------------------------------- #
@_m.scope("level1")
def _masked_sums_any(x, x_sq, src, key, hstate=None, *, kind, inv_bw, beta,
                     pairwise, block_size, num_blocks, n, s, exact,
                     use_pallas, interpret, bm, level1="blocked", num_far=64,
                     precision="f32"):
    """Masked level-1 sums for a frontier, dispatching to the Pallas
    masked-blocksum kernel on the exact+Pallas path (no Gumbel state --
    probability evaluation needs sums only), or to the hashed-KDE read
    (``level1="hash"``: O(max_bucket + num_far) evals per row instead of
    the blocked O(B s) / O(n), DESIGN.md §10).  Returns ``(bs, status)``;
    on the blocked paths the status covers NaN/Inf and zero-mass rows."""
    if level1 == "hash":
        from repro.kernels.kde_hash import ops as _hops
        return _hops._hashed_block_sums(
            x, src, hstate, key, kind=kind, inv_bw=inv_bw, beta=beta,
            pairwise=pairwise, num_far=num_far, block_size=block_size,
            num_blocks=num_blocks, n=n, use_pallas=use_pallas,
            interpret=interpret, bm=bm, precision=precision)
    if exact and use_pallas:
        bs = _masked_rows(x, src, kind=kind, inv_bw=inv_bw, beta=beta,
                          block_size=block_size, interpret=interpret, bm=bm,
                          precision=precision)
        return bs, _g.sums_status(bs, _ref.BLOCK_SUM_FLOOR)
    bs = _masked_block_sums(x, x_sq, src, key, kind=kind, inv_bw=inv_bw,
                            beta=beta, pairwise=pairwise,
                            block_size=block_size, num_blocks=num_blocks,
                            n=n, s=s, exact=exact, precision=precision)
    return bs, _g.sums_status(bs, _ref.BLOCK_SUM_FLOOR)


def _edge_batch_core(x, x_sq, views, cdf, degs, inv_total, inv_t, key,
                     hstate=None, *, batch, kind, inv_bw, beta, pairwise,
                     block_size, num_blocks, n, s, exact, use_pallas,
                     interpret, bm, level1="blocked", num_far=64,
                     precision="f32"):
    """One Algorithm 5.1 edge batch, steps (a)-(d), as straight-line device
    code: u ~ degrees (inverse CDF over the device prefix array), v | u by
    the depth-2 engine, the reverse probability, and the importance weight
    ``k(u,v) / (t (p_u q_uv + p_v q_vu))``.

    The reverse probability collapses algebraically (DESIGN.md §6): the
    depth-2 factorization gives q(u | v) = S_v(blk_u)/deg(v) *
    k(v,u)/S_v(blk_u) = k(u,v)/deg(v), so no level-1 read of the v
    frontier is needed -- ``degs`` is the degree array the vertex sampler
    already preprocessed, and p_v * q_vu further reduces to
    k(u,v)/sum(deg).  The forward q_uv stays the *realized* sampling
    probability (from the same level-1 sums that drew v)."""
    k_u, k_fwd = jax.random.split(key)
    u = _ref.inverse_cdf_index(cdf, jax.random.uniform(k_u, (batch,)))
    v, q_uv, _, cw = _fused_sample(x, x_sq, u, k_fwd, hstate, kind=kind,
                                   inv_bw=inv_bw, beta=beta,
                                   pairwise=pairwise, block_size=block_size,
                                   num_blocks=num_blocks, n=n, s=s,
                                   exact=exact, use_pallas=use_pallas,
                                   interpret=interpret, bm=bm, level1=level1,
                                   num_far=num_far, precision=precision,
                                   views=views)
    kuv = _ref.kv_pairs(x[u], x[v], kind, inv_bw, beta, pairwise)
    q_vu = kuv / jnp.maximum(degs[v], _ref.BLOCK_SUM_FLOOR)
    # q_e = p_u q_uv + p_v q_vu with p_i = deg_i / sum(deg); the second
    # term telescopes to k(u,v) / sum(deg).
    q_edge = inv_total * (degs[u] * q_uv + kuv)
    wgt = kuv * inv_t / jnp.maximum(q_edge, 1e-30)
    # fused_sample's word + the batch aligned k(u,v) pairs + the batch
    # inverse-CDF u draws (host accounting: level1 + batch*bs + batch)
    cw = _c.fold(cw, _c.word(status=_g.result_status(wgt, q_vu),
                             evals=batch, draws=batch))
    return u, v, wgt, q_uv, q_vu, cw


@_jit
@_m.scope("edge_scan")
def fused_edge_batch(x, x_sq, cdf, degs, inv_total, inv_t, key, hstate=None,
                     *, batch, kind, inv_bw, beta, pairwise, block_size,
                     num_blocks, n, s, exact, use_pallas, interpret, bm,
                     level1="blocked", num_far=64, precision="f32"):
    """One fused Algorithm 5.1 edge batch: (u, v, weight, q_uv, q_vu,
    counter word)."""
    TRACE_COUNTS["fused_edge_batch"] += 1
    views = _block_views(x, x_sq, block_size)
    return _edge_batch_core(x, x_sq, views, cdf, degs, inv_total, inv_t, key,
                            hstate, batch=batch, kind=kind, inv_bw=inv_bw,
                            beta=beta, pairwise=pairwise,
                            block_size=block_size, num_blocks=num_blocks,
                            n=n, s=s, exact=exact, use_pallas=use_pallas,
                            interpret=interpret, bm=bm, level1=level1,
                            num_far=num_far, precision=precision)


@_jit
@_m.scope("edge_scan")
def edge_batch_scan(x, x_sq, cdf, degs, inv_total, inv_t, keys, hstate=None,
                    *, batch, kind, inv_bw, beta, pairwise, block_size,
                    num_blocks, n, s, exact, use_pallas, interpret, bm,
                    level1="blocked", num_far=64, precision="f32"):
    """All T = len(keys) edge batches of the sparsifier in ONE program: a
    ``lax.scan`` over per-batch keys whose body is one fused edge batch.
    The whole Algorithm 5.1 sampling loop runs with a single dispatch and
    a single device->host transfer of the (T, batch) edge lists.  The
    per-batch counter words are folded (status ors, counters add) through
    the scan carry -- the last output is the run's merged word."""
    TRACE_COUNTS["edge_batch_scan"] += 1
    views = _block_views(x, x_sq, block_size)

    def body(cw, k):
        u, v, wgt, q_uv, q_vu, cw_b = _edge_batch_core(
            x, x_sq, views, cdf, degs, inv_total, inv_t, k, hstate,
            batch=batch, kind=kind, inv_bw=inv_bw, beta=beta,
            pairwise=pairwise, block_size=block_size, num_blocks=num_blocks,
            n=n, s=s, exact=exact, use_pallas=use_pallas,
            interpret=interpret, bm=bm, level1=level1, num_far=num_far,
            precision=precision)
        return _c.fold(cw, cw_b), (u, v, wgt, q_uv, q_vu)

    word, out = jax.lax.scan(body, _c.word(), keys)
    return out + (word,)


@_jit
def kernel_rows(q, x, x_sq, *, kind, inv_bw, beta, pairwise,
                precision="f32"):
    """Exact (m, n) kernel rows in one program -- the FKV sketch rows and
    the CP17 column reads of Section 5.2, replacing the host chunk loop
    over ``kernel.pairwise``.  Returns ``(rows, counter word)``."""
    TRACE_COUNTS["kernel_rows"] += 1
    kv = _ref.kv_matrix(q, x, x_sq, kind, inv_bw, beta, pairwise,
                        precision=precision)
    return kv, _c.word(status=_g.nonfinite_status(kv),
                       evals=q.shape[0] * x.shape[0])


def _sample_exact_core(x, x_sq, views, src, bs, key, *, kind, inv_bw, beta,
                       pairwise, block_size, n, rounds, slack):
    zs = bs.sum(axis=1)
    keys = jax.random.split(key, 2 * rounds + 1)
    cur, _ = _sample_core(x, x_sq, views, src, bs, keys[0], kind=kind,
                          inv_bw=inv_bw, beta=beta, pairwise=pairwise,
                          block_size=block_size, n=n)
    accepted = jnp.zeros(src.shape[0], bool)
    xs = x[src]
    for r in range(rounds):
        cand, q = _sample_core(x, x_sq, views, src, bs, keys[2 * r + 1],
                               kind=kind, inv_bw=inv_bw, beta=beta,
                               pairwise=pairwise, block_size=block_size, n=n)
        kuv = _ref.kv_pairs(xs, x[cand], kind, inv_bw, beta, pairwise)
        ratio = kuv / jnp.maximum(slack * q * zs, 1e-30)
        u = jax.random.uniform(keys[2 * r + 2], (src.shape[0],))
        acc = (~accepted) & (u < jnp.minimum(ratio, 1.0))
        cur = jnp.where(acc, cand, cur)
        accepted |= acc
    fallbacks = jnp.sum(~accepted).astype(jnp.int32)
    st = _g.flag_if(fallbacks > 0, _g.REJECT_EXHAUSTED)
    return cur, st, fallbacks


@_jit
def fused_sample_exact(x, x_sq, src, bs, key, *, kind, inv_bw, beta, pairwise,
                       block_size, n, rounds, slack):
    """Theorem 4.12 rejection rounds in one program.  The cached level-1
    sums ``bs`` are shared across every proposal round AND the degree
    estimate -- the seed re-swept the dataset once per round.  Returns
    (neighbors, counter word, fallback count): draws whose rounds all
    rejected keep the round-0 proposal (biased) and are counted in the
    word's RETRIES slot, not hidden."""
    TRACE_COUNTS["fused_sample_exact"] += 1
    views = _block_views(x, x_sq, block_size)
    cur, st, fallbacks = _sample_exact_core(
        x, x_sq, views, src, bs, key, kind=kind, inv_bw=inv_bw, beta=beta,
        pairwise=pairwise, block_size=block_size, n=n, rounds=rounds,
        slack=slack)
    st = _g.merge(st, _g.sums_status(bs, _ref.BLOCK_SUM_FLOOR))
    w = src.shape[0]
    # (rounds + 1) level-2 rows + rounds aligned accept pairs -- the host
    # accounting in NeighborSampler.sample_exact, verbatim
    cw = _c.word(status=st,
                 evals=(rounds + 1) * w * block_size + rounds * w,
                 draws=(rounds + 1) * w, retries=fallbacks)
    return cur, cw, fallbacks


# fold_in constant deriving a walk program's cache key from its first
# step key (any fixed value works; it only has to be distinct from the
# per-step split stream).
_WALK_CACHE_FOLD = 97


def walk_cache_samples(num_blocks: int, s: int) -> int:
    """Per-block subsample width ``s_eff`` of the walk-resident cache --
    exposed so eval accounting (``core.sampling.edge``) and the benchmarks
    report the true per-step level-1 cost."""
    return _tuning.walk_samples_per_block(num_blocks, s)


def walk_layout(n: int, block_size: int, num_blocks: int, s: int):
    """(stratum width, stratum count, per-stratum cache width) of the
    walk-resident layout (``tuning.walk_block_size``): the walk step's own
    block granularity, decoupled from the sampler's query layout so the
    exact level-2 read stays narrow as n grows.  Shared by ``walk_scan``
    and the eval accounting in ``core.sampling.edge``.

    When the sampler's own layout already fits the cache budget
    (``num_blocks * s <= WALK_CACHE_COLS``) it is returned unchanged, so
    small problems keep the query layout -- and the per-step eval count
    stays EXACTLY the mesh engine's ``B * s + block_size`` (the sharded
    walk has no resident cache; counter parity across backends is a
    pinned contract)."""
    if num_blocks * s <= _tuning.WALK_CACHE_COLS:
        return block_size, num_blocks, s
    wbs = _tuning.walk_block_size(n, block_size)
    w_blocks = -(-int(n) // wbs)
    return wbs, w_blocks, _tuning.walk_samples_per_block(w_blocks, s)


@_m.scope("level1")
def _walk_level1_cache(x, x_sq, key, *, block_size, num_blocks, n, s):
    """Walk-resident compact level-1 subsample (DESIGN.md §14).

    ONE stratified per-block draw per walk program -- ``s_eff =
    walk_cache_samples(B, s)`` columns per block, total capped at
    ~``tuning.WALK_CACHE_COLS`` columns -- gathered into a compact
    (B * s_eff, d) array that every step's level-1 read sweeps instead of
    re-gathering a fresh O(B s) subsample from the full dataset.  This is
    the n=65536 walk-cliff fix: the per-step level-1 cost becomes
    O(w * WALK_CACHE_COLS), independent of n, and the gather touches a
    dataset-sized array once per *program* instead of once per *step*.
    The cache key is ``fold_in(keys[0], const)`` so the draw is a pure
    function of the walk's key stream (vmap-safe for the serving lanes;
    re-running with the same keys reuses the identical subsample).
    Returns ``(xs, xs_sq, sel, scale)``; ``sel`` is None on the tail-free
    layout.  The cache is laid out SAMPLE-major -- column ``j`` holds
    sample ``j // B`` of block ``j % B`` -- so the per-step reduction is
    ``reshape(w, s_eff, B).sum(1)``: a middle-axis sum with the B blocks
    contiguous in the minor axis, which vectorizes ~2x better than the
    narrow trailing ``(w, B, s_eff).sum(-1)`` when ``s_eff`` is small."""
    ck = jax.random.fold_in(key, _WALK_CACHE_FOLD)
    base = jnp.arange(num_blocks, dtype=jnp.int32) * block_size
    u = jax.random.uniform(ck, (num_blocks, block_size))
    if n == num_blocks * block_size:
        _, order = jax.lax.top_k(-u, s)           # (B, s_eff) w/o repl.
        flat = (base[:, None] + order).T.reshape(-1)
        sel = None
        scale = jnp.full((num_blocks,), block_size / float(s), jnp.float32)
    else:
        pos = base[:, None] + jnp.arange(block_size, dtype=jnp.int32)[None, :]
        valid_pos = pos < n
        u = jnp.where(valid_pos, u, jnp.inf)
        _, order = jax.lax.top_k(-u, s)
        idx = jnp.take_along_axis(pos, order, axis=1)
        sel = jnp.take_along_axis(valid_pos, order, axis=1).T.reshape(-1)
        flat = jnp.minimum(idx, n - 1).T.reshape(-1)
        sizes = jnp.minimum(n - base, block_size).astype(jnp.float32)
        s_b = jnp.minimum(sizes, float(s))
        scale = sizes / jnp.maximum(s_b, 1.0)
    return x[flat], x_sq[flat], sel, scale


@_m.scope("level1")
def _cached_block_sums(cache, x, src, *, kind, inv_bw, beta, pairwise,
                       block_size, num_blocks, s, precision):
    """Masked level-1 read against the walk-resident cache: one compact
    (w, B * s_eff) kernel eval, per-block reduction and rescale, then the
    §2 own-block correction + floor (identical post-processing to
    ``_masked_block_sums``)."""
    xs, xs_sq, sel, scale = cache
    q = x[src]
    kv = _ref.kv_matrix(q, xs, xs_sq, kind, inv_bw, beta, pairwise,
                        precision=precision)
    if sel is not None:
        kv = kv * sel[None, :]
    bs = kv.reshape(q.shape[0], s, num_blocks).sum(1) * scale[None, :]
    own = (src // block_size).astype(jnp.int32)
    corr = jnp.arange(num_blocks, dtype=jnp.int32)[None, :] == own[:, None]
    bs = jnp.where(corr, bs - 1.0, bs)
    return jnp.maximum(bs, _ref.BLOCK_SUM_FLOOR)


@_jit
def walk_scan(x, x_sq, starts, keys, hstate=None, *, kind, inv_bw, beta,
              pairwise, block_size, num_blocks, n, s, exact, use_pallas,
              interpret, bm, rounds, slack, record_path=True,
              level1="blocked", num_far=64, precision="f32"):
    """T-step random walk entirely on device: the frontier is scan carry,
    each step is one fused depth-2 sample (or rejection-exact step when
    ``rounds > 0``).  Returns (endpoints, (T, w) path); with
    ``record_path=False`` the path is never materialized (the scan emits no
    per-step output, so long walks cost O(w) device memory, not O(T w))
    and None is returned in its place.  The key stream is identical either
    way, so endpoints match bitwise.  Returns (endpoints, path, counter
    word, rejection-fallback count) -- per-step words are fold-reduced
    (status ors, counters add) across the T steps inside the scan carry.

    On the stratified blocked path (``exact=False``, jnp level-1) the
    level-1 read runs against the walk-resident subsample cache built ONCE
    before the scan (see ``_walk_level1_cache``); every step still draws
    its own level-2 randomness from the per-step key stream."""
    TRACE_COUNTS["walk_scan"] += 1
    views = _block_views(x, x_sq, block_size)  # hoisted out of the step body
    cache = None
    wbs, w_blocks, s_eff = block_size, num_blocks, s
    if level1 == "blocked" and not exact and not use_pallas:
        # walk-resident layout: same ~WALK_CACHE_COLS cached level-1
        # columns spread over finer strata, so the exact level-2 read is
        # O(wbs) << O(block_size) at large n (tuning.walk_block_size)
        wbs, w_blocks, s_eff = walk_layout(n, block_size, num_blocks, s)
        cache = _walk_level1_cache(x, x_sq, keys[0], block_size=wbs,
                                   num_blocks=w_blocks, n=n, s=s_eff)
        views = _block_views(x, x_sq, wbs)

    w = starts.shape[0]
    cols, far, ov = _l1_cols(level1, exact, num_blocks, s, n, num_far,
                             hstate)

    def body(carry, k):
        cur, cw, fb = carry
        if rounds > 0:
            k_l1, k_rs = jax.random.split(k)
            if cache is not None:
                bs = _cached_block_sums(cache, x, cur, kind=kind,
                                        inv_bw=inv_bw, beta=beta,
                                        pairwise=pairwise,
                                        block_size=wbs,
                                        num_blocks=w_blocks, s=s_eff,
                                        precision=precision)
                st1 = _g.sums_status(bs, _ref.BLOCK_SUM_FLOOR)
                l1_evals, l1_far, l1_ov = w * w_blocks * s_eff, 0, 0
            else:
                bs, st1 = _masked_sums_any(x, x_sq, cur, k_l1, hstate,
                                           kind=kind, inv_bw=inv_bw,
                                           beta=beta, pairwise=pairwise,
                                           block_size=block_size,
                                           num_blocks=num_blocks, n=n, s=s,
                                           exact=exact,
                                           use_pallas=use_pallas,
                                           interpret=interpret, bm=bm,
                                           level1=level1, num_far=num_far,
                                           precision=precision)
                l1_evals, l1_far, l1_ov = w * cols, w * far, w * ov
            nxt, st2, fb_k = _sample_exact_core(
                x, x_sq, views, cur, bs, k_rs, kind=kind, inv_bw=inv_bw,
                beta=beta, pairwise=pairwise, block_size=wbs, n=n,
                rounds=rounds, slack=slack)
            cw_k = _c.word(
                status=st1 | st2,
                evals=l1_evals + (rounds + 1) * w * wbs + rounds * w,
                l1_reads=w, draws=(rounds + 1) * w, retries=fb_k,
                far_samples=l1_far, overflow=l1_ov)
            fb = fb + fb_k
        elif cache is not None:
            # mirrors _fused_sample's (k_l1, k_rest) discipline; k_l1 is
            # unused because the level-1 subsample is the walk-resident one
            _, k_rest = jax.random.split(k)
            bs = _cached_block_sums(cache, x, cur, kind=kind, inv_bw=inv_bw,
                                    beta=beta, pairwise=pairwise,
                                    block_size=wbs,
                                    num_blocks=w_blocks, s=s_eff,
                                    precision=precision)
            nxt, prob = _walk_sample_core(x, x_sq, views, cur, bs, k_rest,
                                          kind=kind, inv_bw=inv_bw,
                                          beta=beta, pairwise=pairwise,
                                          block_size=wbs, n=n,
                                          num_blocks=w_blocks)
            cw_k = _c.word(
                status=_g.merge(_g.sums_status(bs, _ref.BLOCK_SUM_FLOOR),
                                _g.result_status(prob)),
                evals=w * w_blocks * s_eff + w * wbs, l1_reads=w, draws=w)
        else:
            nxt, _, _, cw_k = _fused_sample(x, x_sq, cur, k, hstate,
                                           kind=kind, inv_bw=inv_bw,
                                           beta=beta, pairwise=pairwise,
                                           block_size=block_size,
                                           num_blocks=num_blocks, n=n, s=s,
                                           exact=exact, use_pallas=use_pallas,
                                           interpret=interpret, bm=bm,
                                           level1=level1, num_far=num_far,
                                           precision=precision, views=views)
        return (nxt, _c.fold(cw, cw_k), fb), (nxt if record_path else None)

    (end, word, fallbacks), path = jax.lax.scan(
        body, (starts, _c.word(), jnp.int32(0)), keys)
    return end, path, word, fallbacks


# --------------------------------------------------------------------- #
# fused application programs (DESIGN.md §7): eigen / Laplacian / local
# clustering / triangles run their inner loops as single programs too
# --------------------------------------------------------------------- #
@_jit
def noisy_power_scan(ksub, v0, keys, *, num_samples):
    """BIMW21 noisy power method (Algorithm 5.18 step 2) as ONE program:
    every iteration importance-samples ``num_samples`` indices j ~ |v_j|
    by inverse CDF, forms the unbiased matvec estimate
    ``sum_j sign(v_j) z / S * ksub[:, j]``, and renormalizes -- all under
    ``lax.scan`` with no host round-trips.  Returns (Rayleigh quotient
    from one exact final matvec, final unit vector, counter word --
    iterations whose sampled matvec collapsed or went non-finite are
    flagged, not silently skipped; the DRAWS slot counts the sampled
    matvec lookups into the precomputed ``ksub``, which are NOT fresh
    kernel evals).  Oracle: ``ref.noisy_power_ref`` (identical key
    stream, unrolled)."""
    TRACE_COUNTS["noisy_power_scan"] += 1
    t = ksub.shape[0]

    def body(carry, k):
        v, st = carry
        absv = jnp.abs(v)
        z = jnp.sum(absv)
        cdf = jnp.cumsum(absv)
        u = jax.random.uniform(k, (num_samples,)) * jnp.maximum(z, 1e-30)
        idx = jnp.clip(jnp.searchsorted(cdf, u, side="right"),
                       0, t - 1).astype(jnp.int32)
        contrib = jnp.sign(v[idx]) * z / num_samples
        w = ksub[:, idx] @ contrib
        nw = jnp.linalg.norm(w)
        ok = (nw > 0.0) & (z > 0.0)
        st = st | _g.flag_if(~ok, _g.ZERO_MASS) | _g.nonfinite_status(w)
        return (jnp.where(ok, w / jnp.maximum(nw, 1e-30), v), st), None

    (v, st), _ = jax.lax.scan(body, (v0, jnp.uint32(0)), keys)
    lam = v @ (ksub @ v)
    st = _g.merge(st, _g.result_status(lam, v))
    return lam, v, _c.word(status=st,
                           draws=keys.shape[0] * num_samples)


@_jit
def laplacian_matvec(src, dst, w, p, *, n):
    """L_{G'} p = D p - A p over a COO edge list as segment-sum scatters
    (no ``np.add.at``); one jitted program per (n, m) shape pair."""
    TRACE_COUNTS["laplacian_matvec"] += 1
    return _ref.laplacian_matvec_ref(src, dst, w, p, n)


@_jit
def laplacian_cg(src, dst, w, b, tol, *, n, iters):
    """Jacobi-preconditioned CG for ``L_{G'} x = b`` (b perp 1) as ONE
    ``lax.while_loop`` program: the segment-sum matvec, the dot products,
    and the convergence test all stay on device (Section 5.1.1's solve
    step -- the seed ran one host iteration per CG step).

    Float32-safe: the loop tracks the best iterate seen (CG in f32 stalls
    near machine precision instead of hitting ``tol``) and stops on
    stagnation -- non-positive curvature / preconditioned residual, a
    non-finite residual, or 32 consecutive iterations without improving
    the best residual (the f32 plateau; without this exit a sub-f32
    ``tol`` would burn the full ``iters`` budget after convergence).
    Returns (best iterate, projected to 1^perp, its residual norm, and a
    counter word flagging non-convergence / non-finite output; the DRAWS
    slot records the realized CG iteration count -- the one
    data-dependent cost of this program)."""
    TRACE_COUNTS["laplacian_cg"] += 1
    deg = jnp.zeros((n,), w.dtype).at[src].add(w).at[dst].add(w)
    dinv = 1.0 / jnp.maximum(deg, 1e-30)

    def proj(v):
        return v - jnp.mean(v)

    def matvec(p):
        av = jnp.zeros((n,), w.dtype).at[src].add(w * p[dst]).at[dst].add(
            w * p[src])
        return deg * p - av

    bb = proj(b)
    x0 = jnp.zeros((n,), w.dtype)
    r0 = bb
    z0 = proj(dinv * r0)
    rz0 = jnp.dot(r0, z0)
    bnorm = jnp.maximum(jnp.linalg.norm(bb), 1e-30)

    def cond(c):
        return (c[0] < iters) & (~c[-1])

    def body(c):
        i, x_, r_, p_, rz_, bx, br, stall, _ = c
        ap = matvec(p_)
        denom = jnp.dot(p_, ap)
        ok = (denom > 0.0) & (rz_ > 0.0)
        alpha = jnp.where(ok, rz_ / jnp.maximum(denom, 1e-30), 0.0)
        x2 = x_ + alpha * p_
        r2 = r_ - alpha * ap
        rn = jnp.linalg.norm(r2)
        better = ok & (rn < br)
        bx2 = jnp.where(better, x2, bx)
        br2 = jnp.where(better, rn, br)
        stall2 = jnp.where(better, 0, stall + 1)
        z2 = proj(dinv * r2)
        rz2 = jnp.dot(r2, z2)
        p2 = z2 + jnp.where(ok, rz2 / jnp.maximum(rz_, 1e-30), 0.0) * p_
        stop = (~ok) | (rn < tol * bnorm) | (~jnp.isfinite(rn)) \
            | (rz2 <= 0.0) | (stall2 >= 32)
        return i + 1, x2, r2, p2, rz2, bx2, br2, stall2, stop

    init = (0, x0, r0, z0, rz0, x0, jnp.linalg.norm(r0), 0, False)
    out = jax.lax.while_loop(cond, body, init)
    sol, res = proj(out[5]), out[6]
    st = _g.merge(_g.flag_if(res >= tol * bnorm, _g.CG_NO_CONVERGE),
                  _g.result_status(sol, res))
    return sol, res, _c.word(status=st, draws=out[0])


@_jit
def signed_endpoint_stat(ends, signs, *, n):
    """``sum_i (sum_j signs_j [ends_j = i])^2`` -- the collision part of
    the CDVV14 l2 statistic computed on device: with signs +1 for the u
    walks and -1 for the w walks this is ``sum_i (X_i - Y_i)^2`` over the
    endpoint count vectors, one segment-sum and one reduction.  Returns
    ``(statistic, counter word)`` -- zero kernel evals by construction."""
    TRACE_COUNTS["signed_endpoint_stat"] += 1
    c = jnp.zeros((n,), signs.dtype).at[ends].add(signs)
    stat = jnp.sum(c * c)
    return stat, _c.word(status=_g.result_status(stat))


@_jit
def triangle_edge_scan(x, x_sq, u, v, degs, keys, hstate=None, *, kind,
                       inv_bw, beta, pairwise, block_size, num_blocks, n, s,
                       exact, use_pallas, interpret, bm, level1="blocked",
                       num_far=64, precision="f32"):
    """Theorem 6.17's per-edge inner loop as ONE program: degree-ordered
    orientation of the (u, v) pairs, ONE masked level-1 read of the
    oriented v frontier (keys[0], shared by every draw -- the §4 caching
    contract inside a single trace), then a ``lax.scan`` over keys[1:]
    where each step draws w ~ k(v, .)/deg(v), masks by the ordering
    ``v < w`` and ``w != u``, and accumulates k(u,v) k(u,w); the final
    reweighting by deg(v)/num_draws also happens in-program.  Returns
    (oriented u, oriented v, per-edge weight estimates W_e, counter
    word).  Oracle: ``ref.triangle_batch_ref``."""
    TRACE_COUNTS["triangle_edge_scan"] += 1
    views = _block_views(x, x_sq, block_size)
    prec = _ref.degree_precedes(degs, u, v)
    uu = jnp.where(prec, u, v)
    vv = jnp.where(prec, v, u)
    kuv = _ref.kv_pairs(x[uu], x[vv], kind, inv_bw, beta, pairwise)
    bs, st = _masked_sums_any(x, x_sq, vv, keys[0], hstate, kind=kind,
                              inv_bw=inv_bw, beta=beta, pairwise=pairwise,
                              block_size=block_size, num_blocks=num_blocks,
                              n=n, s=s, exact=exact, use_pallas=use_pallas,
                              interpret=interpret, bm=bm, level1=level1,
                              num_far=num_far, precision=precision)

    def body(acc, k):
        w, _ = _sample_core(x, x_sq, views, vv, bs, k, kind=kind,
                            inv_bw=inv_bw, beta=beta, pairwise=pairwise,
                            block_size=block_size, n=n)
        valid = _ref.degree_precedes(degs, vv, w) & (w != uu)
        kuw = _ref.kv_pairs(x[uu], x[w], kind, inv_bw, beta, pairwise)
        return acc + jnp.where(valid, kuv * kuw, 0.0), None

    acc, _ = jax.lax.scan(body, jnp.zeros_like(kuv), keys[1:])
    num_draws = keys.shape[0] - 1
    w_hat = acc * degs[vv] / num_draws
    m = u.shape[0]
    cols, far, ov = _l1_cols(level1, exact, num_blocks, s, n, num_far,
                             hstate)
    # one level-1 read of the m-edge frontier + m k(u,v) pairs + per draw
    # m level-2 rows and m k(u,w) pairs -- NeighborSampler.triangle_batches
    cw = _c.word(status=_g.merge(st, _g.result_status(w_hat)),
                 evals=m * cols + m + num_draws * (m * block_size + m),
                 l1_reads=m, draws=num_draws * m, far_samples=m * far,
                 overflow=m * ov)
    return uu, vv, w_hat, cw


# --------------------------------------------------------------------- #
# batched multi-tenant entry points (DESIGN.md §13)
#
# One serving tick aggregates R concurrent requests -- possibly from
# different tenants -- into ONE padded device batch: ``tidx (R,)`` indexes
# the stacked tenant arena ``xa (T, n, d)`` / ``xa_sq (T, n)`` (and, for
# hashed level-1 tenants, a stacked ``HashState`` pytree), every request
# carries its OWN PRNG key, and every program returns a PER-REQUEST uint32
# status word.  ``jax.vmap`` over the request axis reduces each lane to
# the identical op sequence the single-request entry point runs, so lanes
# match the sequential calls bitwise on the jnp paths (the parity contract
# ``tests/test_serving.py`` asserts).  One exception to the vmap: on a
# one-tenant arena the exact blocked Pallas read of ``batched_fused_sample``,
# ``batched_prob_of`` and ``batched_kde_query`` packs every request's
# frontier rows (or query points) into the query tiles of ONE level-1 pass
# (``_packs``), where the vmap would give each request a pass of its own
# over the whole dataset -- or, for queries, a materialised (w, n) kernel
# tile of its own; the noise, the level-2 draws and the counter words stay
# per request, so the lanes are unchanged.  Request widths are padded to
# static buckets by the serving layer, which bounds recompiles to one
# program per (tenant signature, op, bucket) group.
# --------------------------------------------------------------------- #
def tenant_slice(a, ti):
    """One request's slice of a stacked arena leaf.  Runs under vmap, so
    ``ti`` is a traced per-request scalar and ``a[ti]`` is a batched
    gather -- one copy of the tenant's rows per request.  A one-tenant
    arena (static leading dim 1) is read as ``a[0]`` instead: unbatched,
    so the request lanes share the tenant's rows without copying them."""
    return a[0] if a.shape[0] == 1 else a[ti]


def _tenant(xa, xa_sq, hstate, ti):
    """Gather one request's tenant slice out of the stacked arena (the
    hash state, when present, leaf-wise from the stacked pytree)."""
    hs = (jax.tree_util.tree_map(lambda a: tenant_slice(a, ti), hstate)
          if hstate is not None else None)
    return tenant_slice(xa, ti), tenant_slice(xa_sq, ti), hs


def _pallas_sweep(exact, use_pallas, level1) -> bool:
    """True when a draw, ``prob_of`` or query reads level 1 with the
    exact blocked Pallas sweep over the whole dataset."""
    return exact and use_pallas and level1 == "blocked"


def _packs(tenants, exact, use_pallas, level1) -> bool:
    """True when a batched draw, ``prob_of`` or query program reads
    level 1 in ONE Pallas pass over all of its requests' rows: the arena
    holds one tenant (a static shape, as in ``tenant_slice``) and the read
    is the Pallas sweep, whose row sums do not depend on the other rows of
    their query tile.  Otherwise each request reads on its own."""
    return tenants == 1 and _pallas_sweep(exact, use_pallas, level1)


def level1_passes(tenants, requests, width, *, exact, use_pallas, level1,
                  bm, **_):
    """Pallas level-1 passes over the dataset that one
    ``batched_fused_sample`` / ``batched_prob_of`` program makes for
    ``requests`` frontiers of padded ``width`` on an arena of ``tenants``
    (a ``batched_kde_query`` program makes them only where it packs):
    one per ``bm``-row query tile, ``ceil(R w / bm)`` when the rows pack
    (``_packs``) and ``R ceil(w / bm)`` when each request has its own
    tiles; 0 where the read is no Pallas sweep (jnp, stratified, hashed).
    Takes the sampler's static config as keywords."""
    if not _pallas_sweep(exact, use_pallas, level1):
        return 0
    if _packs(tenants, exact, use_pallas, level1):
        return -(-requests * width // bm)
    return requests * -(-width // bm)


def _packed_fused_sample(x, x_sq, src, keys, *, kind, inv_bw, beta,
                         pairwise, block_size, num_blocks, n, interpret, bm,
                         precision):
    """``batched_fused_sample`` on a one-tenant arena's exact Pallas path:
    the R frontiers ``src (R, w)`` share one level-1 pass, whose query
    tiles hold the R w rows in request order.  Each request's noise comes
    from its own key exactly as in ``_fused_sample``, and its level-2 draw
    and counter word are built per request, so lane r equals
    ``fused_sample`` with key ``keys[r]``."""
    R, w = src.shape
    views = _block_views(x, x_sq, block_size)
    gumbel, u = jax.vmap(
        lambda k: _pallas_noise(jax.random.split(k)[1], w, num_blocks))(keys)
    blk, pb, bs = _sample_block_rows(
        x, src.reshape(R * w), gumbel.reshape(R * w, num_blocks), kind=kind,
        inv_bw=inv_bw, beta=beta, block_size=block_size, interpret=interpret,
        bm=bm, precision=precision)
    blk, pb = blk.reshape(R, w), pb.reshape(R, w)
    bs = bs.reshape(R, w, num_blocks)

    def level2(src_r, blk_r, pb_r, bs_r, u_r):
        nb, prob, st = _pallas_level2(x, x_sq, views, src_r, blk_r, pb_r,
                                      bs_r, u_r, kind=kind, inv_bw=inv_bw,
                                      beta=beta, pairwise=pairwise,
                                      block_size=block_size, n=n)
        return nb, prob, _c.word(status=st, evals=w * (n + block_size),
                                 l1_reads=w, draws=w)

    nb, prob, cw = jax.vmap(level2)(src, blk, pb, bs, u)
    return nb, prob, bs, cw


def _packed_prob_of(x, x_sq, src, dst, *, kind, inv_bw, beta, pairwise,
                    block_size, num_blocks, n, interpret, bm, precision):
    """``batched_prob_of`` on a one-tenant arena's exact Pallas path: one
    masked level-1 pass over the R w rows of ``src (R, w)``, then each
    request's exact level-2 probability and counter word."""
    R, w = src.shape
    views = _block_views(x, x_sq, block_size)
    bs = _masked_rows(x, src.reshape(R * w), kind=kind, inv_bw=inv_bw,
                      beta=beta, block_size=block_size, interpret=interpret,
                      bm=bm, precision=precision).reshape(R, w, num_blocks)

    def level2(src_r, dst_r, bs_r):
        prob = _prob_core(x, x_sq, views, src_r, dst_r, bs_r, kind=kind,
                          inv_bw=inv_bw, beta=beta, pairwise=pairwise,
                          block_size=block_size, n=n)
        st = _g.merge(_g.sums_status(bs_r, _ref.BLOCK_SUM_FLOOR),
                      _g.result_status(prob))
        return prob, _c.word(status=st, evals=w * (n + block_size),
                             l1_reads=w)

    return jax.vmap(level2)(src, dst, bs)


@_m.scope("level1")
def _query_rows(x, q, *, kind, inv_bw, beta, block_size, interpret, bm,
                precision):
    """One Pallas blocksum pass over the external query points ``q (m,
    d)``: their plain (m, B) block sums, neither own-block corrected nor
    floored, with the dataset padded to a block_size multiple at the far
    offset (kernel values 0)."""
    m = q.shape[0]
    return _rk.blocksum_pallas(
        _pad_rows(q, bm, 0.0), _pad_rows(x, block_size, _PAD_OFFSET), kind,
        inv_bw, beta, bm=bm, bn=block_size, interpret=interpret,
        precision=precision)[:m]


def _packed_kde_query(x, y, *, kind, inv_bw, beta, block_size, num_blocks,
                      n, interpret, bm, precision):
    """``batched_kde_query`` on a one-tenant arena's exact Pallas path:
    the R w points of ``y (R, w, d)`` share one level-1 pass in request
    order, then each request's row sums, status and counter word -- the
    fields ``exact_block_sums`` and the per-lane query fold give."""
    R, w, d = y.shape
    bs = _query_rows(x, y.reshape(R * w, d), kind=kind, inv_bw=inv_bw,
                     beta=beta, block_size=block_size, interpret=interpret,
                     bm=bm, precision=precision).reshape(R, w, num_blocks)

    def lane(bs_r):
        est = bs_r.sum(-1)
        # sums_status carries the NONFINITE bit of the block sums too
        st = _g.merge(_g.sums_status(bs_r, _ref.BLOCK_SUM_FLOOR),
                      _g.result_status(est))
        return est, _c.word(status=st, evals=w * n, l1_reads=w)

    return jax.vmap(lane)(bs)


@_jit
def batched_fused_sample(xa, xa_sq, tidx, src, keys, hstate=None, *, kind,
                         inv_bw, beta, pairwise, block_size, num_blocks, n,
                         s, exact, use_pallas, interpret, bm,
                         level1="blocked", num_far=64, precision="f32"):
    """One serving tick's depth-2 draws for R requests across T tenants as
    ONE program: ``src (R, w)`` padded frontiers, ``keys (R, 2)``
    per-request PRNG keys, ``tidx (R,)`` tenant indices.  Returns
    (neighbors (R, w), probs (R, w), level-1 sums (R, w, B), per-request
    counter words (R, obs.WIDTH)).  Lane r is exactly ``fused_sample`` on
    tenant ``tidx[r]`` with key ``keys[r]``; on a one-tenant exact Pallas
    arena the lanes share one level-1 pass (``_packs``)."""
    TRACE_COUNTS["batched_fused_sample"] += 1
    if _packs(xa.shape[0], exact, use_pallas, level1):
        return _packed_fused_sample(
            xa[0], xa_sq[0], src, keys, kind=kind, inv_bw=inv_bw, beta=beta,
            pairwise=pairwise, block_size=block_size, num_blocks=num_blocks,
            n=n, interpret=interpret, bm=bm, precision=precision)

    def one(ti, src_r, key_r):
        x, x_sq, hs = _tenant(xa, xa_sq, hstate, ti)
        return _fused_sample(x, x_sq, src_r, key_r, hs, kind=kind,
                             inv_bw=inv_bw, beta=beta, pairwise=pairwise,
                             block_size=block_size, num_blocks=num_blocks,
                             n=n, s=s, exact=exact, use_pallas=use_pallas,
                             interpret=interpret, bm=bm, level1=level1,
                             num_far=num_far, precision=precision)

    return jax.vmap(one)(tidx, src, keys)


@_jit
def batched_walk_scan(xa, xa_sq, tidx, starts, keys, hstate=None, *, kind,
                      inv_bw, beta, pairwise, block_size, num_blocks, n, s,
                      exact, use_pallas, interpret, bm, rounds, slack,
                      record_path=False, level1="blocked", num_far=64,
                      precision="f32"):
    """R independent T-step walks (``starts (R, w)``, ``keys (R, T, 2)``)
    across stacked tenants in ONE program.  Returns (endpoints (R, w),
    path ((R, T, w) or None), counter words (R, obs.WIDTH), rejection
    fallbacks (R,)) -- lane r is ``walk_scan`` on its tenant with its own
    key stream, so endpoints are bitwise equal to the sequential
    per-request calls."""
    TRACE_COUNTS["batched_walk_scan"] += 1

    def one(ti, st_r, keys_r):
        x, x_sq, hs = _tenant(xa, xa_sq, hstate, ti)
        return walk_scan(x, x_sq, st_r, keys_r, hs, kind=kind, inv_bw=inv_bw,
                         beta=beta, pairwise=pairwise, block_size=block_size,
                         num_blocks=num_blocks, n=n, s=s, exact=exact,
                         use_pallas=use_pallas, interpret=interpret, bm=bm,
                         rounds=rounds, slack=slack, record_path=record_path,
                         level1=level1, num_far=num_far,
                         precision=precision)

    return jax.vmap(one)(tidx, starts, keys)


@_jit
def batched_prob_of(xa, xa_sq, tidx, src, dst, keys, hstate=None, *, kind,
                    inv_bw, beta, pairwise, block_size, num_blocks, n, s,
                    exact, use_pallas, interpret, bm, level1="blocked",
                    num_far=64, precision="f32"):
    """q(dst | src) for R requests (``src``/``dst`` (R, w)) in ONE
    program: per lane one masked level-1 read of the src frontier (the
    same read ``prob_of`` performs when its cache is cold) followed by the
    exact level-2 probability; on a one-tenant exact Pallas arena one
    read covers every lane (``_packs``).  Returns (probs (R, w), counter
    words (R, obs.WIDTH))."""
    TRACE_COUNTS["batched_prob_of"] += 1
    if _packs(xa.shape[0], exact, use_pallas, level1):
        return _packed_prob_of(
            xa[0], xa_sq[0], src, dst, kind=kind, inv_bw=inv_bw, beta=beta,
            pairwise=pairwise, block_size=block_size, num_blocks=num_blocks,
            n=n, interpret=interpret, bm=bm, precision=precision)

    def one(ti, src_r, dst_r, key_r):
        x, x_sq, hs = _tenant(xa, xa_sq, hstate, ti)
        views = _block_views(x, x_sq, block_size)
        bs, st = _masked_sums_any(x, x_sq, src_r, key_r, hs, kind=kind,
                                  inv_bw=inv_bw, beta=beta, pairwise=pairwise,
                                  block_size=block_size,
                                  num_blocks=num_blocks, n=n, s=s,
                                  exact=exact, use_pallas=use_pallas,
                                  interpret=interpret, bm=bm, level1=level1,
                                  num_far=num_far, precision=precision)
        prob = _prob_core(x, x_sq, views, src_r, dst_r, bs, kind=kind,
                          inv_bw=inv_bw, beta=beta, pairwise=pairwise,
                          block_size=block_size, n=n)
        wq = src_r.shape[0]
        cols, far, ov = _l1_cols(level1, exact, num_blocks, s, n, num_far,
                                 hs)
        return prob, _c.word(status=_g.merge(st, _g.result_status(prob)),
                             evals=wq * (cols + block_size), l1_reads=wq,
                             far_samples=wq * far, overflow=wq * ov)

    return jax.vmap(one)(tidx, src, dst, keys)


@_jit
def batched_kde_query(xa, xa_sq, tidx, y, keys, *, kind, inv_bw, beta,
                      pairwise, block_size, num_blocks, n, s, exact,
                      use_pallas=False, interpret=False, bm=128,
                      level1="blocked", precision="f32"):
    """Definition 1.1 row-sum estimates for R query requests (``y``
    (R, q, d) external points) in ONE program -- the dense level-1 read
    per lane (exact or stratified, matching ``ExactBlockKDE`` /
    ``StratifiedKDE.query``); on a one-tenant exact Pallas arena one
    blocksum pass reads every lane's points (``_packs``).  Hash tenants
    are served by ``kde_hash.ops.batched_hashed_query`` instead.  Returns
    (estimates (R, q), counter words (R, obs.WIDTH))."""
    TRACE_COUNTS["batched_kde_query"] += 1
    if _packs(xa.shape[0], exact, use_pallas, level1):
        return _packed_kde_query(
            xa[0], y, kind=kind, inv_bw=inv_bw, beta=beta,
            block_size=block_size, num_blocks=num_blocks, n=n,
            interpret=interpret, bm=bm, precision=precision)

    def one(ti, y_r, key_r):
        x, x_sq, _ = _tenant(xa, xa_sq, None, ti)
        if exact:
            bs, cw = exact_block_sums(y_r, x, x_sq, kind=kind,
                                      inv_bw=inv_bw, beta=beta,
                                      pairwise=pairwise,
                                      block_size=block_size,
                                      num_blocks=num_blocks, n=n,
                                      precision=precision)
        else:
            bs, cw = stratified_block_sums(y_r, x, x_sq, key_r, kind=kind,
                                           inv_bw=inv_bw, beta=beta,
                                           pairwise=pairwise,
                                           block_size=block_size,
                                           num_blocks=num_blocks, n=n, s=s,
                                           precision=precision)
        est = bs.sum(-1)
        st = _g.merge(_g.sums_status(bs, _ref.BLOCK_SUM_FLOOR),
                      _g.result_status(est))
        return est, _c.fold_status(cw, st)

    return jax.vmap(one)(tidx, y, keys)


# --------------------------------------------------------------------- #
# streaming patches (DESIGN.md §12)
# --------------------------------------------------------------------- #
@_jit
def patch_block_sums(bs, x, src, slots, old_x, new_x, *, kind, inv_bw, beta,
                     pairwise, block_size):
    """Incrementally update a cached (w, B) level-1 read after a dataset
    mutation batch: O(w m) kernel evals instead of the O(w n) rebuild.
    The jitted body IS ``ref.patch_block_sums_ref`` (same delta scatter),
    so the oracle parity is structural; equivalence vs a fresh rebuild is
    what the streaming tests assert.  Frontier rows that mutated must NOT
    be patched -- the consumer drops the cache instead (the ``src``
    operand is only read for the frontier coordinates).  Returns
    ``(patched sums, counter word)``."""
    TRACE_COUNTS["patch_block_sums"] += 1
    out = _ref.patch_block_sums_ref(bs, x[src], slots, old_x, new_x, kind,
                                    inv_bw, beta, block_size, pairwise)
    # old + new kernel values per (frontier row, mutated slot) pair --
    # the host accounting in NeighborSampler._sync, verbatim
    return out, _c.word(status=_g.nonfinite_status(out),
                        evals=2 * src.shape[0] * slots.shape[0])


@_jit
@_m.scope("degrees")
def degree_delta(degs, x, x_sq, slots, old_x, new_x, old_live, new_live, *,
                 kind, inv_bw, beta, pairwise):
    """Incremental Algorithm 4.3 degree update after a mutation batch:
    O(n m) evals against the post-mutation padded arrays (column deltas
    for untouched rows, exact recompute for the mutated slots), replacing
    the O(n^2 / estimator-budget) degree rebuild.  Returns ``(degrees,
    counter word)``."""
    TRACE_COUNTS["degree_delta"] += 1
    out = _ref.degree_delta_ref(degs, x, x_sq, slots, old_x, new_x,
                                old_live, new_live, kind, inv_bw, beta,
                                pairwise)
    # old + new kernel column per mutated slot against all n rows -- the
    # host accounting in DegreeSampler._sync / RowNormSampler._sync
    return out, _c.word(status=_g.nonfinite_status(out),
                        evals=2 * slots.shape[0] * degs.shape[0])
