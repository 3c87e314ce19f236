"""Pure-jnp oracle + shared kernel-value math for the fused depth-2 sampler.

``sample_block_ref`` is the bit-for-bit reference of the Pallas kernel in
``kernel.py``: masked per-block sums with the self-block correction applied
in the same pass, plus a Gumbel-max draw of the block index.  The kernel
values reuse squared norms precomputed once over the dataset (``x_sq``) --
the level-1 read never recomputes ``||x_j||^2`` (DESIGN.md §3).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_L2_KINDS = ("gaussian", "exponential", "rational_quadratic")
# Kinds with closed-form math in this module: their jitted programs don't
# need (and must not be keyed on) a Kernel's pairwise closure.
BUILTIN_KINDS = _L2_KINDS + ("laplacian",)


def static_pairwise(kernel):
    """The ``pairwise`` value to put in a jit static config for ``kernel``:
    None for built-in kinds (stable jit cache across Kernel instances),
    the kernel's own callable for custom kinds."""
    return None if kernel.name in BUILTIN_KINDS else kernel.pairwise

# Floor applied to every (corrected) block-sum estimate, matching the seed
# host sampler: keeps log() finite and the own-block sum positive after the
# k(x, x) = 1 subtraction.
BLOCK_SUM_FLOOR = 1e-12


def _finish_l2(d2, kind: str, inv_bw: float, beta: float):
    d2 = jnp.maximum(d2, 0.0)
    if kind == "gaussian":
        return jnp.exp(-d2 * (inv_bw * inv_bw))
    if kind == "exponential":
        return jnp.exp(-jnp.sqrt(d2) * inv_bw)
    return (1.0 + d2 * (inv_bw * inv_bw)) ** (-beta)


# --------------------------------------------------------------------- #
# mixed precision (DESIGN.md §14)
#
# ``precision="bf16"`` rounds the dataset/query tiles to bfloat16 before
# the level-1 distance GEMM and keeps EVERYTHING downstream in f32: the
# cross term accumulates in f32 (``preferred_element_type``), the norms
# are recomputed in f32 from the *rounded* coordinates (so d2 is the exact
# f32 distance of the bf16-rounded points, never a mixed-rounding hybrid),
# and the CDF/prefix sums of the draw stages are untouched -- the PR-2
# prefix-sum bias fix is precision-independent.  ``"f32"`` is the default
# and stays bitwise identical to the pre-policy code path.
# --------------------------------------------------------------------- #
PRECISIONS = ("f32", "bf16")

# Documented accuracy bound of the bf16 eval path for Table-1 kernels.
# The error is INPUT-rounding dominated: each coordinate picks up one bf16
# rounding (eps = 2^-8), so the squared distance of the rounded points
# drifts by |Δd2| <~ 2 eps d2, and for the exponential-family kernels
# k = exp(-c d2) the per-value relative error is ~ Δd2 = 2^-7 d2.  Terms
# with d2 large enough to push that bound past ~6% (d2 > 8) contribute
# k < 3e-4 of the row mass, so the row-sum relative error is bounded by
# the d2 <~ 8 envelope: 8 * 2^-7 = 2^-4.  (Rounding the exp argument to
# bf16 adds only 2^-9 on top.)  Measured on gaussian n=262144 d=16:
# 4.1e-2 max over 256 queries -- inside this bound, outside any tighter
# one.
# tests/test_precision.py pins estimator outputs to 2 * this bound.
BF16_REL_ERR = 2.0 ** -4

# Mirrors kde_rowsum.ops._PAD_OFFSET (imported there, duplicated here to
# keep ref.py import-free of the ops layer): bf16-representable, and its
# squared norm overflows f32 to inf, so padded rows evaluate to exactly 0
# on the bf16 path too.
_FAR_OFFSET = 1.0e30

def exp_bf16(y):
    """exp() of ``y`` after rounding it to bf16, evaluated in f32.

    The bf16 path's transcendental takes the rounded argument (so it is a
    pure function of a bf16 value) and runs the native f32 ``exp`` --
    the same call inside the Pallas kernel bodies and in the jnp refs.
    """
    return jnp.exp(y.astype(jnp.bfloat16).astype(jnp.float32))


def check_precision(precision: str, kind: str, pairwise=None) -> None:
    """Reject unsupported precision configs at trace time (not mid-run)."""
    if precision not in PRECISIONS:
        raise ValueError(f"unknown precision {precision!r}; "
                         f"expected one of {PRECISIONS}")
    if precision == "bf16" and (kind not in _L2_KINDS or pairwise is not None):
        raise ValueError(
            "precision='bf16' supports the built-in L2 kernels only "
            f"(gaussian / exponential / rational_quadratic); got {kind!r}")


def _finish_l2_bf16(d2, kind: str, inv_bw: float, beta: float):
    """L2-kind finisher of the bf16 path: f32 d2 in, ``exp_bf16`` out.
    This exact function runs inside the Pallas kernel bodies AND the jnp
    refs, so interpret-mode bf16 runs match the oracles bitwise."""
    d2 = jnp.maximum(d2, 0.0)
    if kind == "gaussian":
        return exp_bf16(-d2 * (inv_bw * inv_bw))
    if kind == "exponential":
        return exp_bf16(-jnp.sqrt(d2) * inv_bw)
    return (1.0 + d2 * (inv_bw * inv_bw)) ** (-beta)


def kv_matrix_bf16(q, x, kind: str, inv_bw: float, beta: float):
    """(m, n) kernel values with bf16 operand tiles and f32 accumulation.
    The passed-in dataset norms are NOT reused: they describe the unrounded
    rows, so the bf16 path recomputes both norm vectors in f32 from the
    rounded coordinates (O((m + n) d), amortized by the O(m n d) GEMM)."""
    qb = q.astype(jnp.bfloat16)
    xb = x.astype(jnp.bfloat16)
    qf = qb.astype(jnp.float32)
    xf = xb.astype(jnp.float32)
    qq = jnp.sum(qf * qf, axis=1, keepdims=True)
    xx = jnp.sum(xf * xf, axis=1)
    cross = jax.lax.dot_general(qb, xb, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
    d2 = qq + xx[None, :] - 2.0 * cross
    return _finish_l2_bf16(d2, kind, inv_bw, beta)


def kv_block_sums_bf16(q, x, kind: str, inv_bw: float, beta: float,
                       bn: int, blocks_per_tile: int | None = None):
    """(m, ceil(n/bn)) per-block sums as a bf16 column-tile scan.

    The bandwidth-optimal level-1 sweep: the dataset is rounded to bf16,
    pre-transposed into (tile, d, tile_cols) GEMM layout ONCE, and a
    ``lax.scan`` walks the column tiles -- each step is one
    (m, d) x (d, tile_cols) bf16 GEMM with an f32 accumulator, the bf16
    exp, and an in-register per-block reduction.  Peak live memory is the
    (m, tile_cols) f32 value tile instead of the dense (m, n) matrix, so
    the sweep streams the dataset at memory bandwidth.  The tail is padded
    at the far offset (kernel values exactly 0) and sliced off.
    """
    from repro.kernels import tuning
    m = q.shape[0]
    n, d = x.shape
    num_b = -(-n // bn)
    t = blocks_per_tile or tuning.sweep_blocks_per_tile(bn, d)
    ntiles = -(-num_b // t)
    pad = ntiles * t * bn - n
    if pad:
        x = jnp.concatenate(
            [x, jnp.full((pad, d), _FAR_OFFSET, x.dtype)], axis=0)
    xb = x.astype(jnp.bfloat16)
    xf = xb.astype(jnp.float32)
    x_sq = jnp.sum(xf * xf, axis=-1)
    xt = xb.T.reshape(d, ntiles, t * bn).transpose(1, 0, 2)  # (T, d, cols)
    xsq_t = x_sq.reshape(ntiles, t * bn)
    qb = q.astype(jnp.bfloat16)
    qf = qb.astype(jnp.float32)
    qq = jnp.sum(qf * qf, axis=1, keepdims=True)

    def body(_, operand):
        xt_i, xsq_i = operand
        cross = jax.lax.dot_general(qb, xt_i, (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)
        d2 = qq + xsq_i[None, :] - 2.0 * cross
        kv = _finish_l2_bf16(d2, kind, inv_bw, beta)
        return None, kv.reshape(m, t, bn).sum(-1)

    _, out = jax.lax.scan(body, None, (xt, xsq_t))           # (T, m, t)
    out = out.transpose(1, 0, 2).reshape(m, ntiles * t)
    return out[:, :num_b]


def kv_matrix(q, x, x_sq, kind: str, inv_bw: float, beta: float,
              pairwise=None, precision: str = "f32") -> jnp.ndarray:
    """(m, n) kernel values; L2 kinds reuse precomputed ``x_sq = ||x_j||^2``.

    Built-in kinds never touch ``pairwise`` -- keeping it out of the jit
    static key means one compiled program per (kind, inv_bw, beta), not one
    per ``Kernel`` instance.  Unknown kinds (custom ``Kernel`` objects) fall
    back to the ``pairwise`` callable.  ``precision="bf16"`` dispatches to
    the mixed-precision evaluator (L2 kinds only; ``x_sq`` is recomputed
    from the rounded rows there).
    """
    if precision != "f32":
        check_precision(precision, kind, pairwise)
        return kv_matrix_bf16(q, x, kind, inv_bw, beta)
    if kind in _L2_KINDS:
        qq = jnp.sum(q * q, axis=1, keepdims=True)
        # full f32 contract precision (a TPU's default f32 dot is one
        # bf16 pass -- see kernels_fn._sq_dists)
        d2 = qq + x_sq[None, :] - 2.0 * jnp.matmul(
            q, x.T, precision=jax.lax.Precision.HIGHEST)
        return _finish_l2(d2, kind, inv_bw, beta)
    if kind == "laplacian":
        # cap the (m, n, d) broadcast at ~1 GiB of f32 (static unroll)
        m, d = q.shape
        n = x.shape[0]
        chunk = max(int((1 << 28) // max(n * d, 1)), 1)
        outs = [jnp.exp(-jnp.sum(jnp.abs(q[lo:lo + chunk, None, :]
                                         - x[None, :, :]), axis=-1) * inv_bw)
                for lo in range(0, m, chunk)]
        return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=0)
    return pairwise(q, x)


def kv_rows(xs, xb, xs_sq, xb_sq, kind: str, inv_bw: float, beta: float,
            pairwise=None) -> jnp.ndarray:
    """Per-row block values k(xs_i, xb_i_j): xs (w, d), xb (w, bs, d) ->
    (w, bs).  The level-2 read of the depth-2 sampler."""
    if kind in _L2_KINDS:
        # broadcast multiply-reduce -- the batched dot_general lowering is
        # ~8x slower on the host backend for these thin (w, 1, d) x
        # (w, d, bs) shapes (it was the hidden per-step cost of the walk
        # level-2 read at large n)
        cross = jnp.sum(xs[:, None, :] * xb, axis=-1)
        d2 = xs_sq[:, None] + xb_sq - 2.0 * cross
        return _finish_l2(d2, kind, inv_bw, beta)
    if kind == "laplacian":
        d1 = jnp.sum(jnp.abs(xs[:, None, :] - xb), axis=-1)
        return jnp.exp(-d1 * inv_bw)
    return jax.vmap(lambda a, b: pairwise(a[None, :], b)[0])(xs, xb)


def kv_pairs(a, b, kind: str, inv_bw: float, beta: float,
             pairwise=None) -> jnp.ndarray:
    """Elementwise k(a_i, b_i) for aligned (w, d) arrays -- O(w d)."""
    if kind in _L2_KINDS:
        d2 = jnp.sum((a - b) ** 2, axis=-1)
        return _finish_l2(d2, kind, inv_bw, beta)
    if kind == "laplacian":
        d1 = jnp.sum(jnp.abs(a - b), axis=-1)
        return jnp.exp(-d1 * inv_bw)
    return jax.vmap(lambda u, v: pairwise(u[None, :], v[None, :])[0, 0])(a, b)


def inverse_cdf_index(cdf, u) -> jnp.ndarray:
    """Vectorized inverse-CDF lookup over a normalized prefix array
    (Algorithm 4.5 in its dense device form): cdf (n,) nondecreasing with
    cdf[-1] ~= 1, u (w,) uniforms -> (w,) int32 indices.

    The prefix array is accumulated in float64 on the host (see
    ``core.sampling.vertex.PrefixCDF``) and only *rounded* to float32 for
    the device lookup -- per-entry rounding is O(eps) and unbiased, unlike
    float32 prefix accumulation whose error grows with n."""
    idx = jnp.searchsorted(cdf, u, side="right")
    return jnp.clip(idx, 0, cdf.shape[0] - 1).astype(jnp.int32)


def inverse_cdf_pick(w, u):
    """Inverse-CDF draw per row of nonnegative weights ``w`` (m, k) at
    uniforms ``u`` (m,): the first entry of positive weight whose prefix
    sum reaches ``u * total``, ``total`` being the last prefix sum.  The
    pick never lands on an entry of zero weight, however the sums round
    (a total summed apart from the prefix sums, or a prefix sum taken by
    a tree, as a TPU takes it, can exceed the prefix sum at the last
    positive entry, and ``u * total`` then passes it).  Returns ``(index,
    total)``; a row of zeros picks 0."""
    k = w.shape[1]
    c = jnp.cumsum(w, axis=1)
    tot = c[:, -1]
    pos = w > 0
    hit = (c >= (u * tot)[:, None]) & pos
    col = jnp.arange(k, dtype=jnp.int32)[None, :]
    first = jnp.min(jnp.where(hit, col, k), axis=1)
    last = jnp.max(jnp.where(pos, col, 0), axis=1)
    return jnp.where(first < k, first, last), tot


def block_views(x, x_sq, block_size: int):
    """(B, bs, d) / (B, bs) contiguous views of the (padded) dataset.
    Built once per compiled program; the level-2 read then gathers whole
    block *slices* instead of w*bs random rows."""
    pad = -x.shape[0] % block_size
    xb_all = jnp.pad(x, ((0, pad), (0, 0))).reshape(-1, block_size,
                                                    x.shape[1])
    xb_sq_all = jnp.pad(x_sq, (0, pad)).reshape(-1, block_size)
    return xb_all, xb_sq_all


def level2_row(x, x_sq, views, src, blk, kind: str, inv_bw: float,
               beta: float, block_size: int, n: int, pairwise=None):
    """Exact kernel row of each source against its chosen block, with the
    self edge and out-of-range tail columns masked to 0.  Shared by the
    fused ops and the ref oracles (the level-2 math is identical on every
    path; only the level-1 read differs)."""
    xb_all, xb_sq_all = views
    lo = blk * block_size
    cols = lo[:, None] + jnp.arange(block_size, dtype=jnp.int32)[None, :]
    xs = x[src]
    kv = kv_rows(xs, xb_all[blk], x_sq[src], xb_sq_all[blk], kind,
                 inv_bw, beta, pairwise)
    if n % block_size == 0:
        # tail-free fast path: every column is in range, so only the self
        # edge needs masking
        live = cols != src[:, None]
        return jnp.where(live, kv, 0.0), live, cols
    valid = cols < n
    cols_c = jnp.minimum(cols, n - 1)
    live = valid & (cols_c != src[:, None])
    return jnp.where(live, kv, 0.0), live, cols_c


def level2_draw(kv, live, cols_c, u2):
    """Inverse-CDF draw from each row of ``kv``; all-zero rows (numerically
    underflowed blocks) fall back to uniform over the live columns instead
    of producing NaN.  The pick never lands on a dead column (the self
    edge, a ragged tail block's out-of-range columns), however the prefix
    sums round (``inverse_cdf_pick``)."""
    rowsum = kv.sum(axis=1)
    use = jnp.where((rowsum > 0.0)[:, None], kv, live.astype(jnp.float32))
    j, tot = inverse_cdf_pick(use, u2)
    nb = jnp.take_along_axis(cols_c, j[:, None], axis=1)[:, 0]
    pin = jnp.take_along_axis(use, j[:, None], axis=1)[:, 0] \
        / jnp.maximum(tot, 1e-30)
    return nb, pin


def choose_block(bs, key):
    """Exact inverse-CDF categorical over rows of the (floored) block sums;
    returns (block index, realized block probability).  (The Pallas kernel
    uses Gumbel-max instead because it streams blocks one at a time; both
    are exact samplers of the same law.)"""
    c = jnp.cumsum(bs, axis=1)
    tot = c[:, -1]
    u = jax.random.uniform(key, (bs.shape[0],))
    blk = jnp.sum((u * tot)[:, None] > c, axis=1).astype(jnp.int32)
    blk = blk.clip(0, bs.shape[1] - 1)
    pb = jnp.take_along_axis(bs, blk[:, None], axis=1)[:, 0] / tot
    return blk, pb


def cdf_group(m: int) -> int:
    """Largest divisor of ``m`` that is <= sqrt(m) -- the inner group width
    of the two-level inverse CDF.  1 for prime ``m`` (degenerates to the
    flat search, still correct)."""
    g = max(int(m ** 0.5), 1)
    while m % g:
        g -= 1
    return g


def grouped_inverse_cdf(vals, u, group: int):
    """Two-level inverse-CDF categorical over each row of ``vals``
    (contiguous groups of ``group`` columns): pick the group by the group
    CDF, then the column inside it.  The SAME sampling law as the flat
    ``cumsum`` inverse CDF -- nested search over contiguous groups visits
    the same index up to fp regrouping of partial sums -- but the per-row
    cumsum touches O(m/group + group) lanes instead of O(m), which is the
    walk step's hot-path win (DESIGN.md §14).  Returns
    (index, vals[index], row total)."""
    w, m = vals.shape
    ng = m // group
    v3 = vals.reshape(w, ng, group)
    grp = v3.sum(-1)
    cg = jnp.cumsum(grp, axis=1)
    tot = cg[:, -1]
    t = u * tot
    g = jnp.sum(t[:, None] > cg, axis=1).clip(0, ng - 1).astype(jnp.int32)
    prev = (jnp.take_along_axis(cg, g[:, None], axis=1)
            - jnp.take_along_axis(grp, g[:, None], axis=1))[:, 0]
    sub = jnp.take_along_axis(v3, g[:, None, None], axis=1)[:, 0]
    cs = jnp.cumsum(sub, axis=1)
    j = jnp.sum((t - prev)[:, None] > cs, axis=1).clip(0, group - 1)
    idx = (g * group + j.astype(jnp.int32))
    val = jnp.take_along_axis(sub, j[:, None], axis=1)[:, 0]
    return idx, val, tot


def choose_block_grouped(bs, key, group: int):
    """``choose_block`` by the two-level inverse CDF -- same categorical
    law, O(B/group + group) cumsum lanes per draw.  Used by the walk's
    resident-cache step where the flat (w, B) cumsum dominated."""
    u = jax.random.uniform(key, (bs.shape[0],))
    blk, val, tot = grouped_inverse_cdf(bs, u, group)
    return blk, val / tot


def level2_draw_grouped(kv, live, cols_c, u2, group: int):
    """``level2_draw`` by the two-level inverse CDF (same all-zero-row
    fallback to uniform-over-live)."""
    rowsum = kv.sum(axis=1)
    use = jnp.where((rowsum > 0.0)[:, None], kv, live.astype(jnp.float32))
    j, val, tot = grouped_inverse_cdf(use, u2, group)
    nb = jnp.take_along_axis(cols_c, j[:, None], axis=1)[:, 0]
    pin = val / jnp.maximum(tot, 1e-30)
    return nb, pin


def sample_from_sums(x, x_sq, views, src, bs, key, kind: str, inv_bw: float,
                     beta: float, block_size: int, n: int, pairwise=None):
    """One depth-2 draw from given level-1 sums ``bs`` of the ``src``
    frontier: (block draw -> exact level-2 row -> in-block draw), with the
    PR-2 key-split discipline (k_blk, k_in = split(key)).  Shared verbatim
    by ``ops._sample_core`` and the application oracles, so fused programs
    and their ref loops consume identical randomness."""
    k_blk, k_in = jax.random.split(key)
    blk, pb = choose_block(bs, k_blk)
    kv, live, cols_c = level2_row(x, x_sq, views, src, blk, kind, inv_bw,
                                  beta, block_size, n, pairwise)
    nb, pin = level2_draw(kv, live, cols_c,
                          jax.random.uniform(k_in, (src.shape[0],)))
    return nb, pb * pin


def masked_exact_sums_ref(q, x, x_sq, own, kind: str, inv_bw: float,
                          beta: float, bn: int, n: int, pairwise=None):
    """Masked level-1 sums on the *exact non-Pallas* path, matching
    ``ops._masked_block_sums(exact=True)`` bit-for-bit: one dense sweep over
    the unpadded dataset, zero-padded to a block multiple, own-block
    corrected by the self kernel k(x, x) = 1, floored."""
    m = q.shape[0]
    kv = kv_matrix(q, x, x_sq, kind, inv_bw, beta, pairwise)
    pad = -n % bn
    if pad:
        kv = jnp.pad(kv, ((0, 0), (0, pad)))
    bs = kv.reshape(m, -1, bn).sum(-1)
    corr = jnp.arange(bs.shape[1], dtype=jnp.int32)[None, :] == own[:, None]
    bs = jnp.where(corr, bs - 1.0, bs)
    return jnp.maximum(bs, BLOCK_SUM_FLOOR)


def degree_precedes(degs, a, b):
    """Degree-then-index total vertex order from Theorem 6.17's proof:
    a < b iff (deg_a, a) < (deg_b, b) lexicographically."""
    return (degs[a] < degs[b]) | ((degs[a] == degs[b]) & (a < b))


def noisy_power_ref(ksub, v0, keys, num_samples: int):
    """Oracle of ``ops.noisy_power_scan`` -- the BIMW21 noisy power method
    with the identical per-iteration math and key stream, as a host loop
    over the unrolled iterations instead of a ``lax.scan``.  Returns
    (Rayleigh quotient, final unit vector)."""
    t = ksub.shape[0]
    v = v0
    for i in range(keys.shape[0]):
        absv = jnp.abs(v)
        z = jnp.sum(absv)
        cdf = jnp.cumsum(absv)
        u = jax.random.uniform(keys[i], (num_samples,)) * jnp.maximum(z, 1e-30)
        idx = jnp.clip(jnp.searchsorted(cdf, u, side="right"),
                       0, t - 1).astype(jnp.int32)
        contrib = jnp.sign(v[idx]) * z / num_samples
        w = ksub[:, idx] @ contrib
        nw = jnp.linalg.norm(w)
        v = jnp.where((nw > 0.0) & (z > 0.0), w / jnp.maximum(nw, 1e-30), v)
    lam = v @ (ksub @ v)
    return lam, v


def laplacian_matvec_ref(src, dst, w, p, n: int):
    """Oracle of ``ops.laplacian_matvec``: L p = D p - A p via two
    segment-sum scatters over the COO edge list (the jnp transcription of
    ``SparseGraph.matvec``)."""
    av = jnp.zeros((n,), w.dtype).at[src].add(w * p[dst]).at[dst].add(
        w * p[src])
    deg = jnp.zeros((n,), w.dtype).at[src].add(w).at[dst].add(w)
    return deg * p - av


def triangle_batch_ref(x, x_sq, u, v, degs, keys, kind: str, inv_bw: float,
                       beta: float, block_size: int, n: int, pairwise=None):
    """Oracle of ``ops.triangle_edge_scan`` on its exact level-1 path:
    Theorem 6.17's per-edge estimator with the identical key discipline --
    degree-ordered orientation, ONE masked level-1 read of the v frontier
    (keys[0]), then one ``sample_from_sums`` neighbor draw per remaining
    key, validity mask ``v < w`` (degree order) and ``w != u``, and the
    in-program reweighting by deg(v) / num_draws."""
    views = block_views(x, x_sq, block_size)
    prec = degree_precedes(degs, u, v)
    uu = jnp.where(prec, u, v)
    vv = jnp.where(prec, v, u)
    kuv = kv_pairs(x[uu], x[vv], kind, inv_bw, beta, pairwise)
    bs = masked_exact_sums_ref(x[vv], x, x_sq,
                               (vv // block_size).astype(jnp.int32),
                               kind, inv_bw, beta, block_size, n, pairwise)
    acc = jnp.zeros_like(kuv)
    num_draws = keys.shape[0] - 1
    for i in range(1, keys.shape[0]):
        w, _ = sample_from_sums(x, x_sq, views, vv, bs, keys[i], kind,
                                inv_bw, beta, block_size, n, pairwise)
        valid = degree_precedes(degs, vv, w) & (w != uu)
        kuw = kv_pairs(x[uu], x[w], kind, inv_bw, beta, pairwise)
        acc = acc + jnp.where(valid, kuv * kuw, 0.0)
    return uu, vv, acc * degs[vv] / num_draws


def masked_block_sums_ref(q, x, x_sq, own, kind: str, inv_bw: float,
                          beta: float, bn: int, pairwise=None) -> jnp.ndarray:
    """(m, B) per-block sums over a padded dataset (n multiple of ``bn``;
    padding rows are far-offset so their kernel values are ~0), with
    k(x, x) = 1 subtracted from each query's own block and the result
    floored at BLOCK_SUM_FLOOR."""
    m, n = q.shape[0], x.shape[0]
    kv = kv_matrix(q, x, x_sq, kind, inv_bw, beta, pairwise)
    bs = kv.reshape(m, n // bn, bn).sum(-1)
    corr = jnp.arange(n // bn, dtype=jnp.int32)[None, :] == own[:, None]
    bs = jnp.where(corr, bs - 1.0, bs)
    return jnp.maximum(bs, BLOCK_SUM_FLOOR)


def sample_block_ref(q, x, x_sq, own, gumbel, kind: str, inv_bw: float,
                     beta: float, bn: int, pairwise=None):
    """Oracle for ``kernel.sample_block_pallas``: returns
    (blk, p_blk, tot, block_sums) with blk = argmax_b log(bs_b) + g_b."""
    bs = masked_block_sums_ref(q, x, x_sq, own, kind, inv_bw, beta, bn,
                               pairwise)
    score = jnp.log(bs) + gumbel
    blk = jnp.argmax(score, axis=1).astype(jnp.int32)
    tot = jnp.sum(bs, axis=1)
    pb = jnp.take_along_axis(bs, blk[:, None], axis=1)[:, 0] / tot
    return blk, pb, tot, bs


def sharded_masked_sums_ref(x_pad, x_sq_pad, src, key, kind: str,
                            inv_bw: float, beta: float, block_size: int,
                            blocks_per_shard: int, num_shards: int, n: int,
                            exact: bool = True, s: int = 16, pairwise=None):
    """Single-device oracle of ``sharded.ShardedBlocks._local_sums``,
    concatenated over shards: the §2-contract level-1 read on the padded
    ``P * shard_size`` layout -- own-block corrected, real blocks floored
    at 1e-12, all-sentinel blocks pinned to 0.  The stratified path
    replicates the per-shard ``fold_in(key, p)`` subsample key discipline
    (so shard-local draws match the device program bit-for-bit)."""
    w = src.shape[0]
    bs = block_size
    shard_size = blocks_per_shard * bs
    num_blocks_pad = num_shards * blocks_per_shard
    q = x_pad[src]
    if exact:
        kv = kv_matrix(q, x_pad, x_sq_pad, kind, inv_bw, beta, pairwise)
        sums = kv.reshape(w, num_blocks_pad, bs).sum(-1)
    else:
        parts = []
        for p in range(num_shards):
            kk = jax.random.fold_in(key, p)
            lo = p * shard_size
            base = jnp.arange(blocks_per_shard, dtype=jnp.int32) * bs
            u = jax.random.uniform(kk, (blocks_per_shard, bs))
            pos = base[:, None] + jnp.arange(bs, dtype=jnp.int32)[None, :]
            valid = (lo + pos) < n
            u = jnp.where(valid, u, jnp.inf)
            _, order = jax.lax.top_k(-u, s)
            idx = jnp.take_along_axis(pos, order, axis=1)
            sel_valid = jnp.take_along_axis(valid, order, axis=1)
            flat = lo + idx.reshape(-1)
            kv = kv_matrix(q, x_pad[flat], x_sq_pad[flat], kind, inv_bw,
                           beta, pairwise)
            kv = kv.reshape(w, blocks_per_shard, s) * sel_valid[None]
            sizes = jnp.clip(n - (lo + base), 0, bs).astype(jnp.float32)
            s_b = jnp.minimum(sizes, float(s))
            parts.append(kv.sum(-1)
                         * (sizes / jnp.maximum(s_b, 1.0))[None, :])
        sums = jnp.concatenate(parts, axis=1)
    own = (src // bs).astype(jnp.int32)
    corr = jnp.arange(num_blocks_pad, dtype=jnp.int32)[None, :] == own[:, None]
    sums = jnp.where(corr, sums - 1.0, sums)
    gbase = jnp.arange(num_blocks_pad, dtype=jnp.int32) * bs
    real = jnp.clip(n - gbase, 0, bs) > 0
    return jnp.where(real[None, :], jnp.maximum(sums, BLOCK_SUM_FLOOR), 0.0)


def sharded_sample_from_sums_ref(x_pad, x_sq_pad, views, src, sums, key,
                                 kind: str, inv_bw: float, beta: float,
                                 block_size: int, blocks_per_shard: int,
                                 n: int, pairwise=None):
    """Single-device oracle of the two-stage collective draw
    (``sharded.ShardedBlocks._local_draw``): hierarchical inverse-CDF over
    (shard totals -> owner's local block sums -> in-block columns) with
    the identical ``(k_shard, k_blk, k_in) = split(key, 3)`` discipline.
    Returns (nb, prob, total); ints match the device program bit-for-bit,
    floats to f32 tolerance."""
    w, num_blocks_pad = sums.shape
    num_shards = num_blocks_pad // blocks_per_shard
    k_shard, k_blk, k_in = jax.random.split(key, 3)
    by_shard = sums.reshape(w, num_shards, blocks_per_shard)
    t = jnp.cumsum(by_shard, axis=-1)[..., -1]            # (w, P)
    owner, tot = inverse_cdf_pick(t, jax.random.uniform(k_shard, (w,)))
    local = jnp.take_along_axis(by_shard, owner[:, None, None],
                                axis=1)[:, 0]             # (w, B_p)
    blk_l, _ = inverse_cdf_pick(local, jax.random.uniform(k_blk, (w,)))
    s_b = jnp.take_along_axis(local, blk_l[:, None], axis=1)[:, 0]
    gblk = (owner * blocks_per_shard).astype(jnp.int32) + blk_l
    kv, live, cols_c = level2_row(x_pad, x_sq_pad, views, src, gblk, kind,
                                  inv_bw, beta, block_size, n, pairwise)
    nb, pin = level2_draw(kv, live, cols_c,
                          jax.random.uniform(k_in, (w,)))
    return nb, s_b * pin / jnp.maximum(tot, 1e-30), tot


def sharded_fused_sample_ref(x_pad, x_sq_pad, src, key, kind: str,
                             inv_bw: float, beta: float, block_size: int,
                             blocks_per_shard: int, num_shards: int, n: int,
                             exact: bool = True, s: int = 16, pairwise=None):
    """Oracle of ``sharded.ShardedBlocks.fused_sample``: the §2 level-1
    read (``k_l1``) followed by the two-stage draw (``k_rest``) with the
    engine's ``k_l1, k_rest = split(key)`` discipline."""
    k_l1, k_rest = jax.random.split(key)
    sums = sharded_masked_sums_ref(x_pad, x_sq_pad, src, k_l1, kind, inv_bw,
                                   beta, block_size, blocks_per_shard,
                                   num_shards, n, exact=exact, s=s,
                                   pairwise=pairwise)
    views = block_views(x_pad, x_sq_pad, block_size)
    nb, prob, _ = sharded_sample_from_sums_ref(
        x_pad, x_sq_pad, views, src, sums, k_rest, kind, inv_bw, beta,
        block_size, blocks_per_shard, n, pairwise)
    return nb, prob, sums


def sharded_walk_ref(x_pad, x_sq_pad, starts, keys, kind: str, inv_bw: float,
                     beta: float, block_size: int, blocks_per_shard: int,
                     num_shards: int, n: int, exact: bool = True, s: int = 16,
                     pairwise=None):
    """Oracle of ``sharded.ShardedBlocks.walk_scan`` (rounds = 0): a host
    loop of per-step ``split -> level-1 read -> two-stage draw`` with the
    identical key stream; endpoints must match bit-for-bit."""
    cur = starts
    for i in range(keys.shape[0]):
        cur, _, _ = sharded_fused_sample_ref(
            x_pad, x_sq_pad, cur, keys[i], kind, inv_bw, beta, block_size,
            blocks_per_shard, num_shards, n, exact=exact, s=s,
            pairwise=pairwise)
    return cur


def fused_edge_batch_ref(x, x_sq, cdf, degs, inv_total, inv_t, key,
                         batch: int, kind: str, inv_bw: float, beta: float,
                         block_size: int, num_blocks: int, n: int,
                         pairwise=None):
    """Oracle of ``ops.fused_edge_batch`` on its Pallas (exact level-1)
    path: Algorithm 5.1 steps (a)-(d) for one batch, with the identical
    key-split discipline -- u ~ degrees by inverse CDF, v by Gumbel-max
    block draw + exact in-block draw, the collapsed reverse probability
    q(u | v) = k(u,v)/deg(v), and the reweighting
    ``k(u,v) / (t (p_u q_uv + p_v q_vu))``.

    The level-1 sums come from ``sample_block_ref`` (pure jnp) where the
    op runs the Pallas kernel; everything else is shared code, so
    interpret-mode runs of the op must reproduce (u, v) bit-for-bit and
    the floats to f32 tolerance."""
    from repro.kernels.kde_rowsum.ops import _PAD_OFFSET, _pad_rows
    views = block_views(x, x_sq, block_size)
    xp = _pad_rows(x, block_size, _PAD_OFFSET)
    xp_sq = jnp.sum(xp * xp, axis=-1)
    k_u, k_fwd = jax.random.split(key)
    u = inverse_cdf_index(cdf, jax.random.uniform(k_u, (batch,)))
    # forward draw v | u -- mirrors _fused_sample's Pallas branch
    _, k_rest = jax.random.split(k_fwd)
    k_g, k_in = jax.random.split(k_rest)
    g = jax.random.gumbel(k_g, (batch, num_blocks))
    blk, pb, _, _ = sample_block_ref(x[u], xp, xp_sq,
                                     (u // block_size).astype(jnp.int32), g,
                                     kind, inv_bw, beta, block_size, pairwise)
    kv, live, cols_c = level2_row(x, x_sq, views, u, blk, kind, inv_bw, beta,
                                  block_size, n, pairwise)
    v, pin = level2_draw(kv, live, cols_c,
                         jax.random.uniform(k_in, (batch,)))
    q_uv = pb * pin
    kuv = kv_pairs(x[u], x[v], kind, inv_bw, beta, pairwise)
    q_vu = kuv / jnp.maximum(degs[v], BLOCK_SUM_FLOOR)
    q_edge = inv_total * (degs[u] * q_uv + kuv)
    wgt = kuv * inv_t / jnp.maximum(q_edge, 1e-30)
    return u, v, wgt, q_uv, q_vu


# --------------------------------------------------------------------- #
# streaming patches (DESIGN.md §12)
# --------------------------------------------------------------------- #
def patch_block_sums_ref(bs, q, slots, old_x, new_x, kind: str,
                         inv_bw: float, beta: float, bn: int, pairwise=None):
    """Oracle of ``ops.patch_block_sums``: incremental §2 level-1 update.

    Subtracts the mutated slots' *old* kernel contributions from the
    cached (w, B) block sums and adds the *new* ones -- O(w m) evals for
    an m-row mutation batch instead of the O(w n) rebuild.  Sentinel
    coordinates (dead side of inserts/deletes) evaluate to exactly 0.0,
    so one delta formula covers insert, delete and update.  The stored
    sums are post-floor, so a block clamped at BLOCK_SUM_FLOOR cannot be
    un-clamped exactly; callers keep patched caches only while the §2
    floor is not binding (the consumer drops the cache when the frontier
    itself mutates).
    """
    old_sq = jnp.sum(old_x * old_x, axis=-1)
    new_sq = jnp.sum(new_x * new_x, axis=-1)
    kv_new = kv_matrix(q, new_x, new_sq, kind, inv_bw, beta, pairwise)
    kv_old = kv_matrix(q, old_x, old_sq, kind, inv_bw, beta, pairwise)
    blk = (slots // bn).astype(jnp.int32)
    out = bs.at[:, blk].add(kv_new - kv_old)
    return jnp.maximum(out, BLOCK_SUM_FLOOR)


def live_degrees_ref(x, x_sq, live, kind: str, inv_bw: float, beta: float,
                     pairwise=None):
    """Exact degrees of a live-masked padded dataset (the rebuild oracle
    for ``ops.degree_delta``): dead slots get degree 0 and contribute no
    mass; live rows get the usual Algorithm 4.3 row sum minus the self
    kernel k(x, x) = 1."""
    q = jnp.where(live[:, None], x, 0.0)     # dead-vs-dead would be NaN
    kv = kv_matrix(q, x, x_sq, kind, inv_bw, beta, pairwise)
    return jnp.where(live, kv.sum(axis=1) - 1.0, 0.0)


def degree_delta_ref(degs, x, x_sq, slots, old_x, new_x, old_live, new_live,
                     kind: str, inv_bw: float, beta: float, pairwise=None):
    """Oracle of ``ops.degree_delta``: O(n m) incremental degree update.

    ``x``/``x_sq`` are the *post-mutation* padded arrays.  Unmutated rows
    receive the exact column delta sum_j [k(x_i, new_j) - k(x_i, old_j)];
    the mutated slots' own degrees are recomputed exactly from their new
    rows (dead slots get 0).  Matches ``live_degrees_ref`` of the new
    dataset whenever ``degs`` matched it for the old one.
    """
    old_q = jnp.where(old_live[:, None], old_x, 0.0)
    new_q = jnp.where(new_live[:, None], new_x, 0.0)
    a_new = kv_matrix(new_q, x, x_sq, kind, inv_bw, beta, pairwise) \
        * new_live[:, None]
    a_old = kv_matrix(old_q, x, x_sq, kind, inv_bw, beta, pairwise) \
        * old_live[:, None]
    out = degs + (a_new - a_old).sum(axis=0)
    row_new = jnp.where(new_live, a_new.sum(axis=1) - 1.0, 0.0)
    return out.at[slots].set(row_new)
