"""Sub-quadratic KDE decode attention -- the paper's technique as a serving
feature (DESIGN.md §3).

Pipeline (one decode step, KV cache of length S):
  1. level-1 Pallas sweep: per-key-block strided-subsample lse estimates
     (cost S/stride per head instead of S);
  2. top-P block selection per kv-head (GQA group consensus);
  3. exact flash attention over the P gathered blocks (cost P*bk per head);
  4. denominator correction: the *estimated* residual mass of the unselected
     blocks enters the softmax normalizer -- the KDE row-sum estimate of the
     attention kernel matrix.

Total cost per step: O(S/stride + P*bk) vs O(S) exact -- sub-quadratic
end-to-end decode for S >> P*bk, with multiplicative-error mass coverage
controlled by (stride, P) exactly like (eps, tau) in Definition 1.1.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import ops as flash_ops
from repro.kernels import platform as _platform
from repro.kernels.kde_attention import kernel as _k
from repro.kernels.kde_attention import ref as _ref

_NEG_INF = -1.0e30


@functools.partial(jax.jit,
                   static_argnames=("top_p", "bk", "stride", "kv_valid",
                                    "interpret"))
def kde_attention(q, k, v, *, top_p: int, bk: int = 256, stride: int = 8,
                  kv_valid: int | None = None,
                  interpret: bool | None = None) -> jnp.ndarray:
    """q (b, hq, dh); k, v (b, hkv, S, dh) -> (b, hq, dh).  S % bk == 0."""
    interpret = _platform.interpret_mode(interpret)
    b, hq, dh = q.shape
    hkv, s = k.shape[1], k.shape[2]
    group = hq // hkv
    nb = s // bk
    top_p = min(top_p, nb)
    scale = 1.0 / (dh ** 0.5)
    kv_valid = s if kv_valid is None else kv_valid

    # (1) level-1 KDE estimates per block
    est = _k.block_lse_pallas(q, k, scale=scale, stride=stride,
                              kv_valid=kv_valid, bk=bk, interpret=interpret)

    # (2) block selection (shared within each GQA group)
    est_kv = _ref._group_lse(est, group)                  # (b, hkv, nb)
    _, sel = jax.lax.top_k(est_kv, top_p)                 # (b, hkv, P)

    # (3) gather + exact attention over selected blocks
    elem = (sel[..., None] * bk + jnp.arange(bk)).reshape(b, hkv, -1)
    kg = jnp.take_along_axis(k, elem[..., None], axis=2)  # (b, hkv, P*bk, dh)
    vg = jnp.take_along_axis(v, elem[..., None], axis=2)
    # treat the GQA group as the query axis; non-causal over gathered keys
    qg = q.reshape(b, hkv, group, dh)
    # mask out-of-range gathered keys by pushing their scores to -inf via
    # a large negative value bias: zero keys would alias position 0, so we
    # instead mask through kv_valid positions folded into the gather.
    valid = (elem < kv_valid)                             # (b, hkv, P*bk)
    kg = jnp.where(valid[..., None], kg, 0.0)
    vg = jnp.where(valid[..., None], vg, 0.0)
    sc = jnp.einsum("bhgd,bhsd->bhgs", qg.astype(jnp.float32),
                    kg.astype(jnp.float32)) * scale
    sc = jnp.where(valid[:, :, None, :], sc, _NEG_INF)
    m = jnp.max(sc, axis=-1, keepdims=True)
    p = jnp.exp(sc - m)
    l_sel = p.sum(-1)                                     # (b, hkv, g)
    out = jnp.einsum("bhgs,bhsd->bhgd", p, vg.astype(jnp.float32))
    out = out / jnp.maximum(l_sel, 1e-30)[..., None]

    # (4) denominator correction with the estimated residual mass
    sel_q = jnp.repeat(sel, group, axis=1)                # (b, hq, P)
    chosen = jnp.any(jnp.arange(nb)[None, None, :, None] ==
                     sel_q[:, :, None, :], axis=-1)       # (b, hq, nb)
    est_resid = jnp.where(chosen, _NEG_INF, est)
    m_q = m.reshape(b, hq, 1)
    resid_mass = jnp.exp(est_resid - m_q).sum(-1)         # (b, hq)
    l_q = l_sel.reshape(b, hq)
    frac = l_q / jnp.maximum(l_q + resid_mass, 1e-30)
    out = out.reshape(b, hq, dh) * frac[..., None]
    return out.astype(q.dtype)


exact_decode_attention = _ref.exact_decode_attention
kde_attention_ref = _ref.kde_attention_ref
