"""jit'd wrapper for flash attention with padding + custom_vjp.

Forward = Pallas kernel (on TPU; interpret on CPU).  Backward recomputes
attention with the jnp reference and differentiates through it (flash
backward recomputation strategy; the fwd memory win is what matters for
training, the bwd is standard rematerialization).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import platform as _platform
from repro.kernels.flash_attention import kernel as _k
from repro.kernels.flash_attention import ref as _ref


def _pad_seq(a, mult, axis):
    s = a.shape[axis]
    rem = (-s) % mult
    if rem == 0:
        return a
    pad = [(0, 0)] * a.ndim
    pad[axis] = (0, rem)
    return jnp.pad(a, pad)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, causal=True, bq=128, bk=128, interpret=None,
                    with_lse=False):
    out, lse = _fwd_impl(q, k, v, causal, bq, bk, interpret)
    return (out, lse) if with_lse else out


def _fwd_impl(q, k, v, causal, bq, bk, interpret):
    interpret = _platform.interpret_mode(interpret)
    b, hq, sq, dh = q.shape
    skv = k.shape[2]
    scale = 1.0 / (dh ** 0.5)
    bq_ = min(bq, max(_next_mult(sq), 8))
    bk_ = min(bk, max(_next_mult(skv), 8))
    qp = _pad_seq(q, bq_, 2)
    kp = _pad_seq(k, bk_, 2)
    vp = _pad_seq(v, bk_, 2)
    out, lse = _k.flash_attention_pallas(
        qp, kp, vp, causal=causal, scale=scale, kv_valid=skv,
        bq=bq_, bk=bk_, interpret=interpret)
    return out[:, :, :sq], lse[:, :, :sq]


def _next_mult(s, base=128):
    return base if s >= base else 1 << max(s - 1, 0).bit_length()


def _fwd(q, k, v, causal, bq, bk, interpret, with_lse):
    out, lse = _fwd_impl(q, k, v, causal, bq, bk, interpret)
    res = (q, k, v)
    return ((out, lse) if with_lse else out), res


def _bwd(causal, bq, bk, interpret, with_lse, res, g):
    q, k, v = res
    scale = 1.0 / (q.shape[-1] ** 0.5)

    def f(q, k, v):
        out, lse = _ref.attention_ref(q, k, v, causal=causal, scale=scale)
        return (out, lse) if with_lse else out

    _, vjp = jax.vjp(f, q, k, v)
    return vjp(g)


flash_attention.defvjp(_fwd, _bwd)

attention_ref = _ref.attention_ref
