"""Transformer building blocks (pure JAX, GSPMD-friendly).

All matmuls run in the config dtype with float32 accumulation.  Attention has
three implementations selected at call time:
  * "xla"    -- pure-jnp softmax attention (default; the dry-run path, which
                GSPMD can partition freely),
  * "flash"  -- the Pallas flash_attention kernel (TPU),
  * "kde"    -- the paper's sub-quadratic sampled decode attention
                (jnp mirror of the kde_attention kernel so GSPMD can shard
                 the 500k-token cache; kernel validated allclose in tests).
"""
from __future__ import annotations

import dataclasses
from contextlib import contextmanager
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig


_NEG_INF = -1.0e30

# ------------------------------------------------------------- activation
# sharding context: the launchers wrap tracing in ``activation_sharding`` so
# the model code can pin activation layouts (batch over ('pod','data'), TP
# dims over 'model') without threading the mesh through every call.  Without
# constraints GSPMD happily propagates *weight* shardings into the residual
# stream (feature-sharded activations + giant per-layer all-reduces).
_ACT = {"mesh": None, "batch_axes": (), "seq_mode": False}


@contextmanager
def activation_sharding(mesh, batch_axes=("data",), seq_mode: bool = False):
    """seq_mode=True: context parallelism -- activations shard the *sequence*
    dim over 'model' instead of TP dims (heads / d_ff).  Weights then behave
    FSDP-style (gathered per layer); attention queries are seq-sharded while
    keys/values are gathered.  Used for prefill cells whose head counts do
    not divide the TP axis (e.g. qwen2.5's 40 heads on TP16)."""
    old = dict(_ACT)
    _ACT.update(mesh=mesh, batch_axes=tuple(batch_axes), seq_mode=seq_mode)
    try:
        yield
    finally:
        _ACT.update(old)


def _axes_size(mesh, axes) -> int:
    n = 1
    for a in (axes if isinstance(axes, tuple) else (axes,)):
        n *= mesh.shape.get(a, 1)
    return n


def constrain(x, *tail):
    """with_sharding_constraint(P(batch_axes, *tail)) -- skipping any axis
    whose mesh extent does not divide the corresponding dim.

    In seq_mode the positional tail is overridden by arity: 3D activations
    (b, s, *) shard s over 'model'; 4D head tensors (b, h, s, hd) shard s."""
    mesh = _ACT["mesh"]
    if mesh is None:
        return x
    if _ACT["seq_mode"]:
        tail = ("model", None) if x.ndim == 3 else (None, "model", None)
    spec = [None] * x.ndim
    baxes = _ACT["batch_axes"]
    if baxes and x.shape[0] % _axes_size(mesh, baxes) == 0:
        spec[0] = baxes
    for i, s in enumerate(tail, start=1):
        if s is None or i >= x.ndim:
            continue
        if x.shape[i] % _axes_size(mesh, s) == 0:
            spec[i] = s
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*spec)))


def dtype_of(cfg: ArchConfig):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


# ------------------------------------------------------------------ init
def _dense_init(key, shape, scale=None):
    fan_in = shape[0]
    scale = scale if scale is not None else 1.0 / (fan_in ** 0.5)
    return (jax.random.normal(key, shape, jnp.float32) * scale)


def init_attention(key, cfg: ArchConfig) -> Dict[str, Any]:
    d, hq, hkv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.hd
    ks = jax.random.split(key, 4)
    p = {
        "wq": _dense_init(ks[0], (d, hq * hd)),
        "wk": _dense_init(ks[1], (d, hkv * hd)),
        "wv": _dense_init(ks[2], (d, hkv * hd)),
        "wo": _dense_init(ks[3], (hq * hd, d)),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((hq * hd,), jnp.float32)
        p["bk"] = jnp.zeros((hkv * hd,), jnp.float32)
        p["bv"] = jnp.zeros((hkv * hd,), jnp.float32)
    return p


def init_mlp(key, cfg: ArchConfig) -> Dict[str, Any]:
    d, f = cfg.d_model, cfg.d_ff
    if cfg.is_moe:
        ks = jax.random.split(key, 4)
        e = cfg.num_experts
        return {
            "router": _dense_init(ks[0], (d, e)),
            "w1": jax.vmap(lambda k: _dense_init(k, (d, f)))(
                jax.random.split(ks[1], e)),
            "w3": jax.vmap(lambda k: _dense_init(k, (d, f)))(
                jax.random.split(ks[2], e)),
            "w2": jax.vmap(lambda k: _dense_init(k, (f, d)))(
                jax.random.split(ks[3], e)),
        }
    ks = jax.random.split(key, 3)
    return {"w1": _dense_init(ks[0], (d, f)),
            "w3": _dense_init(ks[1], (d, f)),
            "w2": _dense_init(ks[2], (f, d))}


# ------------------------------------------------------------------ norms
import functools as _ft


@_ft.partial(jax.custom_vjp, nondiff_argnums=(2,))
def rmsnorm(x, gain, eps):
    return _rmsnorm_fwd_impl(x, gain, eps)


def _rmsnorm_fwd_impl(x, gain, eps):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * gain).astype(x.dtype)


def _rmsnorm_fwd(x, gain, eps):
    return _rmsnorm_fwd_impl(x, gain, eps), (x, gain)


def _rmsnorm_bwd(eps, res, g):
    """Grad math in f32, but the *returned* x-cotangent is cast back to
    x.dtype: without this the whole backward residual stream (and its TP
    all-reduces) silently runs in f32 -- 2x the collective bytes."""
    x, gain = res
    x32 = x.astype(jnp.float32)
    g32 = g.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = x32 * rstd
    dgain = jnp.sum(g32 * xhat, axis=tuple(range(x.ndim - 1)))
    gg = g32 * gain
    dx = rstd * (gg - xhat * jnp.mean(gg * xhat, axis=-1, keepdims=True))
    return dx.astype(x.dtype), dgain.astype(gain.dtype)


rmsnorm.defvjp(_rmsnorm_fwd, _rmsnorm_bwd)


# ------------------------------------------------------------------ rope
def rope_angles(positions, dim, base=10000.0):
    """positions (...,) -> cos/sin (..., dim/2)."""
    inv = 1.0 / (base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ang = positions[..., None].astype(jnp.float32) * inv
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, positions, style: str = "full"):
    """x (b, h, s, hd); positions (s,) or (b, s).

    style="full": rotate all head dims.  style="glm2d": ChatGLM's 2D RoPE --
    only the first half of the head dims is rotary, the rest pass through.
    """
    hd = x.shape[-1]
    rot = hd if style == "full" else hd // 2
    xr, xp = x[..., :rot], x[..., rot:]
    cos, sin = rope_angles(positions, rot)
    while cos.ndim < xr.ndim - 1:
        cos, sin = cos[None], sin[None]  # broadcast over b, h
    x1, x2 = xr[..., 0::2], xr[..., 1::2]
    o1 = x1 * cos - x2 * sin
    o2 = x2 * cos + x1 * sin
    out = jnp.stack([o1, o2], axis=-1).reshape(xr.shape).astype(x.dtype)
    return jnp.concatenate([out, xp], axis=-1) if rot < hd else out


# ------------------------------------------------------------------ attention
def _split_heads(x, nh, hd):
    b, s, _ = x.shape
    return x.reshape(b, s, nh, hd).transpose(0, 2, 1, 3)


def _merge_heads(x):
    b, h, s, hd = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, s, h * hd)


def _qkv(p, cfg: ArchConfig, x, positions):
    hq, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.hd
    q = x @ p["wq"].astype(x.dtype)
    k = x @ p["wk"].astype(x.dtype)
    v = x @ p["wv"].astype(x.dtype)
    if cfg.qkv_bias:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    q = constrain(_split_heads(q, hq, hd), "model", None, None)
    k = constrain(_split_heads(k, hkv, hd), "model", None, None)
    v = constrain(_split_heads(v, hkv, hd), "model", None, None)
    q = apply_rope(q, positions, cfg.rope_style)
    k = apply_rope(k, positions, cfg.rope_style)
    return q, k, v


def xla_attention(q, k, v, causal: bool, q_offset=0, kv_valid=None):
    """(b, hq, sq, hd) x (b, hkv, skv, hd) -> (b, hq, sq, hd), f32 softmax.

    GQA is expressed by *expanding* kv heads to hq before the einsums: under
    TP the expansion is a device-local gather (each device only materializes
    the kv copies its own q-heads need), whereas a (hkv, group) reshape
    would destroy the 'model' sharding of the head dim (hkv < mesh axis) and
    force GSPMD into full-score all-reduces.
    """
    b, hq, sq, hd = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    g = hq // hkv
    kk = constrain(jnp.repeat(k, g, axis=1), "model", None, None)
    vv = constrain(jnp.repeat(v, g, axis=1), "model", None, None)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   kk.astype(jnp.float32)) / (hd ** 0.5)
    kpos = jnp.arange(skv)
    mask = jnp.ones((sq, skv), bool)
    if kv_valid is not None:
        mask = mask & (kpos[None, :] < kv_valid)
    if causal:
        qpos = jnp.arange(sq)[:, None] + q_offset
        mask = mask & (kpos[None, :] <= qpos)
    s = jnp.where(mask[None, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, vv.astype(jnp.float32))
    return o.astype(q.dtype)


def xla_attention_chunked(q, k, v, causal: bool, q_offset=0, kv_valid=None,
                          chunk: int = 256):
    """Online-softmax attention scanned over KV chunks -- 'flash in XLA'.

    Peak score memory drops from O(sq * skv) to O(sq * chunk); used for
    long-sequence prefill where dense scores would exceed HBM (32k^2 f32
    scores per head = 4 GiB each).  Same math as the Pallas flash kernel.
    """
    b, hq, sq, hd = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    g = hq // hkv
    kk = constrain(jnp.repeat(k, g, axis=1), "model", None, None)
    vv = constrain(jnp.repeat(v, g, axis=1), "model", None, None)
    nc = (skv + chunk - 1) // chunk
    pad = nc * chunk - skv
    if pad:
        kk = jnp.pad(kk, ((0, 0), (0, 0), (0, pad), (0, 0)))
        vv = jnp.pad(vv, ((0, 0), (0, 0), (0, pad), (0, 0)))
    kc = kk.reshape(b, hq, nc, chunk, hd).transpose(2, 0, 1, 3, 4)
    vc = vv.reshape(b, hq, nc, chunk, hd).transpose(2, 0, 1, 3, 4)
    scale = 1.0 / (hd ** 0.5)
    qpos = jnp.arange(sq)[:, None] + q_offset
    q32 = q.astype(jnp.float32)

    def step(carry, inp):
        m, l, acc = carry
        kci, vci, ci = inp
        s = jnp.einsum("bhqd,bhkd->bhqk", q32, kci.astype(jnp.float32)) * scale
        kpos = ci * chunk + jnp.arange(chunk)[None, :]
        mask = kpos < (skv if kv_valid is None else kv_valid)
        if causal:
            mask = mask & (kpos <= qpos)
        s = jnp.where(mask[None, None], s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = l * alpha + p.sum(-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, vci.astype(jnp.float32))
        return (m_new, l, acc), None

    m0 = jnp.full((b, hq, sq), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hq, sq), jnp.float32)
    a0 = jnp.zeros((b, hq, sq, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0),
                                  (kc, vc, jnp.arange(nc)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


# sequences at or above this length use the chunked path (dense 32k^2
# scores would not fit HBM)
CHUNKED_ATTN_THRESHOLD = 8192


def kde_decode_attention_shardmap(q, k, v, kv_valid, top_p: int, bk: int,
                                  stride: int, mesh, baxes):
    """Distributed KDE decode attention under shard_map.

    The GSPMD mirror's weakness (measured on yi long_500k): the top-P block
    gather over a sequence-sharded cache forces a FULL cache all-gather per
    layer (~1 GiB).  Here each shard instead:
      1. computes strided block-lse estimates for its LOCAL cache slice,
      2. all-gathers only the (b, hq, nb) lse table (KBs),
      3. attends exactly over the selected blocks it OWNS,
      4. combines numerator/denominator (+ estimated residual mass) with one
         log-sum-exp psum -- the flash-decode decomposition.
    Per-layer collective bytes drop from ~cache-sized to ~KBs.

    q (b, hq, 1, hd); k, v (b, hkv, S, hd) with S sharded over
    ``seq_axes = baxes (+ 'model' when kv heads don't shard)``.
    """
    b, hq, _, hd = q.shape
    hkv, s_total = k.shape[1], k.shape[2]
    group = hq // hkv
    msize = mesh.shape.get("model", 1)
    heads_sharded = msize > 1 and hkv % msize == 0 and hkv >= msize
    seq_axes = tuple(baxes) if heads_sharded else tuple(baxes) + ("model",)
    nshards = _axes_size(mesh, seq_axes)
    if s_total % (bk * nshards) != 0:
        return None  # caller falls back to the GSPMD mirror
    scale = 1.0 / (hd ** 0.5)
    nb = s_total // bk

    def local(q_l, k_l, v_l):
        bq, hq_l, _, _ = q_l.shape
        hkv_l, s_loc = k_l.shape[1], k_l.shape[2]
        g_l = hq_l // hkv_l
        nb_loc = s_loc // bk
        # shard offset along the sequence
        idx = 0
        for ax in seq_axes:
            idx = idx * mesh.shape[ax] + jax.lax.axis_index(ax)
        seq_off = idx * s_loc

        q32 = q_l[:, :, 0, :].astype(jnp.float32)            # (b, hq_l, hd)
        kk = jnp.repeat(k_l, g_l, axis=1).astype(jnp.float32)
        vv = jnp.repeat(v_l, g_l, axis=1).astype(jnp.float32)

        # (1) local strided block-lse estimates
        ks = kk[:, :, ::stride, :]                           # (b,hq,s/stride,hd)
        sc = jnp.einsum("bhd,bhsd->bhs", q32, ks) * scale
        pos = seq_off + jnp.arange(0, s_loc, stride)
        sc = jnp.where(pos[None, None, :] < kv_valid, sc, _NEG_INF)
        sc = sc.reshape(bq, hq_l, nb_loc, -1)
        mloc = jnp.max(sc, axis=-1)
        lse_loc = mloc + jnp.log(jnp.maximum(
            jnp.sum(jnp.exp(sc - mloc[..., None]), -1), 1e-30)) \
            + jnp.log(float(stride))

        # (2) global lse table (tiny) + top-P selection per kv head
        lse = jax.lax.all_gather(lse_loc, seq_axes, axis=2, tiled=True)
        if heads_sharded:
            pass  # heads are local; each shard selects for its own heads
        e = lse.reshape(bq, hkv_l, g_l, -1)
        m_g = jnp.max(e, axis=2)
        lse_kv = m_g + jnp.log(jnp.maximum(
            jnp.sum(jnp.exp(e - m_g[:, :, None]), 2), 1e-30))  # (b,hkv,nb)
        _, sel = jax.lax.top_k(lse_kv, top_p)                  # (b,hkv,P)

        # (3) exact attention over the selected blocks THIS shard owns
        my_first = seq_off // bk
        sel_local = sel - my_first
        owned = (sel_local >= 0) & (sel_local < nb_loc)        # (b,hkv,P)
        sel_c = jnp.clip(sel_local, 0, nb_loc - 1)
        kb = k_l.reshape(bq, hkv_l, nb_loc, bk, hd)
        vb = v_l.reshape(bq, hkv_l, nb_loc, bk, hd)
        ksel = jnp.take_along_axis(kb, sel_c[:, :, :, None, None], axis=2)
        vsel = jnp.take_along_axis(vb, sel_c[:, :, :, None, None], axis=2)
        ksel = jnp.repeat(ksel, g_l, axis=1).astype(jnp.float32)
        vsel = jnp.repeat(vsel, g_l, axis=1).astype(jnp.float32)
        sc2 = jnp.einsum("bhd,bhpkd->bhpk", q32, ksel) * scale
        kpos = (seq_off + sel_c[:, :, :, None] * bk
                + jnp.arange(bk)[None, None, None, :])         # (b,hkv,P,bk)
        valid = (kpos < kv_valid) & owned[..., None]
        valid = jnp.repeat(valid, g_l, axis=1)
        sc2 = jnp.where(valid, sc2, _NEG_INF)

        # (4) combine with a fixed global reference (pmax) + psum
        m_ref = jax.lax.pmax(jnp.max(sc2, axis=(2, 3)), seq_axes)  # (b, hq)
        p = jnp.exp(sc2 - m_ref[..., None, None])
        l_loc = p.sum((2, 3))
        acc_loc = jnp.einsum("bhpk,bhpkd->bhd", p, vsel)
        # residual: local unselected blocks' estimated mass
        sel_q = jnp.repeat(sel, g_l, axis=1) - my_first        # (b,hq,P)
        chosen = jnp.any(
            jnp.arange(nb_loc)[None, None, :, None] == sel_q[:, :, None, :],
            axis=-1)                                           # (b,hq,nb_loc)
        resid_loc = jnp.where(chosen, 0.0,
                              jnp.exp(lse_loc - m_ref[..., None])).sum(-1)
        l = jax.lax.psum(l_loc, seq_axes)
        acc = jax.lax.psum(acc_loc, seq_axes)
        resid = jax.lax.psum(resid_loc, seq_axes)
        out = acc / jnp.maximum(l + resid, 1e-30)[..., None]
        return out[:, :, None, :].astype(q_l.dtype)

    hspec = "model" if heads_sharded else None
    shmap = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(None, hspec, None, None),
                  P(None, hspec, seq_axes, None),
                  P(None, hspec, seq_axes, None)),
        out_specs=P(None, hspec, None, None),
        check_vma=False,
    )
    return shmap(q, k, v)


def kde_decode_attention(q, k, v, kv_valid, top_p: int, bk: int,
                         stride: int):
    """jnp mirror of the kde_attention kernel, GSPMD-shardable.

    q (b, hq, 1, hd) single decode step; k, v (b, hkv, S, hd)."""
    from repro.kernels.kde_attention.ref import kde_attention_ref
    assert k.shape[2] % bk == 0, (
        f"KDE attention needs cache length {k.shape[2]} to be a multiple of "
        f"the block size {bk} -- allocate the cache rounded up to bk")
    out = kde_attention_ref(q[:, :, 0, :], k, v, top_p=top_p, bk=bk,
                            stride=stride, kv_valid=kv_valid)
    return out[:, :, None, :]


def attention_block(p, cfg: ArchConfig, x, positions, impl: str = "xla",
                    cache: Optional[Tuple] = None, cache_pos=None,
                    kde_cfg: Optional[Dict] = None):
    """Returns (out (b, s, d), new_cache)."""
    q, k, v = _qkv(p, cfg, x, positions)
    if cache is None:
        if impl == "flash":
            from repro.kernels.flash_attention.ops import flash_attention
            o = flash_attention(q, k, v, True)
        elif q.shape[2] >= CHUNKED_ATTN_THRESHOLD:
            # long prefill: dense S^2 scores would blow HBM
            o = xla_attention_chunked(q, k, v, causal=True)
        else:
            o = xla_attention(q, k, v, causal=True)
        new_cache = None
    else:
        ck, cv = cache                       # (b, hkv, S, hd)
        ck = jax.lax.dynamic_update_slice_in_dim(ck, k.astype(ck.dtype), cache_pos, axis=2)
        cv = jax.lax.dynamic_update_slice_in_dim(cv, v.astype(cv.dtype), cache_pos, axis=2)
        kv_valid = cache_pos + q.shape[2]
        if impl == "kde" and q.shape[2] == 1:
            kc = kde_cfg or {}
            o = None
            if _ACT["mesh"] is not None:
                o = kde_decode_attention_shardmap(
                    q, ck, cv, kv_valid, top_p=kc.get("top_p", 16),
                    bk=kc.get("bk", 512), stride=kc.get("stride", 16),
                    mesh=_ACT["mesh"], baxes=_ACT["batch_axes"])
            if o is None:
                o = kde_decode_attention(q, ck, cv, kv_valid,
                                         top_p=kc.get("top_p", 16),
                                         bk=kc.get("bk", 512),
                                         stride=kc.get("stride", 16))
        else:
            o = xla_attention(q, ck, cv, causal=True,
                              q_offset=cache_pos, kv_valid=kv_valid)
        new_cache = (ck, cv)
    out = constrain(_merge_heads(o) @ p["wo"].astype(x.dtype), None, None)
    return out, new_cache


def cross_attention_block(p, cfg: ArchConfig, x, memory):
    """Encoder-decoder cross attention (no rope on memory keys)."""
    hq, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.hd
    q = _split_heads(x @ p["wq"].astype(x.dtype), hq, hd)
    k = _split_heads(memory @ p["wk"].astype(x.dtype), hkv, hd)
    v = _split_heads(memory @ p["wv"].astype(x.dtype), hkv, hd)
    o = xla_attention(q, k, v, causal=False)
    return _merge_heads(o) @ p["wo"].astype(x.dtype)


# ------------------------------------------------------------------ mlp
def swiglu(p, x):
    h = jax.nn.silu(x @ p["w1"].astype(x.dtype)) * (x @ p["w3"].astype(x.dtype))
    h = constrain(h, None, "model")
    return h @ p["w2"].astype(x.dtype)


def moe_block_dense(p, cfg: ArchConfig, x):
    """Reference top-k MoE: every expert runs on every token, outputs
    combined by the gate matrix.  O(e) cost -- test oracle only."""
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.experts_per_token
    logits = x.astype(jnp.float32) @ p["router"].astype(jnp.float32)  # (b,s,e)
    gates, idx = jax.lax.top_k(logits, k)                             # (b,s,k)
    gates = jax.nn.softmax(gates, axis=-1)
    onehot = jax.nn.one_hot(idx, e, dtype=jnp.float32)                # (b,s,k,e)
    combine = (gates[..., None] * onehot).sum(2).astype(x.dtype)      # (b,s,e)

    def expert_apply(w1, w3, w2):
        h = jax.nn.silu(jnp.einsum("bsd,df->bsf", x, w1.astype(x.dtype)))
        h = h * jnp.einsum("bsd,df->bsf", x, w3.astype(x.dtype))
        return jnp.einsum("bsf,fd->bsd", h, w2.astype(x.dtype))

    outs = jax.vmap(expert_apply)(p["w1"], p["w3"], p["w2"])          # (e,b,s,d)
    out = jnp.einsum("ebsd,bse->bsd", outs, combine)
    aux = _load_balance_loss(logits, idx, e)
    return out, aux


def moe_block(p, cfg: ArchConfig, x, capacity_factor: float = 1.25):
    """Top-k MoE dispatcher: shard_map expert parallelism when a mesh with a
    divisible 'model' axis is active (one output psum per layer -- see
    _moe_block_shardmap), else the GSPMD scatter/gather fallback."""
    mesh = _ACT["mesh"]
    if (mesh is not None and "model" in mesh.shape
            and cfg.num_experts % mesh.shape["model"] == 0
            and not _ACT["seq_mode"]
            and x.shape[0] % _axes_size(mesh, _ACT["batch_axes"]) == 0):
        return _moe_block_shardmap(p, cfg, x, mesh, _ACT["batch_axes"],
                                   capacity_factor)
    return _moe_block_gspmd(p, cfg, x, capacity_factor)


def _moe_block_shardmap(p, cfg: ArchConfig, x, mesh, baxes,
                        capacity_factor: float = 1.25):
    """Expert-parallel MoE under shard_map: each 'model' shard owns
    e/msize experts, routes the (replicated-over-'model') tokens to its own
    experts only, and the outputs combine with ONE psum of (b_loc, s, d).

    vs the GSPMD fallback, which materializes all-expert buffers and
    all-gathers ~e*cap*d per layer: measured 5.4 GB -> 0.5 GB per layer on
    qwen3-moe train_4k (EXPERIMENTS.md §Perf).
    """
    e, topk = cfg.num_experts, cfg.experts_per_token
    msize = mesh.shape["model"]
    e_loc = e // msize
    b, s, d = x.shape
    cap = max(int(capacity_factor * s * topk / e), 1)

    def local(x_loc, router, w1, w3, w2):
        bl = x_loc.shape[0]
        router_full = jax.lax.all_gather(router.astype(jnp.float32),
                                         "model", axis=1, tiled=True)
        logits = x_loc.astype(jnp.float32) @ router_full      # (bl, s, e)
        gates, idx = jax.lax.top_k(logits, topk)
        gates = jax.nn.softmax(gates, axis=-1)
        eid = idx.reshape(bl, s * topk)
        gate = gates.reshape(bl, s * topk).astype(x_loc.dtype)
        onehot = jax.nn.one_hot(eid, e, dtype=jnp.int32)
        slot = (jnp.cumsum(onehot, axis=1) * onehot).max(-1) - 1
        keep = (slot >= 0) & (slot < cap)
        off = jax.lax.axis_index("model") * e_loc
        el = eid - off
        mine = keep & (el >= 0) & (el < e_loc)
        el_c = jnp.clip(el, 0, e_loc - 1)
        slot_c = jnp.clip(slot, 0, cap - 1)
        x_rep = jnp.repeat(x_loc, topk, axis=1)

        def scatter(xg, eg, sg, mg):
            buf = jnp.zeros((e_loc, cap, d), x_loc.dtype)
            return buf.at[eg, sg].add(xg * mg[:, None].astype(x_loc.dtype))

        buf = jax.vmap(scatter)(x_rep, el_c, slot_c, mine)     # (bl,e_loc,cap,d)
        h = jax.nn.silu(jnp.einsum("becd,edf->becf", buf,
                                   w1.astype(x_loc.dtype)))
        h = h * jnp.einsum("becd,edf->becf", buf, w3.astype(x_loc.dtype))
        yb = jnp.einsum("becf,efd->becd", h, w2.astype(x_loc.dtype))

        def gather(ybg, eg, sg, mg, gg):
            return ybg[eg, sg] * (mg.astype(x_loc.dtype) * gg)[:, None]

        y = jax.vmap(gather)(yb, el_c, slot_c, mine, gate)
        y = y.reshape(bl, s, topk, d).sum(2)
        y = jax.lax.psum(y, "model")                           # THE combine
        # aux loss: fractions must be averaged over the GLOBAL batch before
        # the product (aux is nonlinear in the per-shard means)
        probs = jax.nn.softmax(logits, axis=-1)
        frac_tokens = jnp.mean(
            jax.nn.one_hot(idx[..., 0], e, dtype=jnp.float32), axis=(0, 1))
        frac_probs = jnp.mean(probs, axis=(0, 1)).astype(jnp.float32)
        if baxes:
            frac_tokens = jax.lax.pmean(frac_tokens, tuple(baxes))
            frac_probs = jax.lax.pmean(frac_probs, tuple(baxes))
        aux = e * jnp.sum(frac_tokens * frac_probs)
        return y, aux

    shmap = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(baxes, None, None), P(None, "model"),
                  P("model", None, None), P("model", None, None),
                  P("model", None, None)),
        out_specs=(P(baxes, None, None), P()),
        check_vma=False,
    )
    return shmap(x, p["router"], p["w1"], p["w3"], p["w2"])


def _moe_block_gspmd(p, cfg: ArchConfig, x, capacity_factor: float = 1.25):
    """Production top-k MoE: grouped capacity dispatch via scatter/gather.

    Tokens are grouped along the batch dim (groups align with the 'data'
    sharding, so slotting stays device-local); each group scatters its
    routed tokens into (e, cap) expert buffers, experts matmul on the
    buffers (sharded over 'model' -> expert parallelism), and a gather
    combines.  FLOPs scale with top-k (cap ~ s*k/e), not with num_experts;
    dropped tokens (over capacity) pass through the residual, standard
    Switch behavior.
    """
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.experts_per_token
    cap = max(int(capacity_factor * s * k / e), 1)
    logits = x.astype(jnp.float32) @ p["router"].astype(jnp.float32)  # (b,s,e)
    gates, idx = jax.lax.top_k(logits, k)                 # (b, s, k)
    gates = jax.nn.softmax(gates, axis=-1)

    eid = idx.reshape(b, s * k)                           # expert per slot-req
    gate = gates.reshape(b, s * k).astype(x.dtype)
    onehot = jax.nn.one_hot(eid, e, dtype=jnp.int32)      # (b, s*k, e)
    slot = (jnp.cumsum(onehot, axis=1) * onehot).max(-1) - 1   # (b, s*k)
    keep = (slot >= 0) & (slot < cap)
    slot_c = jnp.clip(slot, 0, cap - 1)
    x_rep = jnp.repeat(x, k, axis=1)                      # (b, s*k, d)

    def scatter_group(xg, eg, sg, kg):
        buf = jnp.zeros((e, cap, d), x.dtype)
        return buf.at[eg, sg].add(xg * kg[:, None].astype(x.dtype))

    buf = jax.vmap(scatter_group)(x_rep, eid, slot_c, keep)  # (b, e, cap, d)
    buf = constrain(buf, "model", None, None)

    h = jax.nn.silu(jnp.einsum("becd,edf->becf", buf, p["w1"].astype(x.dtype)))
    h = h * jnp.einsum("becd,edf->becf", buf, p["w3"].astype(x.dtype))
    yb = jnp.einsum("becf,efd->becd", h, p["w2"].astype(x.dtype))

    def gather_group(ybg, eg, sg, kg, gg):
        return ybg[eg, sg] * (kg.astype(x.dtype) * gg)[:, None]

    y = jax.vmap(gather_group)(yb, eid, slot_c, keep, gate)  # (b, s*k, d)
    y = y.reshape(b, s, k, d).sum(2)
    aux = _load_balance_loss(logits, idx, e)
    return y, aux


def _load_balance_loss(logits, idx, e):
    """Switch-style aux loss: e * sum_i f_i * p_i."""
    probs = jax.nn.softmax(logits, axis=-1)
    frac_tokens = jnp.mean(
        jax.nn.one_hot(idx[..., 0], e, dtype=jnp.float32), axis=(0, 1))
    frac_probs = jnp.mean(probs, axis=(0, 1)).astype(jnp.float32)
    return e * jnp.sum(frac_tokens * frac_probs)
