"""Per-kernel shape/dtype sweeps: Pallas (interpret=True on CPU) vs the
pure-jnp oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.kernels_fn import exponential, gaussian, laplacian
from repro.kernels.flash_attention import ops as fa
from repro.kernels.kde_attention import ops as ka
from repro.kernels.kde_rowsum import ops as rs
from repro.kernels.kde_sampler import kernel as sk
from repro.kernels.kde_sampler import ops as sops
from repro.kernels.kde_sampler import ref as sref

RNG = np.random.default_rng(0)


# --------------------------------------------------------------- kde_rowsum
@pytest.mark.parametrize("kind,ker", [
    ("gaussian", gaussian(1.3)), ("exponential", exponential(0.7)),
    ("laplacian", laplacian(2.0))])
@pytest.mark.parametrize("m,n,d", [(5, 64, 3), (37, 301, 19), (128, 512, 64)])
def test_kde_rowsum_sweep(kind, ker, m, n, d):
    q = RNG.normal(0, 0.5, (m, d)).astype(np.float32)
    x = RNG.normal(0, 0.5, (n, d)).astype(np.float32)
    out = rs.kde_rowsum(q, x, ker, bm=32, bn=128, interpret=True)
    ref = rs.rowsum_ref(jnp.asarray(q), jnp.asarray(x), kind,
                        1.0 / ker.bandwidth)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=1e-5)


def test_kde_blocksum():
    ker = gaussian(1.0)
    q = RNG.normal(0, 0.5, (17, 8)).astype(np.float32)
    x = RNG.normal(0, 0.5, (256, 8)).astype(np.float32)
    out = rs.kde_blocksum(q, x, ker, bm=16, bn=64, interpret=True)
    ref = rs.blocksum_ref(jnp.asarray(q), jnp.asarray(x), "gaussian", 1.0,
                          bn=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4)


# -------------------------------------------------------------- kde_sampler
@pytest.mark.parametrize("kind,ker", [
    ("gaussian", gaussian(1.3)), ("exponential", exponential(0.7)),
    ("laplacian", laplacian(2.0))])
@pytest.mark.parametrize("m,n,d,bn,bm", [(16, 128, 4, 32, 8),
                                         (32, 256, 8, 64, 16)])
def test_kde_sampler_block_vs_ref(kind, ker, m, n, d, bn, bm):
    """The fused level-1 Pallas kernel (masked block sums + in-pass
    Gumbel-max block draw) agrees with the jnp oracle on every output."""
    q = jnp.asarray(RNG.normal(0, 0.5, (m, d)).astype(np.float32))
    x = jnp.asarray(RNG.normal(0, 0.5, (n, d)).astype(np.float32))
    own = jnp.asarray(RNG.integers(-1, n // bn, m).astype(np.int32))[:, None]
    g = jnp.asarray(RNG.gumbel(size=(m, n // bn)).astype(np.float32))
    inv_bw = 1.0 / ker.bandwidth
    blk, pb, tot, bs = sk.sample_block_pallas(q, x, own, g, kind, inv_bw,
                                              1.0, bm=bm, bn=bn,
                                              interpret=True)
    x_sq = jnp.sum(x * x, axis=-1)
    rblk, rpb, rtot, rbs = sref.sample_block_ref(q, x, x_sq, own[:, 0], g,
                                                 kind, inv_bw, 1.0, bn,
                                                 ker.pairwise)
    np.testing.assert_array_equal(np.asarray(blk), np.asarray(rblk))
    np.testing.assert_allclose(np.asarray(bs), np.asarray(rbs), rtol=2e-4,
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(pb), np.asarray(rpb), rtol=2e-4)
    np.testing.assert_allclose(np.asarray(tot), np.asarray(rtot), rtol=2e-4)


@pytest.mark.parametrize("kind,ker", [
    ("gaussian", gaussian(1.3)), ("laplacian", laplacian(2.0))])
def test_kde_sampler_masked_blocksum_vs_ref(kind, ker):
    """The Gumbel-free masked-blocksum Pallas kernel (the level-1 read of
    prob_of / sample_exact / exact walks on TPU) agrees with the jnp
    oracle."""
    m, n, d, bn, bm = 32, 256, 6, 64, 16
    q = jnp.asarray(RNG.normal(0, 0.5, (m, d)).astype(np.float32))
    x = jnp.asarray(RNG.normal(0, 0.5, (n, d)).astype(np.float32))
    own = jnp.asarray(RNG.integers(-1, n // bn, m).astype(np.int32))[:, None]
    inv_bw = 1.0 / ker.bandwidth
    bs = sk.masked_blocksum_pallas(q, x, own, kind, inv_bw, 1.0, bm=bm,
                                   bn=bn, interpret=True)
    x_sq = jnp.sum(x * x, axis=-1)
    ref = sref.masked_block_sums_ref(q, x, x_sq, own[:, 0], kind, inv_bw,
                                     1.0, bn, ker.pairwise)
    np.testing.assert_allclose(np.asarray(bs), np.asarray(ref), rtol=2e-4,
                               atol=1e-6)


def test_kde_sampler_fused_pallas_engine_law():
    """End-to-end sampler with the Pallas level-1 (interpret mode): the
    neighbor distribution matches the exact k(u, v)/deg(u) law and matches
    the jnp engine.  (The two paths use different categorical samplers --
    Gumbel-max streamed in-kernel vs inverse-CDF -- so streams differ but
    the law must not.)"""
    from repro.core.sampling.edge import NeighborSampler
    x = RNG.normal(0, 0.5, (300, 5)).astype(np.float32)
    ker = gaussian(1.5)
    k = np.asarray(ker.matrix(jnp.asarray(x)), np.float64)
    src = 13
    row = k[src].copy()
    row[src] = 0
    p = row / row.sum()
    reps = 6000
    a = NeighborSampler(x, ker, exact_blocks=True, seed=7, use_pallas=True,
                        interpret=True)
    va, pa = a.sample(np.full(reps, src))
    emp = np.bincount(va, minlength=len(p)) / reps
    assert 0.5 * np.abs(emp - p).sum() < 3.0 * np.sqrt(len(p) / reps)
    # realized probabilities are the exact law (level-1 reads are exact)
    np.testing.assert_allclose(pa, p[va], rtol=1e-3, atol=1e-9)


def test_kde_sampler_stratified_tail_block_unbiased():
    """Padding-bias regression: with a tail block smaller than
    samples_per_block, the stratified estimate of the tail sum must stay
    unbiased (the seed summed duplicated pad indices into it)."""
    rng = np.random.default_rng(5)
    n, d, bn, s = 5 * 128 + 40, 6, 128, 64        # tail size 40 < s = 64
    x = jnp.asarray(rng.normal(0, 0.5, (n, d)).astype(np.float32))
    x_sq = jnp.sum(x * x, axis=-1)
    ker = gaussian(2.0)
    y = x[:4]
    cfg = dict(kind="gaussian", inv_bw=0.5, beta=1.0, pairwise=ker.pairwise,
               block_size=bn, num_blocks=6, n=n)
    exact = np.asarray(sops.exact_block_sums(y, x, x_sq, **cfg)[0])
    reps = 300
    keys = jax.random.split(jax.random.PRNGKey(0), reps)
    est = np.stack([np.asarray(sops.stratified_block_sums(y, x, x_sq, k,
                                                          s=s, **cfg)[0])
                    for k in keys]).mean(0)
    # the tail block (last column) is exact when s >= tail size; all blocks
    # must match the exact sums in expectation
    np.testing.assert_allclose(est[:, -1], exact[:, -1], rtol=1e-3)
    np.testing.assert_allclose(est, exact, rtol=0.05)


# ----------------------------------------------------------- flash attention
@pytest.mark.parametrize("b,hq,hkv,sq,skv,dh", [
    (2, 4, 2, 64, 64, 32),       # GQA, square causal
    (1, 8, 2, 1, 300, 64),       # decode: 1 query vs long cache
    (2, 4, 4, 100, 228, 16),     # MHA, ragged shapes
    (1, 2, 1, 17, 17, 8),        # tiny odd
])
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_flash_attention_sweep(b, hq, hkv, sq, skv, dh, dtype):
    q = RNG.normal(0, 1, (b, hq, sq, dh)).astype(dtype)
    k = RNG.normal(0, 1, (b, hkv, skv, dh)).astype(dtype)
    v = RNG.normal(0, 1, (b, hkv, skv, dh)).astype(dtype)
    out = fa.flash_attention(q, k, v, True, 64, 64, True, False)
    ref, _ = fa.attention_ref(q, k, v, causal=True, scale=1 / np.sqrt(dh))
    tol = 2e-5 if dtype == np.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol)


def test_flash_attention_grads():
    b, hq, hkv, sq, skv, dh = 2, 4, 2, 48, 48, 16
    q = RNG.normal(0, 1, (b, hq, sq, dh)).astype(np.float32)
    k = RNG.normal(0, 1, (b, hkv, skv, dh)).astype(np.float32)
    v = RNG.normal(0, 1, (b, hkv, skv, dh)).astype(np.float32)

    def loss_k(q, k, v):
        return jnp.sum(fa.flash_attention(q, k, v, True, 64, 64, True,
                                          False) ** 2)

    def loss_r(q, k, v):
        o, _ = fa.attention_ref(q, k, v, causal=True, scale=1 / np.sqrt(dh))
        return jnp.sum(o ** 2)

    g1 = jax.grad(loss_k, (0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_r, (0, 1, 2))(q, k, v)
    for a, b_ in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=1e-4)


def test_flash_lse_output():
    q = RNG.normal(0, 1, (1, 2, 32, 16)).astype(np.float32)
    k = RNG.normal(0, 1, (1, 2, 32, 16)).astype(np.float32)
    v = RNG.normal(0, 1, (1, 2, 32, 16)).astype(np.float32)
    out, lse = fa.flash_attention(q, k, v, True, 32, 32, True, True)
    ref, lse_ref = fa.attention_ref(q, k, v, causal=True,
                                    scale=1 / np.sqrt(16))
    np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_ref),
                               atol=1e-4)


# ------------------------------------------------------------ kde attention
@pytest.mark.parametrize("b,hq,hkv,S,dh,bk,stride,top_p", [
    (2, 8, 2, 2048, 64, 128, 8, 4),
    (1, 4, 4, 1024, 32, 256, 16, 2),
    (2, 2, 1, 512, 16, 64, 4, 3),
])
def test_kde_attention_matches_mirror(b, hq, hkv, S, dh, bk, stride, top_p):
    """The Pallas pipeline is deterministic (strided subsample), so it must
    agree with the jnp mirror exactly."""
    q = RNG.normal(0, 1, (b, hq, dh)).astype(np.float32)
    k = RNG.normal(0, 0.3, (b, hkv, S, dh)).astype(np.float32)
    v = RNG.normal(0, 1, (b, hkv, S, dh)).astype(np.float32)
    out = ka.kde_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                           top_p=top_p, bk=bk, stride=stride, interpret=True)
    ref = ka.kde_attention_ref(jnp.asarray(q), jnp.asarray(k),
                               jnp.asarray(v), top_p=top_p, bk=bk,
                               stride=stride)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_kde_attention_approximates_exact_on_peaked():
    """When attention mass is concentrated (the realistic long-context
    regime), top-P blocks + KDE residual get close to exact attention."""
    b, hq, hkv, S, dh = 1, 4, 2, 4096, 32
    q = RNG.normal(0, 1, (b, hq, dh)).astype(np.float32)
    k = RNG.normal(0, 0.05, (b, hkv, S, dh)).astype(np.float32)
    # plant high-score keys inside two blocks (strong enough that the
    # planted mass dominates the 4096-key background)
    for h in range(hkv):
        qv = q.reshape(b, hkv, hq // hkv, dh).mean(2)[0, h]
        k[0, h, 100:140] = 8.0 * qv / np.linalg.norm(qv) + k[0, h, 100:140]
        k[0, h, 3000:3020] = 6.0 * qv / np.linalg.norm(qv) + k[0, h, 3000:3020]
    v = RNG.normal(0, 1, (b, hkv, S, dh)).astype(np.float32)
    out = ka.kde_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                           top_p=8, bk=256, stride=8, interpret=True)
    exact = ka.exact_decode_attention(jnp.asarray(q), jnp.asarray(k),
                                      jnp.asarray(v))
    err = float(jnp.abs(out - exact).max())
    scale = float(jnp.abs(exact).max())
    assert err < 0.2 * scale, (err, scale)


def test_kde_attention_exact_when_all_blocks_selected():
    """top_p = all blocks -> no residual -> exact attention."""
    b, hq, hkv, S, dh = 1, 2, 2, 256, 16
    q = RNG.normal(0, 1, (b, hq, dh)).astype(np.float32)
    k = RNG.normal(0, 0.5, (b, hkv, S, dh)).astype(np.float32)
    v = RNG.normal(0, 1, (b, hkv, S, dh)).astype(np.float32)
    out = ka.kde_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                           top_p=4, bk=64, stride=4, interpret=True)
    exact = ka.exact_decode_attention(jnp.asarray(q), jnp.asarray(k),
                                      jnp.asarray(v))
    np.testing.assert_allclose(np.asarray(out), np.asarray(exact), atol=1e-4)


# ----------------------------------------------------------- platform choice
def test_platform_decides_pallas_and_interpret(monkeypatch):
    """One function decides: Pallas (compiled) by default on a TPU for the
    kinds the kernels implement, jnp elsewhere, interpret only off-TPU."""
    from repro.core.kde.base import ExactBlockKDE, ExactKDE
    from repro.core.kernels_fn import Kernel
    from repro.kernels import platform
    x = RNG.normal(0, 0.5, (64, 3)).astype(np.float32)
    assert platform.resolve() == (False, True)
    assert not ExactKDE(x, gaussian(1.0)).use_pallas
    monkeypatch.setattr(platform, "on_tpu", lambda: True)
    assert platform.resolve(kind="gaussian") == (True, False)
    assert ExactKDE(x, gaussian(1.0)).use_pallas
    assert ExactBlockKDE(x, laplacian(1.0), block_size=16).use_pallas
    custom = Kernel("custom", gaussian(1.0).pairwise, None, 1.0)
    assert not ExactKDE(x, custom).use_pallas
    with pytest.raises(ValueError):
        platform.resolve(interpret=True)
