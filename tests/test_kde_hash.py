"""Hashed-KDE subsystem (kernels/kde_hash, DESIGN.md §10): oracle parity,
GridHBE equivalence, §2-contract level-1 reads, the ``level1="hash"``
sampler hybrid, estimator="hash" pipelines, and the sharded one-psum
query schedule (subprocesses own their XLA_FLAGS)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import subproc
from repro.core.kde.base import ExactKDE, make_estimator
from repro.core.kde.hashed import HashedKDE
from repro.core.kde.hbe import GridHBE
from repro.core.kernels_fn import gaussian, laplacian
from repro.kernels.kde_hash import ops as hops
from repro.kernels.kde_hash import ref as href
from repro.kernels.kde_sampler import ops as sops


def _run(code: str, devices: int = 8) -> str:
    return subproc.run_devices(code, devices, tail=1200)


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(1)
    x = rng.normal(0, 1.0, (700, 8)).astype(np.float32)
    ker = laplacian(bandwidth=4.0)
    truth = np.asarray(ExactKDE(x, ker).query(x[:24]))
    return x, ker, truth


def _cfg(ker, cw, num_far, n, **kw):
    base = dict(kind=ker.name, inv_bw=1.0 / ker.bandwidth,
                beta=getattr(ker, "beta", 1.0), pairwise=None,
                cell_width=cw, num_far=num_far, n=n)
    base.update(kw)
    return base


def test_hashed_query_matches_oracle_bitwise(data):
    """ops jnp path AND Pallas interpret path == ref.py oracle, bitwise."""
    x, ker, _ = data
    state, cw = hops.build_hash_state(x, ker, seed=0)
    xd = jnp.asarray(x)
    y = xd[:24]
    key = jax.random.PRNGKey(3)
    want, want_cnt = href.hashed_query_ref(xd, y, state, key, ker.name,
                                           1.0 / ker.bandwidth, 1.0, cw,
                                           64, 700)
    got, cnt, st = hops.hashed_query(xd, y, state, key,
                                     **_cfg(ker, cw, 64, 700))
    got_p, cnt_p, st_p = hops.hashed_query(xd, y, state, key,
                                           **_cfg(ker, cw, 64, 700,
                                                  use_pallas=True,
                                                  interpret=True))
    assert int(np.asarray(st)[0]) == 0 and int(np.asarray(st_p)[0]) == 0
    assert np.array_equal(np.asarray(got), np.asarray(want))
    assert np.array_equal(np.asarray(got_p), np.asarray(want))
    assert np.array_equal(np.asarray(cnt), np.asarray(want_cnt))
    assert np.array_equal(np.asarray(cnt_p), np.asarray(want_cnt))


def test_hashed_query_accuracy_and_sublinear_evals(data):
    """Definition 1.1 accuracy at O(max_bucket + num_far) evals/query."""
    x, ker, truth = data
    est = HashedKDE(x, ker, num_far_samples=128, seed=0)
    vals = np.asarray(est.query(x[:24]))
    rel = np.abs(vals / truth - 1)
    assert rel.mean() < 0.15, rel.mean()
    assert est.evals < 24 * 700            # sublinear per query
    assert est.evals >= 24 * 128           # FAR budget is counted


def test_hashed_query_batches_hit_compiled_path(data):
    """Repeated same-shape queries never retrace (TRACE_COUNTS)."""
    x, ker, _ = data
    est = HashedKDE(x, ker, seed=0)
    est.query(x[:16])
    before = sops.TRACE_COUNTS["hashed_query"]
    est.query(x[16:32])
    est.query(x[32:48])
    assert sops.TRACE_COUNTS["hashed_query"] == before


def test_hashed_matches_gridhbe_buckets_and_near(data):
    """Same seed => same random-shifted grid: the uint32 layout partitions
    the dataset exactly like GridHBE's uint64 keys, and the NEAR-only
    estimates (num_far=0, max_bucket covering every bucket) agree."""
    x, ker, _ = data
    n = x.shape[0]
    hbe = GridHBE(x, ker, num_far_samples=0, max_bucket=n, seed=0)
    est = HashedKDE(x, ker, num_far_samples=0, max_bucket=n, seed=0)
    # identical hash dims + shifts (same RNG call order)
    assert np.array_equal(np.asarray(est.state.dims), hbe.hash_dims)
    np.testing.assert_allclose(np.asarray(est.state.shift), hbe.shift)
    # partition equality: uint64 groups <-> uint32 groups bijectively
    lab64 = np.unique(hbe._keys, return_inverse=True)[1]
    lab32 = np.asarray(est.state.point_bucket)
    pairs = {(int(a), int(b)) for a, b in zip(lab64, lab32)}
    assert len(pairs) == len(np.unique(lab64)) == len(np.unique(lab32))
    # NEAR-only estimates agree (GridHBE with num_far_samples=0 returns
    # the exact bucket sum)
    got = np.asarray(est.query(x[:24]))
    want = np.asarray(hbe.query(jnp.asarray(x[:24])))
    np.testing.assert_allclose(got, want, rtol=3e-5, atol=1e-5)


def test_far_distribution_matches_gridhbe_ks(data):
    """On an empty-bucket query both estimators reduce to the plain RS
    law n * mean(k over s uniform draws); two-sample KS over seeds
    (manual D statistic, same style as tests/test_distributed.py)."""
    x, ker, _ = data
    y = np.full((1, x.shape[1]), 50.0, np.float32)   # far from every cell
    a, b = [], []
    m = 160
    for seed in range(m):
        hbe = GridHBE(x, ker, num_far_samples=64, seed=seed)
        a.append(float(hbe.query(jnp.asarray(y))[0]))
        est = HashedKDE(x, ker, num_far_samples=64, seed=seed)
        b.append(float(est.query(y)[0]))
    a, b = np.sort(a), np.sort(b)
    grid = np.union1d(a, b)
    d = np.abs(np.searchsorted(a, grid, side="right") / m
               - np.searchsorted(b, grid, side="right") / m).max()
    assert d < 2.2 * np.sqrt(2.0 / m), (d, np.mean(a), np.mean(b))


def test_hashed_block_sums_oracle_and_contract(data):
    """Level-1 hashed read == ref oracle bitwise (both Pallas-interpret
    and jnp paths); §2 contract: mean over seeds ~= exact masked sums
    (self excluded, floored)."""
    x, ker, _ = data
    n = x.shape[0]
    state, cw = hops.build_hash_state(x, ker, seed=0, max_bucket=128)
    xd = jnp.asarray(x)
    x_sq = jnp.sum(xd * xd, axis=-1)
    src = jnp.asarray(np.arange(0, 64, dtype=np.int32))
    bs_blk, nb = 64, 11
    kw = dict(kind=ker.name, inv_bw=1.0 / ker.bandwidth, beta=1.0,
              pairwise=None, num_far=2, block_size=bs_blk, num_blocks=nb,
              n=n)
    key = jax.random.PRNGKey(7)
    want = href.hashed_block_sums_ref(xd, src, state, key, ker.name,
                                      1.0 / ker.bandwidth, 1.0, 2, bs_blk,
                                      nb, n)
    got, st = hops.hashed_block_sums(xd, src, state, key, **kw)
    got_p, st_p = hops.hashed_block_sums(xd, src, state, key,
                                         use_pallas=True, interpret=True,
                                         **kw)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    assert np.array_equal(np.asarray(got_p), np.asarray(want))
    # unbiasedness against the exact §2 read (same masking, same floor)
    exact = np.asarray(sops.masked_block_sums(
        xd, x_sq, src, key, kind=ker.name, inv_bw=1.0 / ker.bandwidth,
        beta=1.0, pairwise=None, block_size=bs_blk, num_blocks=nb, n=n,
        s=16, exact=True)[0])
    acc = np.zeros_like(exact)
    reps = 150
    for i in range(reps):
        acc += np.asarray(hops.hashed_block_sums(
            xd, src, state, jax.random.PRNGKey(100 + i), **kw)[0])
    acc /= reps
    rel = np.abs(acc.sum(1) / exact.sum(1) - 1)
    assert rel.mean() < 0.1, rel.mean()


def test_level1_hash_sampler_consistency(data):
    """level1="hash": prob_of on the cached frontier equals the realized
    sampling probabilities; draws are valid, never the source itself."""
    from repro.core.sampling.edge import NeighborSampler
    x, ker, _ = data
    nbr = NeighborSampler(x, ker, mode="blocked", level1="hash", seed=0)
    src = np.arange(48) * 3
    v, q = nbr.sample(src)
    assert np.all(v >= 0) and np.all(v < x.shape[0])
    assert np.all(v != src)
    q2 = nbr.prob_of(src, v)
    np.testing.assert_allclose(q, q2, rtol=2e-4, atol=1e-8)
    # rejection-exact mode runs off the same cached hashed sums
    ve = nbr.sample_exact(src, rounds=4)
    assert np.all(ve >= 0) and np.all(ve < x.shape[0])
    assert np.all(ve != src)
    # eval counter: hashed level-1 is cheaper than the stratified read
    nbr_s = NeighborSampler(x, ker, mode="blocked", seed=0)
    assert nbr._level1_evals(48) < nbr_s._level1_evals(48)


def test_level1_hash_walk_and_distribution(data):
    """Hashed level-1 walks stay on device and the depth-2 draw law stays
    close to the true k(u, .)/deg(u) law (chi-square on a small n)."""
    from repro.core.sampling.edge import NeighborSampler
    rng = np.random.default_rng(0)
    x = rng.normal(0, 0.6, (120, 4)).astype(np.float32)
    ker = gaussian(bandwidth=1.5)
    nbr = NeighborSampler(x, ker, mode="blocked", level1="hash", seed=0,
                          hash_opts={"far_per_block": 4})
    end, path = nbr.walk(np.arange(16), length=5, record_path=True)
    assert end.shape == (16,) and path.shape == (5, 16)
    # draw distribution: chi-square of 4000 draws from one source
    k = np.asarray(ker.matrix(jnp.asarray(x)), np.float64)
    p = k[7].copy()
    p[7] = 0.0
    p /= p.sum()
    draws = []
    for _ in range(120):
        v, _ = nbr.sample(np.full(40, 7))
        nbr._l1_cache = None            # fresh level-1 noise each batch
        draws.extend(v.tolist())
    counts = np.bincount(draws, minlength=120)
    exp = p * len(draws)
    keep = exp > 8
    chi2 = float(((counts[keep] - exp[keep]) ** 2 / exp[keep]).sum())
    df = int(keep.sum()) - 1
    # hashed level-1 block masses are estimates, so the realized law is
    # only approximately the target -- allow ~2x a generous 1e-4-level
    # normal-approximation chi-square quantile
    assert chi2 < 2.0 * (df + 4.0 * np.sqrt(2.0 * df) + 16.0), (chi2, df)


def test_sparsify_and_triangles_hash_estimator():
    """estimator="hash" end-to-end: fewer kernel evals than stratified,
    spectral error within 1.5x, triangle estimate in range."""
    from repro.core.graph.triangles import (estimate_triangle_weight,
                                            exact_triangle_weight)
    from repro.core.sparsify import spectral_sparsify
    rng = np.random.default_rng(0)
    n = 512
    x = rng.normal(0, 0.35, (n, 8)).astype(np.float32)
    ker = gaussian(bandwidth=3.0)
    t = 12 * n
    g_h = spectral_sparsify(x, ker, num_edges=t, estimator="hash", seed=0)
    g_s = spectral_sparsify(x, ker, num_edges=t, estimator="stratified",
                            seed=0)
    assert g_h.kernel_evals < g_s.kernel_evals
    k = np.asarray(ker.matrix(jnp.asarray(x)), np.float64)
    np.fill_diagonal(k, 0.0)
    l_true = np.diag(k.sum(1)) - k
    v = np.random.default_rng(1).standard_normal((n, 24))
    v -= v.mean(0)

    def err(g):
        r = np.einsum("ij,ij->j", v, g.laplacian_dense() @ v) \
            / np.einsum("ij,ij->j", v, l_true @ v)
        return np.abs(r - 1.0).max()

    e_h, e_s = err(g_h), err(g_s)
    assert e_h < max(1.5 * e_s, 0.08), (e_h, e_s)
    tri_h = estimate_triangle_weight(x, ker, 500, 24, estimator="hash",
                                     seed=0)
    tri_s = estimate_triangle_weight(x, ker, 500, 24, estimator="stratified",
                                     seed=0)
    tw = exact_triangle_weight(ker, x)
    assert tri_h.kernel_evals < tri_s.kernel_evals
    assert abs(tri_h.total_weight / tw - 1) < 0.2
    # both pipelines share ONE hash layout (degrees + level-1 reads)
    assert g_h.kde_queries == g_s.kde_queries


def test_rownorm_and_factory_hash():
    """make_estimator("hash") and the Section 5.2 row-norm sampler accept
    the hashed backend unchanged."""
    from repro.core.sampling.rownorm import RowNormSampler
    rng = np.random.default_rng(0)
    x = rng.normal(0, 0.5, (256, 6)).astype(np.float32)
    ker = gaussian(1.5)
    est = make_estimator("hash", x, ker, seed=0)
    v = np.asarray(est.query(x[:8]))
    assert v.shape == (8,) and np.all(np.isfinite(v))
    s = RowNormSampler(x, ker, estimator="hash", seed=0)
    idx = s.sample(64)
    assert idx.shape == (64,) and np.all(idx < 256)
    k = np.asarray(ker.matrix(jnp.asarray(x)), np.float64)
    want = (k ** 2).sum(1)
    rel = np.abs(s.row_norms_sq / want - 1)
    assert rel.mean() < 0.2, rel.mean()


def test_degrees_via_hash_match_exact(data):
    """Algorithm 4.3 degrees from the hashed estimator track the exact
    degrees (the DegreeSampler preprocessing path)."""
    from repro.core.sampling.vertex import approximate_degrees
    x, ker, _ = data
    est = HashedKDE(x, ker, num_far_samples=128, seed=0)
    deg = approximate_degrees(est)
    k = np.asarray(ker.matrix(jnp.asarray(x)), np.float64)
    np.fill_diagonal(k, 0.0)
    want = k.sum(1)
    rel = np.abs(deg / np.maximum(want, 1e-12) - 1)
    assert np.median(rel) < 0.25, np.median(rel)


def test_sharded_hash_one_psum_and_oracle():
    """Sharded hashed query: exactly one psum / zero ppermute per batch,
    NEAR counts bitwise vs the single-device oracle, floats to f32
    tolerance, and NEAR-only estimates equal to the flat engine."""
    out = _run("""
import numpy as np, jax, jax.numpy as jnp
from repro.core.kernels_fn import gaussian
from repro.kernels.kde_hash.sharded import ShardedHashTable
from repro.kernels.kde_hash import ops as hops, ref as href
from repro.kernels.kde_sampler.sharded import collective_counts

rng = np.random.default_rng(0)
n, d = 700, 8
x = rng.normal(0, 1.0, (n, d)).astype(np.float32)
ker = gaussian(bandwidth=2.0)
mesh = make_mesh((8,), ("data",))
tab = ShardedHashTable(mesh, x, ker, seed=3)
y = jnp.asarray(x[:32])
key = jax.random.PRNGKey(5)
cc = collective_counts(lambda yy, kk: tab._program()(
    tab._keys, tab._members, tab._counts, tab._overflow, tab._dims,
    tab._shift, tab.x_sh, yy, kk), y, key)
assert cc["psum_total"] == 1 and cc["ppermute_total"] == 0, cc
est, cnt, st = tab.query(y, key)
assert int(np.asarray(st)[0]) == 0, st
ref_est, ref_cnt = href.sharded_hashed_query_ref(
    tab.x_pad, y, tab.shard_states, key, ker.name, 1.0 / ker.bandwidth,
    1.0, tab.spec.cell_width, tab.num_far, n, tab.shard_size)
assert np.array_equal(np.asarray(cnt), np.asarray(ref_cnt))
np.testing.assert_allclose(np.asarray(est), np.asarray(ref_est),
                           rtol=2e-5, atol=1e-5)
# NEAR-only: sharded union of local buckets == flat bucket layout
tab0 = ShardedHashTable(mesh, x, ker, seed=3, num_far_samples=0,
                        max_bucket=512)
est0, cnt0, _ = tab0.query(y, key)
state, cw = hops.build_hash_state(x, ker, seed=3, max_bucket=512)
estf, cntf, _ = hops.hashed_query(
    jnp.asarray(x), y, state, key, kind=ker.name,
    inv_bw=1.0 / ker.bandwidth, beta=1.0, pairwise=None, cell_width=cw,
    num_far=0, n=n)
assert np.array_equal(np.asarray(cnt0), np.asarray(cntf))
np.testing.assert_allclose(np.asarray(est0), np.asarray(estf), rtol=2e-5,
                           atol=1e-5)
# estimator adapter: one program per batch, accuracy vs dense truth
from repro.core.kde.hashed import HashedKDE
hk = HashedKDE(x, ker, seed=3, num_far_samples=128, mesh=mesh)
vals = np.asarray(hk.query(x[:32]))
truth = np.asarray(ker.matrix(jnp.asarray(x))[:32].sum(1))
assert np.abs(vals / truth - 1).mean() < 0.15
print("SHARDED_HASH_OK")
""")
    assert "SHARDED_HASH_OK" in out
