"""Distribution layer: the sharded sampling engine (DESIGN.md §9 -- ref
oracles, collective schedule, distribution equivalence, pipeline counter
audits), sharded KDE wrappers, sharding rules, small-mesh dry-run
(subprocesses own their XLA_FLAGS -- the main test process stays 1-device)."""
import json

import numpy as np
import pytest

import subproc
from repro.configs.base import get_config


# Sharded and flat level-1 reads sum the same kernel values in different
# orders; f32 reassociation over a few hundred terms stays well inside
# this relative bound (observed <= 1e-6).
F32_SUM_RTOL = 1e-5


def _run(code: str, devices: int = 8) -> str:
    return subproc.run_devices(f"F32_SUM_RTOL = {F32_SUM_RTOL}\n" + code,
                               devices, tail=1200)


def test_sharded_kde_query_matches_local():
    out = _run("""
import jax, jax.numpy as jnp, numpy as np
from repro.core.kernels_fn import gaussian
from repro.core.kde.distributed import sharded_kde_query, make_sharded_dataset, degree_preprocessing
ker = gaussian(1.0)
rng = np.random.default_rng(0)
x = rng.normal(0, 0.6, (256, 5)).astype(np.float32)
y = rng.normal(0, 0.6, (16, 5)).astype(np.float32)
mesh = make_mesh((4, 2), ("data", "model"))
xs = make_sharded_dataset(mesh, x)
q = sharded_kde_query(mesh, ker)
got = np.asarray(q(jnp.asarray(y), xs))
want = np.asarray(ker.pairwise(jnp.asarray(y), jnp.asarray(x)).sum(1))
np.testing.assert_allclose(got, want, rtol=1e-4)
deg = degree_preprocessing(mesh, ker)
dg = np.asarray(deg(xs))
wantd = np.asarray(ker.matrix(jnp.asarray(x)).sum(1)) - 1.0
np.testing.assert_allclose(dg, wantd, rtol=1e-3, atol=1e-3)
print("SHARDED_KDE_OK")
""")
    assert "SHARDED_KDE_OK" in out


def test_degree_preprocessing_multi_axis_mesh():
    """Regression: the ring permutation in degree_preprocessing must run
    over the *flattened* index of all data axes.  On a ("pod", "data") =
    (4, 2) mesh the old ring covered axis_size(axes[0]) = 4 of 8 shards and
    silently dropped half the dataset's contributions."""
    out = _run("""
import jax, jax.numpy as jnp, numpy as np
from repro.core.kernels_fn import gaussian
from repro.core.kde.distributed import degree_preprocessing, make_sharded_dataset
ker = gaussian(1.0)
rng = np.random.default_rng(0)
x = rng.normal(0, 0.6, (256, 5)).astype(np.float32)
mesh = make_mesh((4, 2), ("pod", "data"))
xs = make_sharded_dataset(mesh, x, data_axes=("pod", "data"))
deg = degree_preprocessing(mesh, ker, data_axes=("pod", "data"))
got = np.asarray(deg(xs))
want = np.asarray(ker.matrix(jnp.asarray(x)).sum(1)) - 1.0
np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-3)
print("MULTIAXIS_DEG_OK")
""")
    assert "MULTIAXIS_DEG_OK" in out


def test_sharded_block_sums():
    out = _run("""
import jax, jax.numpy as jnp, numpy as np
from repro.core.kernels_fn import gaussian
from repro.core.kde.distributed import sharded_block_sums, make_sharded_dataset
ker = gaussian(1.0)
rng = np.random.default_rng(0)
x = rng.normal(0, 0.6, (256, 5)).astype(np.float32)
y = rng.normal(0, 0.6, (8, 5)).astype(np.float32)
mesh = make_mesh((4,), ("data",))
xs = make_sharded_dataset(mesh, x)
f = sharded_block_sums(mesh, ker, num_blocks_per_shard=4)
got = np.asarray(f(jnp.asarray(y), xs))       # (8, 16)
kv = np.asarray(ker.pairwise(jnp.asarray(y), jnp.asarray(x)))
want = kv.reshape(8, 16, 16).sum(-1)
np.testing.assert_allclose(got, want, rtol=1e-4)
print("BLOCKSUMS_OK")
""")
    assert "BLOCKSUMS_OK" in out


def test_sharded_block_sums_ragged_shard_regression():
    """Regression: a shard size not divisible by the block count used to
    crash the in-body reshape.  Now the shard is padded with the sentinel
    rows (kernel values exactly 0), so tail blocks sum only their real
    rows -- checked against a host oracle of the same layout."""
    out = _run("""
import jax, jax.numpy as jnp, numpy as np
from repro.core.kernels_fn import gaussian
from repro.core.kde.distributed import sharded_block_sums, make_sharded_dataset
ker = gaussian(1.0)
rng = np.random.default_rng(0)
x = rng.normal(0, 0.6, (256, 5)).astype(np.float32)   # shard = 64 rows
y = rng.normal(0, 0.6, (6, 5)).astype(np.float32)
mesh = make_mesh((4,), ("data",))
xs = make_sharded_dataset(mesh, x)
f = sharded_block_sums(mesh, ker, num_blocks_per_shard=5)  # 64 % 5 != 0
got = np.asarray(f(jnp.asarray(y), xs))               # (6, 20)
kv = np.asarray(ker.pairwise(jnp.asarray(y), jnp.asarray(x)))
want = np.zeros((6, 20))
for p in range(4):                                    # bs_l = ceil(64/5) = 13
    for b in range(5):
        lo = p * 64 + b * 13
        hi = min(p * 64 + min((b + 1) * 13, 64), 256)
        if lo < hi:
            want[:, p * 5 + b] = kv[:, lo:hi].sum(1)
np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
print("RAGGED_OK")
""")
    assert "RAGGED_OK" in out


def test_sharded_block_sums_section2_contract_bitwise():
    """With ``own=`` the distributed level-1 read applies the §2 sampling
    contract (self-block correction, 1e-12 floor) and must agree bitwise
    with the single-device ``ops.masked_block_sums`` on aligned layouts, to
    f32 reduction-order tolerance (the two layouts sum in different
    orders; block indices and the floor are exact)."""
    out = _run("""
import jax, jax.numpy as jnp, numpy as np
from repro.core.kernels_fn import gaussian
from repro.core.kde.distributed import sharded_block_sums, make_sharded_dataset
from repro.kernels.kde_sampler import ops as sops
ker = gaussian(1.0)
rng = np.random.default_rng(0)
n, bs = 256, 16
x = rng.normal(0, 0.6, (n, 5)).astype(np.float32)
src = rng.integers(0, n, 24).astype(np.int32)
mesh = make_mesh((8,), ("data",))
xs = make_sharded_dataset(mesh, x)
f = sharded_block_sums(mesh, ker, num_blocks_per_shard=2)   # 32/2 = bs 16
got = np.asarray(f(jnp.asarray(x[src]), xs, own=src // bs))
xd = jnp.asarray(x)
want = np.asarray(sops.masked_block_sums(
    xd, jnp.sum(xd * xd, -1), jnp.asarray(src), jax.random.PRNGKey(0),
    kind="gaussian", inv_bw=1.0, beta=1.0, pairwise=None, block_size=bs,
    num_blocks=n // bs, n=n, s=16, exact=True)[0])
assert got.shape == want.shape
np.testing.assert_allclose(got, want, rtol=F32_SUM_RTOL, atol=1e-12)
print("CONTRACT_BITWISE_OK")
""")
    assert "CONTRACT_BITWISE_OK" in out


@pytest.mark.parametrize("case", ["past_every_prefix", "at_zero",
                                  "random"])
def test_inverse_cdf_pick_never_lands_on_zero_weight(case):
    """The mesh draw's shard and block picks: rows like a last shard's
    block sums (positive real blocks, zero-weight sentinel blocks at the
    tail, a zero in the middle).  A threshold past every prefix sum -- as
    rounding makes it when the total is summed apart from the prefix sums,
    or by a tree -- picks the last positive entry, a zero threshold the
    first positive one, and elsewhere the pick is the plain inverse CDF."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.kde_sampler import ref
    rng = np.random.default_rng(5)
    w = rng.uniform(20.0, 200.0, (512, 12)).astype(np.float32)
    w[:, 10:] = 0.0
    w[:, 0] = np.where(np.arange(512) % 2, 0.0, w[:, 0])
    w[:, 4] = 0.0
    w[-1] = 0.0                                  # an empty shard picks 0
    u = {"past_every_prefix": np.full(512, 1.0 + 1e-6, np.float32),
         "at_zero": np.zeros(512, np.float32),
         "random": rng.uniform(size=512).astype(np.float32)}[case]
    j, tot = ref.inverse_cdf_pick(jnp.asarray(w), jnp.asarray(u))
    j, tot = np.asarray(j), np.asarray(tot)
    assert np.all(w[np.arange(511), j[:-1]] > 0) and j[-1] == 0
    c = np.cumsum(w, axis=1)
    np.testing.assert_array_equal(tot, c[:, -1])
    if case == "past_every_prefix":
        np.testing.assert_array_equal(j[:-1], 9)
    elif case == "at_zero":
        np.testing.assert_array_equal(j[:-1], np.where(
            np.arange(511) % 2, 1, 0))
    else:
        plain = np.sum((u * tot)[:, None] > c, axis=1)
        np.testing.assert_array_equal(j[:-1], plain[:-1])


def test_sharded_draw_never_returns_a_zero_mass_block():
    """The collective draw on a layout whose last shard ends in
    all-sentinel blocks (n = 1,000 in blocks of 4 over 4 shards: 61 real
    blocks and 2 sentinel ones in the last), all mass in that shard, and
    a key whose block uniform for row 904 is the largest one JAX draws.  The
    rows are ones whose summed total rounds above their last real prefix
    sum: a pick against that total lands on a sentinel block, a draw of
    probability 0 at row n - 1.  Every draw must have positive mass."""
    out = _run("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core.kernels_fn import gaussian
from repro.kernels.kde_sampler.sharded import ShardedBlocks
mesh = make_mesh((4,), ("data",))
x = np.random.default_rng(1).normal(0, 0.5, (1000, 2)).astype(np.float32)
eng = ShardedBlocks(mesh, x, gaussian(0.7), block_size=4, exact=True)
assert (eng.blocks_per_shard, eng.n_pad) == (63, 1008)
key = jax.random.fold_in(jax.random.PRNGKey(0), 12324)
u1 = jax.random.uniform(jax.random.split(key, 3)[1], (1024,))
assert float(u1[904]) == 1.0 - 2.0 ** -23         # the largest it draws
rows = np.random.default_rng(0).uniform(20, 200, (4096, 63)).astype(np.float32)
rows[:, 61:] = 0.0
sums = np.full((1024, 252), 1e-12, np.float32)
sums[:, 189:] = rows[:1024]
for i in (422, 436, 587, 748, 756):
    sums[904, 189:] = rows[i]
    nb, prob, _ = eng.sample_from_block_sums(
        jnp.zeros(1024, jnp.int32), jax.device_put(
            jnp.asarray(sums), NamedSharding(mesh, P(None, "data"))), key)
    assert np.all(np.asarray(prob) > 0), (i, float(prob[904]))
    assert int(nb[904]) < 1000
print("POSITIVE_OK")
""", devices=4)
    assert "POSITIVE_OK" in out


@pytest.mark.parametrize("case", ["past_every_prefix", "random"])
def test_level2_draw_never_picks_a_dead_column(case):
    """The in-block draw over rows like the mesh cell's ragged tail block
    (28 real columns, 334 out-of-range ones) with the self column dead in
    half the rows and some rows underflowed to zero (uniform over the live
    columns).  A threshold past every prefix sum picks the last live
    column; elsewhere the pick and its probability are bitwise the plain
    inverse CDF's."""
    import jax.numpy as jnp
    from repro.kernels.kde_sampler import ref
    rng = np.random.default_rng(3)
    m, bs, real = 256, 362, 28
    live = np.zeros((m, bs), bool)
    live[:, :real] = True
    live[::2, 5] = False                              # the self edge
    kv = np.where(live, rng.uniform(1e-3, 1.0, (m, bs)), 0.0).astype(
        np.float32)
    kv[::7] = 0.0                                     # underflowed rows
    cols = np.minimum(np.arange(bs), real - 1)[None].repeat(m, 0) + 4096
    u = (np.full(m, 1.0 + 1e-6, np.float32) if case == "past_every_prefix"
         else rng.uniform(size=m).astype(np.float32))
    nb, pin = ref.level2_draw(jnp.asarray(kv), jnp.asarray(live),
                              jnp.asarray(cols.astype(np.int32)),
                              jnp.asarray(u))
    nb, pin = np.asarray(nb), np.asarray(pin)
    assert np.all(pin > 0)
    use = np.where(kv.sum(1, keepdims=True) > 0, kv, live.astype(np.float32))
    c = np.asarray(jnp.cumsum(jnp.asarray(use), axis=1))
    if case == "past_every_prefix":
        np.testing.assert_array_equal(nb, 4096 + real - 1)
    else:
        j = np.sum((u * c[:, -1])[:, None] > c, axis=1).clip(0, bs - 1)
        np.testing.assert_array_equal(nb, cols[np.arange(m), j])
        np.testing.assert_array_equal(
            pin, use[np.arange(m), j] / np.maximum(c[:, -1], 1e-30))


def test_sharded_in_block_draw_never_lands_on_a_dead_column():
    """The collective draw on a layout whose last real block is ragged (n
    = 1,000 in blocks of 32 over 4 shards: block 31 holds 8 real rows and
    24 sentinel ones), all mass in that block, with prefix sums that round
    upward along a row, as a tree sum can (emulated: the k-th prefix sum
    scaled by 1 + k 1e-4, so the total tops the last live prefix sum).
    The plain inverse CDF then lands on the block's out-of-range columns;
    every draw must stay on a live column of positive probability."""
    out = _run("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
cumsum = jnp.cumsum
def rounding_up(a, axis=None, **kw):
    c = cumsum(a, axis=axis, **kw)
    k = jnp.arange(c.shape[axis], dtype=c.dtype)
    return c * (1.0 + 1e-4 * jnp.expand_dims(k, tuple(
        i for i in range(c.ndim) if i != axis % c.ndim)))
jnp.cumsum = rounding_up
from repro.core.kernels_fn import gaussian
from repro.kernels.kde_sampler.sharded import ShardedBlocks
mesh = make_mesh((4,), ("data",))
x = np.random.default_rng(1).normal(0, 0.5, (1000, 2)).astype(np.float32)
eng = ShardedBlocks(mesh, x, gaussian(0.7), block_size=32, exact=True)
assert (eng.blocks_per_shard, eng.n_pad) == (8, 1024)
src = np.arange(1024, dtype=np.int32) % 1000
sums = np.full((1024, 32), 1e-12, np.float32)
sums[:, 31] = 1.0
key = jax.random.PRNGKey(7)
# the emulated rounding does send the plain pick past the live columns
u = np.asarray(jax.random.uniform(jax.random.split(key, 3)[2], (1024,)))
c = np.asarray(rounding_up(jnp.ones((1024, 32)).at[:, 8:].set(0.0), axis=1))
assert np.sum(np.sum((u * c[:, -1])[:, None] > c, axis=1) >= 8) > 0
nb, prob, _ = eng.sample_from_block_sums(
    jnp.asarray(src), jax.device_put(
        jnp.asarray(sums), NamedSharding(mesh, P(None, "data"))), key)
nb, prob = np.asarray(nb), np.asarray(prob)
assert np.all(prob > 0), np.sum(prob <= 0)
assert np.all((nb >= 992) & (nb < 1000) & (nb != src))
print("LIVE_OK")
""", devices=4)
    assert "LIVE_OK" in out


def test_sharded_engine_oracle_schedule_and_no_retrace():
    """The ShardedBlocks engine: (a) draws/walks reproduce the ref.py
    oracles bit-for-bit on both level-1 paths, (b) the collective schedule
    is exactly one psum and zero ppermute per draw batch (jaxpr-counted),
    (c) repeated calls never retrace, (d) the level-1 read agrees bitwise
    with the single-device engine to f32 reduction-order tolerance
    (draws, indices and counts stay bitwise)."""
    out = _run("""
import jax, jax.numpy as jnp, numpy as np
from repro.core.kernels_fn import gaussian
from repro.kernels.kde_sampler.sharded import ShardedBlocks, collective_counts
from repro.kernels.kde_sampler import ref as sref, ops as sops
ker = gaussian(1.0)
rng = np.random.default_rng(0)
n, d, bsz = 250, 5, 16
x = rng.normal(0, 0.6, (n, d)).astype(np.float32)
mesh = make_mesh((8,), ("data",))
key = jax.random.PRNGKey(3)
src = jnp.asarray(rng.integers(0, n, 64), jnp.int32)
for exact in (True, False):
    eng = ShardedBlocks(mesh, x, ker, block_size=bsz, exact=exact,
                        samples_per_block=8)
    nb, prob, sums, st = eng.fused_sample(src, key)
    assert int(np.asarray(st)[0]) == 0, st
    rnb, rprob, rsums = sref.sharded_fused_sample_ref(
        eng.x_rep, eng.x_sq_rep, src, key, "gaussian", 1.0, 1.0, bsz,
        eng.blocks_per_shard, eng.num_shards, n, exact=exact, s=8)
    np.testing.assert_array_equal(np.asarray(nb), np.asarray(rnb))
    np.testing.assert_allclose(np.asarray(prob), np.asarray(rprob),
                               rtol=2e-5, atol=1e-9)
    np.testing.assert_allclose(np.asarray(sums), np.asarray(rsums),
                               rtol=F32_SUM_RTOL, atol=1e-12)
eng = ShardedBlocks(mesh, x, ker, block_size=bsz, exact=True)
keys = jax.random.split(jax.random.PRNGKey(7), 5)
end, _, wst, wfb = eng.walk_scan(src, keys)
assert int(np.asarray(wst)[0]) == 0 and int(np.asarray(wfb)) == 0
rend = sref.sharded_walk_ref(eng.x_rep, eng.x_sq_rep, src, keys, "gaussian",
                             1.0, 1.0, bsz, eng.blocks_per_shard,
                             eng.num_shards, n, exact=True)
np.testing.assert_array_equal(np.asarray(end), np.asarray(rend))
# bitwise vs the single-device level-1 read (real blocks; pads are 0)
xd = jnp.asarray(x)
sd = np.asarray(sops.masked_block_sums(
    xd, jnp.sum(xd * xd, -1), src, key, kind="gaussian", inv_bw=1.0,
    beta=1.0, pairwise=None, block_size=bsz, num_blocks=-(-n // bsz), n=n,
    s=16, exact=True)[0])
sums = np.asarray(eng.masked_block_sums(src, key)[0])
np.testing.assert_allclose(sums[:, :sd.shape[1]], sd, rtol=F32_SUM_RTOL,
                           atol=1e-12)
assert np.all(sums[:, sd.shape[1]:] == 0.0)
# collective schedule: one psum, no ppermute, per draw batch
degs = (np.asarray(ker.matrix(xd), np.float64).sum(1) - 1).astype(np.float32)
cdf = (np.cumsum(degs) / degs.sum()).astype(np.float32)
ekeys = jax.random.split(jax.random.PRNGKey(1), 3)
u = src[:40]; v = (src[:40] + 7) % n
for name, cc in [
    ("walk", collective_counts(lambda s, k: eng.walk_scan(s, k), src, keys)),
    ("edges", collective_counts(
        lambda c, dg, ks: eng.edge_batch_scan(c, dg, 1.0 / degs.sum(),
                                              1.0 / 300, ks, batch=64),
        cdf, degs, ekeys)),
    ("tri", collective_counts(
        lambda a, b, dg, ks: eng.triangle_edge_scan(a, b, dg, ks),
        u, v, degs, ekeys)),
    ("draw", collective_counts(lambda s, k: eng.fused_sample(s, k), src,
                               key)),
]:
    assert cc["psum_total"] == 1 and cc["ppermute_total"] == 0, (name, cc)
# noisy power: one psum per iteration (scan body) + one final exact matvec
from repro.kernels.kde_sampler.sharded import sharded_noisy_power
ksub = jnp.asarray(np.asarray(ker.matrix(xd[:96, :]), np.float32))
v0 = jnp.ones(96, jnp.float32) / jnp.sqrt(96.0)
nkeys = jax.random.split(jax.random.PRNGKey(4), 6)
cc = collective_counts(lambda kk: sharded_noisy_power(
    mesh, ksub, v0, kk, num_samples=16), nkeys)
assert cc["psum_total"] == 2 and cc["ppermute_total"] == 0, cc
# no-retrace
eng.fused_sample(src, key); eng.walk_scan(src, keys)
before = dict(sops.TRACE_COUNTS)
for _ in range(3):
    eng.fused_sample(src, key); eng.walk_scan(src, keys)
assert dict(sops.TRACE_COUNTS) == before
print("ENGINE_OK")
""")
    assert "ENGINE_OK" in out


def test_sharded_draw_distribution_equivalence_ks():
    """The two-stage collective draw samples the same law as the flat
    single-device draw: one-sample KS against the exact conditional
    k(u, .)/deg(u) for both engines, and a two-sample KS between them.
    Seeds derive from ``stats.ROOT_SEED`` and the thresholds are the
    precomputed ``stats.ks_critical`` values at alpha = 1e-4 (the
    false-positive budget documented in tests/stats.py; at m = 4096 the
    one-sample critical value is 0.0348, matching the old ad-hoc
    2.2/sqrt(m) = 0.0344 in strictness)."""
    import stats
    data_seed = stats.derive_seed("distributed", "ks", "data")
    engine_seed = stats.derive_seed("distributed", "ks", "engine")
    crit1 = stats.ks_critical(4096, alpha=1e-4)
    crit2 = stats.ks_critical(4096, 4096, alpha=1e-4)
    out = _run(f"""
import jax, jax.numpy as jnp, numpy as np
from repro.core.kernels_fn import gaussian
from repro.core.sampling.edge import NeighborSampler
ker = gaussian(1.0)
rng = np.random.default_rng({data_seed})
n, m, u0 = 512, 4096, 17
x = rng.normal(0, 0.5, (n, 6)).astype(np.float32)
mesh = make_mesh((8,), ("data",))
k = np.asarray(ker.matrix(jnp.asarray(x)), np.float64)
p = k[u0].copy(); p[u0] = 0.0; p /= p.sum()
cdf = np.cumsum(p)
src = np.full(m, u0, np.int64)
def ecdf_D(samples):
    counts = np.bincount(samples, minlength=n)
    return np.abs(np.cumsum(counts) / len(samples) - cdf).max()
nb_s, _ = NeighborSampler(x, ker, exact_blocks=True, seed={engine_seed},
                          mesh=mesh).sample(src)
nb_1, _ = NeighborSampler(x, ker, exact_blocks=True,
                          seed={engine_seed}).sample(src)
D_s, D_1 = ecdf_D(nb_s), ecdf_D(nb_1)
assert D_s < {crit1!r} and D_1 < {crit1!r}, (D_s, D_1, {crit1!r})
c2 = np.bincount(nb_s, minlength=n), np.bincount(nb_1, minlength=n)
D_2 = np.abs(np.cumsum(c2[0]) / m - np.cumsum(c2[1]) / m).max()
assert D_2 < {crit2!r}, (D_2, {crit2!r})
print("KS_OK", D_s, D_1, D_2)
""")
    assert "KS_OK" in out


def test_sharded_pipelines_counters_and_accuracy():
    """Every mesh=-enabled Table-1 pipeline (sparsify, arboricity,
    triangles, LRA, eigen, walks via spectrum) matches the single-device
    eval counters EXACTLY and stays within the single-device accuracy
    envelope on a simulated 8-device mesh."""
    out = _run("""
import jax, jax.numpy as jnp, numpy as np
from repro.core.kernels_fn import gaussian
from repro.core.sparsify import spectral_sparsify
from repro.core.graph.arboricity import estimate_arboricity, exact_arboricity
from repro.core.graph.triangles import estimate_triangle_weight, exact_triangle_weight
from repro.core.lowrank import fkv_lowrank, projection_error, optimal_error
from repro.core.eigen import top_eigenvalue
from repro.core.spectrum import approximate_spectrum
mesh = make_mesh((8,), ("data",))
rng = np.random.default_rng(0)
x = rng.normal(0, 0.35, (300, 5)).astype(np.float32)
ker = gaussian(2.0)
k = np.asarray(ker.matrix(jnp.asarray(x)), np.float64)

from repro.obs import metrics as M
edge_spans, span = [], M.span
def noting(name, **meta):
    if name == "sparsify.edges":
        edge_spans.append(meta)
    return span(name, **meta)
M.span = noting
g1 = spectral_sparsify(x, ker, 3000, estimator="exact", exact_blocks=True, seed=0)
g2 = spectral_sparsify(x, ker, 3000, estimator="exact", exact_blocks=True, seed=0, mesh=mesh)
M.span = span
assert (g1.kernel_evals, g1.kde_queries) == (g2.kernel_evals, g2.kde_queries)
# one psum per edge batch of 1024 on the mesh (the ring adds none), none
# on one device: from the counter words and on the edge scan's span
assert (g1.device_psums, g2.device_psums) == (0, 3)
assert edge_spans == [dict(psums=0, shards=1), dict(psums=3, shards=8)]
lt = np.diag(k.sum(1) - 1) - (k - np.eye(300))
err = np.linalg.norm(g2.laplacian_dense() - lt) / np.linalg.norm(lt)
assert err < 0.5, err
g1s = spectral_sparsify(x, ker, 3000, seed=0)
g2s = spectral_sparsify(x, ker, 3000, seed=0, mesh=mesh)
assert g1s.kernel_evals == g2s.kernel_evals    # stratified counters too

a1 = estimate_arboricity(x, ker, 4000, estimator="exact", seed=0)
a2 = estimate_arboricity(x, ker, 4000, estimator="exact", seed=0, mesh=mesh)
tr = exact_arboricity(ker, x)
assert a1.kernel_evals == a2.kernel_evals and abs(a2.density - tr) / tr < 0.15

t1 = estimate_triangle_weight(x, ker, 300, 16, estimator="exact", seed=0)
t2 = estimate_triangle_weight(x, ker, 300, 16, estimator="exact", seed=0, mesh=mesh)
tt = exact_triangle_weight(ker, x)
assert t1.kernel_evals == t2.kernel_evals and abs(t2.total_weight - tt) / tt < 0.3

r1 = fkv_lowrank(x, ker, rank=6, num_rows=120, seed=0)
r2 = fkv_lowrank(x, ker, rank=6, num_rows=120, seed=0, mesh=mesh)
assert r1.kernel_evals == r2.kernel_evals
assert projection_error(k, r2.u) < optimal_error(k, 6) + 0.02 * np.linalg.norm(k) ** 2

e1 = top_eigenvalue(x, ker, t=150, method="noisy_power", seed=0)
e2 = top_eigenvalue(x, ker, t=150, method="noisy_power", seed=0, mesh=mesh)
assert e1.kernel_evals == e2.kernel_evals
assert abs(e2.eigenvalue - e1.eigenvalue) / abs(e1.eigenvalue) < 1e-3

sp1 = approximate_spectrum(x, ker, length=5, num_sources=6, walks_per_source=8, seed=0)
sp2 = approximate_spectrum(x, ker, length=5, num_sources=6, walks_per_source=8, seed=0, mesh=mesh)
assert sp1.kernel_evals == sp2.kernel_evals
print("PIPELINES_OK")
""")
    assert "PIPELINES_OK" in out


def test_param_sharding_rules():
    """Divisibility fallbacks: granite vocab, yi kv heads."""
    out = _run("""
import jax, jax.numpy as jnp
from repro.configs.base import get_config
from repro.models import transformer as T
from repro.distributed import sharding as shard
mesh = make_mesh((2, 4), ("data", "model"))
for arch in ("yi_6b", "granite_3_2b", "qwen3_moe_235b_a22b"):
    cfg = get_config(arch)
    ps = jax.eval_shape(lambda: T.init_params(jax.random.PRNGKey(0), cfg))
    flat = jax.tree_util.tree_flatten_with_path(ps)[0]
    for path, leaf in flat:
        spec = shard.param_spec(path, leaf, mesh)
        # every sharded dim must divide
        for dim, entry in enumerate(spec):
            if entry is None: continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            size = 1
            for a in axes: size *= mesh.shape[a]
            assert leaf.shape[dim] % size == 0, (arch, path, leaf.shape, spec)
print("RULES_OK")
""")
    assert "RULES_OK" in out


@pytest.mark.parametrize("arch", ["yi_6b", "rwkv6_3b", "granite_moe_1b_a400m"])
def test_small_mesh_dryrun_train_and_decode(arch):
    """Reduced-config lower+compile on a (2,2,2) pod mesh -- the same code
    path as the production dry-run."""
    out = _run(f"""
import dataclasses, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs.base import get_reduced, ShapeConfig
from repro.data.pipeline import input_specs, token_split
from repro.distributed import sharding as shard
from repro.models import transformer as T
from repro.models.layers import activation_sharding
from repro.train import optimizer as opt
from repro.train.train_step import make_train_step, make_decode_step
from repro.roofline.analysis import collective_bytes

cfg = get_reduced("{arch}")
shape = ShapeConfig("t", 64, 8, "train")
mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
params_s = jax.eval_shape(lambda: T.cast_params(T.init_params(jax.random.PRNGKey(0), cfg), jnp.bfloat16))
p_sh = shard.param_shardings(params_s, mesh)
specs = input_specs(cfg, shape)
b_sh = {{k: NamedSharding(mesh, shard.batch_spec(mesh, v.ndim, v.shape[0])) for k, v in specs.items()}}
o_s = jax.eval_shape(opt.init_adamw, params_s)
o_sh = opt.AdamWState(step=NamedSharding(mesh, P()), m=p_sh, v=jax.tree.map(lambda s: s, p_sh))
with activation_sharding(mesh, ("pod", "data")):
    comp = jax.jit(make_train_step(cfg), in_shardings=(p_sh, o_sh, b_sh),
                   out_shardings=(p_sh, o_sh, None)).lower(params_s, o_s, specs).compile()
cs = collective_bytes(comp.as_text(), default_trip=cfg.num_layers)
assert cs.total_bytes > 0
assert comp.memory_analysis().temp_size_in_bytes > 0
print("DRYRUN_OK", cs.count_by_kind)
""")
    assert "DRYRUN_OK" in out
