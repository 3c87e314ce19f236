"""Streaming kernel-graph engine (DESIGN.md §12): randomized
mutation-sequence equivalence against fresh rebuilds.

The contract under test: after ANY interleaving of insert / delete /
update, every consumer's patched derived state answers exactly like an
engine freshly built at the current epoch -- level-1 block sums and
``prob_of`` (deterministic exact level-1: tight allclose), degrees and
row norms (``degree_delta`` patch vs. recomputation), the hashed bucket
layout (same-key ``hashed_query`` parity vs. ``build_hash_state``), walk
draw streams (bitwise, shared PRNG key), and the 8-device sharded path
(subprocess) where the mutation program must also be jaxpr-verifiably
collective-free so the §9 one-psum-per-draw schedule is untouched.

Parity rule (the reason every equivalence test pins ``exact_blocks=True``
or an exact estimator): patched state = old estimate + EXACT delta, so
numeric equality with a fresh build holds only for deterministic level-1
reads.  Randomized (stratified / hashed-FAR) paths agree in distribution,
not per-draw -- those are covered by the TV test and the same-key hashed
parity instead.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import subproc
from repro.core.dataset import DynamicDataset, coalesce_mutations
from repro.core.kernels_fn import gaussian
from repro.ft import guards as _g


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(0)
    x0 = rng.normal(0, 0.7, size=(192, 6)).astype(np.float32)
    return rng, x0, gaussian(1.0)


def _mutate(ds, rng, n_ins=5, dele=(40, 44), upd=(50, 52), keep=()):
    """One standard interleaving: insert a few, delete a range (minus any
    ``keep`` slots a test still holds as a frontier), move two."""
    ins = rng.normal(0, 0.7, size=(n_ins, ds.d)).astype(np.float32)
    slots = ds.insert_rows(ins)
    dead = np.setdiff1d(np.arange(*dele), np.asarray(keep, np.int64))
    ds.delete_rows(dead)
    us = np.setdiff1d(np.arange(*upd), dead)
    ds.update_rows(us, rng.normal(0, 0.7, size=(len(us), ds.d))
                   .astype(np.float32))
    return slots


# --------------------------------------------------------------------- #
# dataset core: epochs, journal, coalescing
# --------------------------------------------------------------------- #
def test_dataset_journal_contract(data):
    rng, x0, _ = data
    ds = DynamicDataset(x0, capacity=256, journal_limit=4)
    assert ds.epoch == 0 and ds.num_live == 192 and ds.n == 256
    assert ds.mutations_since(0) == []

    slots = ds.insert_rows(x0[:3] + 0.5)
    assert ds.epoch == 1 and list(slots) == [192, 193, 194]
    assert ds.is_live(slots)
    ds.delete_rows(slots[:1])
    assert ds.epoch == 2 and not ds.is_live(slots)

    batches = ds.mutations_since(0)
    assert [b.kind for b in batches] == ["insert", "delete"]
    # journal_limit=4: after 5 batches an epoch-0 consumer must rebuild
    for _ in range(3):
        ds.update_rows(np.array([0]), x0[:1])
    assert ds.mutations_since(0) is None
    assert len(ds.mutations_since(ds.epoch - 2)) == 2

    # structural epoch bumps invalidate the whole journal
    e = ds.epoch
    ds.compact()
    assert ds.epoch == e + 1 and ds.mutations_since(e) is None
    assert ds.num_live == 194 and ds.is_live(np.arange(194))

    ds2 = DynamicDataset(x0[:30], capacity=32)
    e = ds2.epoch
    ds2.insert_rows(x0[:8])            # overflow -> grow (doubling)
    assert ds2.capacity >= 64 and ds2.mutations_since(e) is None
    assert ds2.num_live == 38

    # dead slots sit at sentinel coordinates: exactly zero kernel mass
    k = gaussian(1.0)
    ds3 = DynamicDataset(x0, capacity=256)
    ds3.delete_rows(np.array([7]))
    kv = np.asarray(k.pairwise(ds3.x_pad[:1], ds3.x_pad[7:8]))
    assert kv.item() == 0.0


def test_coalesce_telescopes(data):
    rng, x0, _ = data
    ds = DynamicDataset(x0, capacity=256)
    first = np.asarray(ds.x_pad[5])
    ds.update_rows(np.array([5]), x0[10:11] + 1.0)
    ds.update_rows(np.array([5]), x0[10:11] + 2.0)   # second hop
    ds.delete_rows(np.array([9]))
    slots, old_x, new_x, old_live, new_live = \
        coalesce_mutations(ds.mutations_since(0))
    assert list(slots) == [5, 9]
    i5 = int(np.where(slots == 5)[0][0])
    # old side = FIRST touch, new side = LAST touch; the middle hop cancels
    np.testing.assert_array_equal(old_x[i5], first)
    np.testing.assert_array_equal(new_x[i5], x0[10] + 2.0)
    assert old_live[i5] and new_live[i5]
    i9 = int(np.where(slots == 9)[0][0])
    assert old_live[i9] and not new_live[i9]


# --------------------------------------------------------------------- #
# consumers: patched state answers like a fresh rebuild
# --------------------------------------------------------------------- #
def test_neighbor_prob_of_patch_matches_fresh(data):
    from repro.core.sampling.edge import NeighborSampler
    rng, x0, k = data
    ds = DynamicDataset(x0, capacity=256)
    nbr = NeighborSampler(ds.x_pad, k, dataset=ds, seed=3,
                          exact_blocks=True, block_size=16)
    src = np.arange(16)
    v, _ = nbr.sample(src)             # populates the §4 level-1 cache
    _mutate(ds, rng, dele=(40, 48), keep=np.asarray(v))
    p1 = nbr.prob_of(src, v)           # patch_block_sums on the old cache
    fresh = NeighborSampler(ds.x_pad, k, seed=3, exact_blocks=True,
                            block_size=16)
    p2 = fresh.prob_of(src, v)
    np.testing.assert_allclose(p1, p2, rtol=2e-5, atol=1e-7)

    # journal gap (compact) -> transparent full rebuild, same answers
    ds.compact()
    live = ds.live_slots()[:16]
    q1 = nbr.prob_of(live, np.roll(live, 1))
    q2 = NeighborSampler(ds.x_pad, k, seed=3, exact_blocks=True,
                         block_size=16).prob_of(live, np.roll(live, 1))
    np.testing.assert_allclose(q1, q2, rtol=2e-5, atol=1e-7)


def test_degree_patch_matches_fresh(data):
    from repro.core.sampling.edge import NeighborSampler
    from repro.core.sampling.vertex import DegreeSampler, streaming_degrees
    rng, x0, k = data
    ds = DynamicDataset(x0, capacity=256)
    nbr = NeighborSampler(ds.x_pad, k, dataset=ds, seed=5,
                          exact_blocks=True, block_size=16)
    deg = DegreeSampler(nbr.blocks, seed=7, dataset=ds)
    for i in range(3):                 # several batches, one coalesced patch
        _mutate(ds, rng, dele=(60 + 2 * i, 62 + 2 * i),
                upd=(70 + 2 * i, 72 + 2 * i))
    u = deg.sample(256)
    assert ds.is_live(u)
    d_fresh = streaming_degrees(nbr.blocks, ds)
    np.testing.assert_allclose(deg.degrees, d_fresh, rtol=5e-4, atol=5e-5)
    # dead slots carry exactly zero degree mass
    assert deg.degrees[60] == 0.0 and deg.degrees[61] == 0.0


def test_rownorm_patch_matches_fresh(data):
    from repro.core.sampling.rownorm import RowNormSampler
    rng, x0, k = data
    ds = DynamicDataset(x0, capacity=256)
    rn = RowNormSampler(None, k, estimator="exact", seed=1, dataset=ds)
    _mutate(ds, rng)
    idx = rn.sample(128)
    assert ds.is_live(idx)
    fresh = RowNormSampler(None, k, estimator="exact", seed=1, dataset=ds)
    np.testing.assert_allclose(rn.row_norms_sq, fresh.row_norms_sq,
                               rtol=5e-4, atol=5e-5)
    sk = rn.sketch_rows(idx[:8])
    assert np.isfinite(sk).all()


def test_hashed_patch_parity_same_key(data):
    """Patched ``HashState`` vs ``build_hash_state`` at the new epoch:
    delete + in-place update keep the frozen key set aligned with the
    rebuild, so est AND realized NEAR counts agree under the same PRNG
    key (the bucket members stay slot-sorted -- the bitwise contract)."""
    from repro.core.kde.hashed import HashedKDE
    from repro.kernels.kde_hash import ops as hops
    rng, x0, k = data
    ds = DynamicDataset(x0, capacity=256)
    est = HashedKDE(x0, k, seed=5, max_bucket=64, num_far_samples=32,
                    dataset=ds, overflow_cap=64)
    ds.delete_rows(np.arange(40, 56))
    ds.update_rows(np.array([3]), np.asarray(ds.x_pad[3:4]))  # same cell
    est._sync()
    assert est.rebuilds == 0           # patched, not compacted

    state2, _ = hops.build_hash_state(
        ds.x_pad, k, max_bucket=64, seed=5, live=ds.live_host,
        overflow_cap=64)
    y = jnp.asarray(x0[:16])
    key = jax.random.PRNGKey(123)
    cfg = dict(est._cfg)
    e1, c1, _ = hops.hashed_query(ds.x_pad, y, est.state, key, **cfg)
    e2, c2, _ = hops.hashed_query(ds.x_pad, y, state2, key, **cfg)
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))
    np.testing.assert_allclose(np.asarray(e1), np.asarray(e2), rtol=1e-6)

    # inserts land in the overflow region (unhashed cell) and are read by
    # the exact extra sweep: an isolated point reports its own unit mass
    iso = (x0[:1] + 37.0).astype(np.float32)
    ds.insert_rows(iso)
    q = np.asarray(est.query(jnp.asarray(iso)))
    assert abs(q.item() - 1.0) < 1e-2, q


def test_epoch_stale_raises_under_checks(data, monkeypatch):
    from repro.core.sampling.edge import NeighborSampler
    monkeypatch.setenv("REPRO_CHECKS", "1")
    rng, x0, k = data
    ds = DynamicDataset(x0, capacity=256)
    nbr = NeighborSampler(ds.x_pad, k, dataset=ds, seed=3,
                          exact_blocks=True, block_size=16)
    ds.delete_rows(np.array([11]))
    with pytest.raises(_g.EstimationError, match="EPOCH_STALE"):
        nbr.sample(np.array([11]))     # externally-held stale frontier
    assert nbr.status & _g.EPOCH_STALE
    v, _ = nbr.sample(np.array([0, 1]))   # live frontier still serves
    assert ds.is_live(v)


def test_robust_estimator_epoch_sync(data):
    """Satellite regression: a RobustEstimator built over a DynamicDataset
    must answer post-mutation queries at the NEW epoch -- stale stage
    states are dropped, not escalated against."""
    rng, x0, k = data
    ds = DynamicDataset(x0, capacity=400)
    est = _g.RobustEstimator(ds, k, seed=0, stages=("stratified", "exact"))
    base = np.asarray(est.query(jnp.asarray(x0[:2])))
    assert np.isfinite(base).all()

    # a dense far-away cluster only visible after the mutation
    c = x0[:1] + 25.0
    cluster = (c + 0.05 * rng.normal(size=(40, ds.d))).astype(np.float32)
    ds.insert_rows(cluster)
    v = np.asarray(est.query(jnp.asarray(cluster[:1])))
    assert v.item() > 10.0, v          # stale stages would report ~0
    assert est.stage_rebuilds >= 1
    assert est.n == ds.num_live        # compact live view refreshed


def test_walk_draw_stream_bitwise_after_patch(data):
    """Same seed, no draws before the mutation: the patched sampler and a
    fresh rebuild consume identical PRNG streams over identical patched
    coordinates, so walk endpoints match bitwise (the strongest form of
    the distribution-equivalence contract)."""
    from repro.core.sampling.edge import NeighborSampler
    rng, x0, k = data
    ds = DynamicDataset(x0, capacity=256)
    nbr = NeighborSampler(ds.x_pad, k, dataset=ds, seed=9,
                          exact_blocks=True, block_size=16)
    _mutate(ds, rng)
    starts = np.array([0, 1, 2, 3, 20, 21])
    end1, path1 = nbr.walk(starts, 4)
    fresh = NeighborSampler(ds.x_pad, k, seed=9, exact_blocks=True,
                            block_size=16)
    end2, path2 = fresh.walk(starts, 4)
    np.testing.assert_array_equal(np.asarray(end1), np.asarray(end2))
    np.testing.assert_array_equal(np.asarray(path1), np.asarray(path2))
    assert ds.is_live(np.asarray(end1))


def test_neighbor_distribution_tv_after_patch(data):
    """Stochastic level-1 (stratified): patched and fresh samplers with
    diverged keys agree in *distribution* -- total variation over the
    endpoint histogram of single-step draws from one source.  Seeds
    derive from ``stats.ROOT_SEED``; the tolerance is the precomputed
    ``stats.tv_tolerance`` bound (alpha = 1e-3) times a x2 slack because
    the 500 draws of a chunk share ONE stratified level-1 read (8
    independently-keyed chunks, so the iid bound under-counts the
    chunk-level noise; measured statistic under the pinned seed: 0.211
    vs. the inflated bound 0.439)."""
    import stats

    from repro.core.sampling.edge import NeighborSampler
    rng, x0, k = data
    x_small = x0[:96]
    ds = DynamicDataset(x_small, capacity=128)
    nbr = NeighborSampler(ds.x_pad, k, dataset=ds,
                          seed=stats.derive_seed("streaming", "tv-patched"),
                          block_size=16, samples_per_block=8)
    nbr.sample(np.arange(8))           # desync the key streams
    ds.delete_rows(np.arange(64, 80))
    ds.insert_rows((x_small[:4] + 0.3).astype(np.float32))
    fresh = NeighborSampler(ds.x_pad, k,
                            seed=stats.derive_seed("streaming", "tv-fresh"),
                            block_size=16, samples_per_block=8)
    # one stratified level-1 read is shared by a whole batch (one key per
    # frontier), so block-level noise is batch-correlated: average the
    # histograms over several independently-keyed chunks
    src = np.zeros(500, np.int64)
    h1 = np.zeros(ds.n)
    h2 = np.zeros(ds.n)
    reps = 8
    for _ in range(reps):
        v1, _ = nbr.sample(src)
        v2, _ = fresh.sample(src)
        assert ds.is_live(np.asarray(v1)) and ds.is_live(np.asarray(v2))
        h1 += np.bincount(np.asarray(v1), minlength=ds.n)
        h2 += np.bincount(np.asarray(v2), minlength=ds.n)
    tv = stats.tv_distance(h1, h2)
    tol = 2.0 * stats.tv_tolerance(ds.n, len(src) * reps, alpha=1e-3)
    assert tv < tol, (tv, tol)


def test_streaming_graph_end_to_end(data):
    from repro.core.streaming import StreamingKernelGraph
    rng, x0, k = data
    g = StreamingKernelGraph(x0, k, capacity=256, level1="hash", seed=11,
                             hash_opts=dict(max_bucket=64))
    g.insert(rng.normal(0, 0.7, size=(6, 6)).astype(np.float32))
    g.delete(np.arange(5))
    g.update(np.array([30, 31]),
             rng.normal(0, 0.7, size=(2, 6)).astype(np.float32))
    u = g.sample_vertices(64)
    v, q = g.sample_neighbors(u)
    assert g.dataset.is_live(u) and g.dataset.is_live(v)
    assert np.isfinite(np.asarray(q)).all()
    e = g.sample_edges(128)
    assert len(e[0]) == 128
    end, _ = g.walk(u[:8], 3)
    assert g.dataset.is_live(np.asarray(end))
    rep = g.status_report()
    assert rep["num_live"] == g.num_live and rep["mutation_batches"] == 3
    d = g.degrees()
    assert d[0] == 0.0 and (d[np.asarray(g.dataset.live_slots())] > 0).all()


# --------------------------------------------------------------------- #
# 8-device sharded case (subprocess owns its XLA_FLAGS)
# --------------------------------------------------------------------- #
def _run(code: str, devices: int = 8) -> str:
    return subproc.run_devices(code, devices, tail=1500)


def test_sharded_streaming_zero_collective_patch():
    """8-device: the mutation program is jaxpr-verifiably collective-free,
    the per-draw-batch collective schedule is UNCHANGED by patching, and
    patched level-1 sums / prob_of / hashed queries match fresh rebuilds
    at the new epoch."""
    out = _run("""
import jax, jax.numpy as jnp, numpy as np
from repro.core.kernels_fn import gaussian
from repro.core.dataset import DynamicDataset, coalesce_mutations
from repro.kernels.kde_sampler.sharded import ShardedBlocks, collective_counts
ker = gaussian(1.0)
rng = np.random.default_rng(0)
x0 = rng.normal(0, 0.7, (192, 6)).astype(np.float32)
mesh = make_mesh((8,), ("data",))

ds = DynamicDataset(x0, capacity=256)
eng = ShardedBlocks(mesh, ds.x_pad, ker, block_size=16, exact=True)
key = jax.random.PRNGKey(1)
src = jnp.arange(24, dtype=jnp.int32)
base = collective_counts(lambda s, k: eng.fused_sample(s, k), src, key)
assert base["psum_total"] == 1, base

ds.insert_rows(rng.normal(0, 0.7, (8, 6)).astype(np.float32))
ds.delete_rows(np.arange(120, 128))
ds.update_rows(np.arange(4), rng.normal(0, 0.7, (4, 6)).astype(np.float32))
slots, old_x, new_x, old_live, new_live = coalesce_mutations(ds.mutations_since(0))

pcc = collective_counts(eng._patch_program(), *eng._sharded_args(),
                        jnp.asarray(slots, jnp.int32),
                        jnp.asarray(new_x, jnp.float32))
assert pcc["psum_total"] == 0 and pcc["ppermute_total"] == 0, pcc
eng.patch_rows(slots, new_x)

fresh = ShardedBlocks(mesh, ds.x_pad, ker, block_size=16, exact=True)
s1 = np.asarray(eng.masked_block_sums(src, key)[0])
s2 = np.asarray(fresh.masked_block_sums(src, key)[0])
np.testing.assert_allclose(s1, s2, rtol=1e-5, atol=1e-6)
after = collective_counts(lambda s, k: eng.fused_sample(s, k), src, key)
assert after == base, (base, after)
print("SHARDED_PATCH_OK")
""")
    assert "SHARDED_PATCH_OK" in out


def test_sharded_neighbor_prob_of_patch_matches_fresh():
    out = _run("""
import jax, jax.numpy as jnp, numpy as np
from repro.core.kernels_fn import gaussian
from repro.core.dataset import DynamicDataset
from repro.core.sampling.edge import NeighborSampler
ker = gaussian(1.0)
rng = np.random.default_rng(0)
x0 = rng.normal(0, 0.7, (192, 6)).astype(np.float32)
mesh = make_mesh((8,), ("data",))
ds = DynamicDataset(x0, capacity=256)
nbr = NeighborSampler(ds.x_pad, ker, mode="blocked", block_size=16,
                      exact_blocks=True, mesh=mesh, seed=3, dataset=ds)
src = np.arange(16)
v, _ = nbr.sample(src)
ds.insert_rows(rng.normal(0, 0.7, (6, 6)).astype(np.float32))
dead = np.setdiff1d(np.arange(150, 192), np.asarray(v))[:8]
ds.delete_rows(dead)
ds.update_rows(np.arange(8, 10), rng.normal(0, 0.7, (2, 6)).astype(np.float32))
p1 = nbr.prob_of(src, v)
fresh = NeighborSampler(ds.x_pad, ker, mode="blocked", block_size=16,
                        exact_blocks=True, mesh=mesh, seed=3)
p2 = fresh.prob_of(src, v)
np.testing.assert_allclose(p1, p2, rtol=2e-5, atol=1e-7)
print("SHARDED_NBR_OK")
""")
    assert "SHARDED_NBR_OK" in out


def test_sharded_hash_patch_parity_one_psum():
    out = _run("""
import jax, jax.numpy as jnp, numpy as np
from repro.core.kernels_fn import gaussian
from repro.core.dataset import DynamicDataset, coalesce_mutations
from repro.kernels.kde_hash.sharded import ShardedHashTable
from repro.kernels.kde_sampler.sharded import collective_counts
ker = gaussian(1.0)
rng = np.random.default_rng(0)
x0 = rng.normal(0, 0.7, (192, 6)).astype(np.float32)
mesh = make_mesh((8,), ("data",))
ds = DynamicDataset(x0, capacity=256)
tab = ShardedHashTable(mesh, np.asarray(ds.x_pad), ker, max_bucket=32,
                       num_far_samples=16, seed=2, live=ds.live_host,
                       overflow_cap=32)
y = jnp.asarray(x0[:8]); k0 = jax.random.PRNGKey(7)
qcc = collective_counts(tab._program(), tab._keys, tab._members,
                        tab._counts, tab._overflow, tab._dims, tab._shift,
                        tab.x_sh, y, k0)
assert qcc["psum_total"] == 1 and qcc["ppermute_total"] == 0, qcc

# delete + in-place update: key set stays aligned with a rebuild
ds.delete_rows(np.arange(16, 32))
ds.update_rows(np.array([3]), np.asarray(ds.x_pad[3:4]))
slots, old_x, new_x, old_live, new_live = coalesce_mutations(ds.mutations_since(0))
assert tab.patch_rows(slots, old_x, new_x, old_live, new_live)
e1, c1, _ = tab.query(y, k0)
tab2 = ShardedHashTable(mesh, np.asarray(ds.x_pad), ker, max_bucket=32,
                        num_far_samples=16, seed=2, live=ds.live_host,
                        overflow_cap=32)
e2, c2, _ = tab2.query(y, k0)
np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))
np.testing.assert_allclose(np.asarray(e1), np.asarray(e2), rtol=1e-6)

# insert lands in the owning shard's overflow; the exact sweep reads it
iso = (x0[:1] + 37.0).astype(np.float32)
e0 = int(ds.epoch)
ds.insert_rows(iso)
slots, old_x, new_x, old_live, new_live = coalesce_mutations(ds.mutations_since(e0))
assert tab.patch_rows(slots, old_x, new_x, old_live, new_live)
ei, _, _ = tab.query(jnp.asarray(iso), jax.random.PRNGKey(9))
assert abs(float(np.asarray(ei)[0]) - 1.0) < 1e-2, ei
print("SHARDED_HASH_OK")
""")
    assert "SHARDED_HASH_OK" in out
