"""Multi-tenant batched serving layer (DESIGN.md §13): batched-vs-
sequential distributional parity (bitwise for keyed draws/walks at bucket
width, TV within precomputed tolerance for stratified and hashed draws),
tenant LRU lifecycle, per-request guard fan-out, the serve CLI's
graph-stream and multi-tenant paths, and an 8-simulated-device subprocess
assertion that batching adds ZERO extra collectives per draw batch.

All distributional assertions derive their keys from ``stats.ROOT_SEED``
and compare against the precomputed critical values of ``tests/stats.py``
(false-positive budget documented there)."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import stats
import subproc
from repro.core.kernels_fn import gaussian
from repro.core.serving import (DEFAULT_BUCKETS, KernelGraphServable,
                                shape_bucket)
from repro.ft import guards as _g
from repro.kernels.kde_sampler import ops as _ops
from repro.obs import counters as _c

N, D = 192, 4


def _data(label, shift=0.0):
    rng = np.random.default_rng(stats.derive_seed("serving", label))
    return (rng.normal(0, 0.6, size=(N, D)) + shift).astype(np.float32)


@pytest.fixture(scope="module")
def srv2():
    """Two flat tenants with IDENTICAL static config (they stack into one
    batch group) over different datasets."""
    s = KernelGraphServable(max_resident=4)
    s.add_tenant("a", _data("a"), gaussian(1.0), block_size=16, seed=3)
    s.add_tenant("b", _data("b", 0.8), gaussian(1.0), block_size=16, seed=4)
    return s


def _cfg(srv, name):
    return srv.tenant(name).admit()._cfg


# ------------------------------------------------------------------- #
# bitwise parity: a served request IS the sequential program
# ------------------------------------------------------------------- #
def test_sample_bitwise_parity_multi_tenant(srv2):
    """Requests at bucket width on two stacked tenants reproduce the
    sequential single-tenant ``fused_sample`` bit-for-bit (same key), and
    ride in ONE batch group."""
    src = np.arange(16)
    ra = srv2.submit("a", "sample", src=src, seed=101)
    rb = srv2.submit("b", "sample", src=src + 32, seed=202)
    st = srv2.tick()
    assert st["groups"] == 1 and ra.error is None and rb.error is None
    for r, name, s in ((ra, "a", src), (rb, "b", src + 32)):
        nbr = srv2.tenant(name).admit()
        nb0, p0, _, _ = _ops.fused_sample(
            nbr.x, nbr.x_sq, jnp.asarray(s, jnp.int32),
            jax.random.PRNGKey(r.seed), **nbr._cfg)
        np.testing.assert_array_equal(r.result[0], np.asarray(nb0))
        np.testing.assert_array_equal(r.result[1], np.asarray(p0))


def test_walk_bitwise_parity(srv2):
    """Keyed walks through the servable equal the sequential walk_scan
    endpoints bitwise (same per-request key stream)."""
    starts, length = np.arange(8), 5
    r = srv2.submit("a", "walk", starts=starts, length=length, seed=77)
    srv2.tick()
    assert r.error is None
    nbr = srv2.tenant("a").admit()
    keys = jax.random.split(jax.random.PRNGKey(77), length)
    e0, _, _, _ = _ops.walk_scan(nbr.x, nbr.x_sq,
                                 jnp.asarray(starts, jnp.int32), keys,
                                 rounds=0, slack=2.0, record_path=False,
                                 **nbr._cfg)
    np.testing.assert_array_equal(r.result[0], np.asarray(e0))


def test_prob_of_bitwise_parity(srv2):
    """Served q(dst | src) equals the sequential masked level-1 read +
    exact level-2 probability with the same key."""
    src, dst = np.arange(16), (np.arange(16) + 5) % N
    r = srv2.submit("b", "prob_of", src=src, dst=dst, seed=55)
    srv2.tick()
    assert r.error is None
    nbr = srv2.tenant("b").admit()
    key = jax.random.PRNGKey(55)
    bs, _ = _ops.masked_block_sums(nbr.x, nbr.x_sq,
                                   jnp.asarray(src, jnp.int32),
                                   key, **nbr._cfg)
    p0, _ = _ops.prob_of_from_block_sums(nbr.x, nbr.x_sq,
                                         jnp.asarray(src, jnp.int32),
                                         jnp.asarray(dst, jnp.int32), bs,
                                         **nbr._l2_cfg)
    np.testing.assert_array_equal(r.result, np.asarray(p0))


def test_query_parity_dense(srv2):
    """Served KDE queries draw the SAME stratified block subsamples as
    the sequential read (same key); the final row-sum is only
    reduction-order-tight (vmap may reassociate the float32 sum), so the
    estimate comparison is allclose at 1e-6, not bitwise."""
    rng = np.random.default_rng(stats.derive_seed("serving", "query"))
    y = rng.normal(0, 0.6, size=(8, D)).astype(np.float32)
    r = srv2.submit("a", "query", y=y, seed=33)
    srv2.tick()
    assert r.error is None
    nbr = srv2.tenant("a").admit()
    c = nbr._cfg
    bs, _ = _ops.stratified_block_sums(
        jnp.asarray(y), nbr.x, nbr.x_sq, jax.random.PRNGKey(33),
        kind=c["kind"], inv_bw=c["inv_bw"], beta=c["beta"],
        pairwise=c["pairwise"], block_size=c["block_size"],
        num_blocks=c["num_blocks"], n=c["n"], s=c["s"])
    np.testing.assert_allclose(r.result, np.asarray(bs.sum(-1)), rtol=1e-6)


def test_hash_tenants_bitwise_sample_and_query():
    """Hashed level-1 tenants: stacked HashState draws and hashed queries
    through the servable are bitwise the sequential per-tenant calls."""
    from repro.kernels.kde_hash import ops as _hops
    srv = KernelGraphServable()
    srv.add_tenant("h1", _data("h1"), gaussian(1.0), level1="hash",
                   block_size=16, seed=5)
    srv.add_tenant("h2", _data("h2", 0.5), gaussian(1.0), level1="hash",
                   block_size=16, seed=6)
    src = np.arange(16)
    rng = np.random.default_rng(stats.derive_seed("serving", "hq"))
    y = rng.normal(0, 0.6, size=(8, D)).astype(np.float32)
    r1 = srv.submit("h1", "sample", src=src, seed=11)
    r2 = srv.submit("h2", "sample", src=src + 8, seed=12)
    rq = srv.submit("h1", "query", y=y, seed=13)
    st = srv.tick()
    # the hash-state layouts are data-dependent: h1/h2 stack into one
    # sample group only when their bucket counts coincide (2 groups),
    # otherwise they serve in separate groups (3) -- both are correct
    assert st["failed"] == 0 and st["groups"] in (2, 3)
    for r, name, s in ((r1, "h1", src), (r2, "h2", src + 8)):
        nbr = srv.tenant(name).admit()
        nb0, p0, _, _ = _ops.fused_sample(
            nbr.x, nbr.x_sq, jnp.asarray(s, jnp.int32),
            jax.random.PRNGKey(r.seed), hstate=nbr._hstate, **nbr._cfg)
        np.testing.assert_array_equal(r.result[0], np.asarray(nb0))
    hq = srv.tenant("h1").admit().hash_estimator
    e0, _, _ = _hops.hashed_query(srv.tenant("h1").admit().x, jnp.asarray(y),
                                  hq.state, jax.random.PRNGKey(13),
                                  **hq._cfg)
    np.testing.assert_array_equal(rq.result, np.asarray(e0))


# ------------------------------------------------------------------- #
# the packed level-1 pass of one-tenant exact Pallas groups
# ------------------------------------------------------------------- #
def _pallas_grids(fn, *args, **kw):
    """The grid of every ``pallas_call`` in ``fn``'s traced program."""
    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield tuple(eqn.params["grid_mapping"].grid)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from walk(sub)
    return list(walk(jax.make_jaxpr(lambda *a: fn(*a, **kw))(*args).jaxpr))


def _exact_pallas_cfg(bm):
    return dict(kind="gaussian", inv_bw=1.0, beta=1.0, pairwise=None,
                block_size=16, num_blocks=N // 16, n=N, s=8, exact=True,
                use_pallas=True, interpret=True, bm=bm, level1="blocked",
                num_far=64, precision="f32")


@pytest.mark.parametrize("R,w,bm", [(3, 5, 8), (4, 8, 8)])
def test_packed_level1_pass_matches_per_request_reads(R, w, bm):
    """On a one-tenant arena the exact Pallas draw and ``prob_of``
    programs read level 1 in ONE pass over all R w rows (grid
    ``ceil(R w / bm)`` row tiles, a request's rows may straddle two), and
    every lane equals the per-request program bit for bit: neighbours,
    probabilities, block sums and counter words."""
    x = jnp.asarray(_data("packed"))
    x_sq = jnp.sum(x * x, -1)
    xa, xa_sq = x[None], x_sq[None]
    cfg = _exact_pallas_cfg(bm)
    rng = np.random.default_rng(stats.derive_seed("serving", "packed", R))
    src = rng.integers(0, N, size=(R, w)).astype(np.int32)
    dst = ((src + rng.integers(1, N, size=(R, w))) % N).astype(np.int32)
    seeds = [int(v) for v in rng.integers(0, 2**31, size=R)]
    keys = np.stack([np.asarray(jax.random.PRNGKey(v)) for v in seeds])
    tidx = np.zeros(R, np.int32)
    tiles = (-(-R * w // bm), cfg["num_blocks"])
    assert _pallas_grids(_ops.batched_fused_sample, xa, xa_sq, tidx, src,
                         keys, **cfg) == [tiles]
    assert _pallas_grids(_ops.batched_prob_of, xa, xa_sq, tidx, src, dst,
                         keys, **cfg) == [tiles]
    nb, prob, bs, cw = _ops.batched_fused_sample(xa, xa_sq, tidx, src, keys,
                                                 **cfg)
    pq, pcw = _ops.batched_prob_of(xa, xa_sq, tidx, src, dst, keys, **cfg)
    l2 = {k: cfg[k] for k in ("kind", "inv_bw", "beta", "pairwise",
                              "block_size", "n")}
    for r in range(R):
        key = jax.random.PRNGKey(seeds[r])
        s_r, d_r = jnp.asarray(src[r]), jnp.asarray(dst[r])
        for got, want in zip((nb[r], prob[r], bs[r], cw[r]),
                             _ops.fused_sample(x, x_sq, s_r, key, **cfg)):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        bs0, w1 = _ops.masked_block_sums(x, x_sq, s_r, key, **cfg)
        p0, w2 = _ops.prob_of_from_block_sums(x, x_sq, s_r, d_r, bs0, **l2)
        np.testing.assert_array_equal(np.asarray(pq[r]), np.asarray(p0))
        np.testing.assert_array_equal(
            np.asarray(pcw[r]),
            np.asarray(_c.fold_status(_c.fold(w1, w2),
                                      _g.result_status(p0))))


def test_level1_pass_dispatch_rule_and_counter(monkeypatch):
    """Both sides of the dispatch rule through the servable, with Pallas
    on (interpreted): a one-tenant group packs its rows into one pass, a
    two-tenant group reads per request; ``serve.level1_passes`` and the
    tick's ``level1_passes`` count ``ceil(R wb / bm)`` and
    ``R ceil(wb / bm)``, and the served lanes equal the sequential calls."""
    from repro.kernels import platform
    from repro.obs import metrics as M

    monkeypatch.setattr(platform, "resolve", lambda *a, **k: (True, True))
    srv = KernelGraphServable(max_resident=4)
    for name, shift, seed in (("a", 0.0, 3), ("b", 0.8, 4)):
        srv.add_tenant(name, _data(name, shift), gaussian(1.0),
                       block_size=16, exact_blocks=True, seed=seed)
    cfg = _cfg(srv, "a")
    assert cfg["use_pallas"] and cfg["interpret"] and cfg["bm"] == 128
    src, wb = np.arange(16), 16
    M.reset()
    M.enable()
    try:
        one = [srv.submit("a", "sample", src=src + 8 * i, seed=500 + i)
               for i in range(3)]
        one.append(srv.submit("a", "prob_of", src=src, dst=(src + 5) % N,
                              seed=510))
        st1 = srv.tick()
        two = [srv.submit(nm, "sample", src=src + 8 * i, seed=520 + i)
               for i, nm in enumerate("aab")]
        st2 = srv.tick()
        counted = M.get_registry()["counters"]["serve.level1_passes"]
    finally:
        M.disable()
        M.reset()
    assert st1["failed"] == st2["failed"] == 0
    assert st1["level1_passes"] == -(-3 * wb // 128) + 1 == 2
    assert st2["level1_passes"] == 3 * -(-wb // 128) == 3
    assert counted == 5
    tidx, keys = np.zeros(3, np.int32), np.zeros((3, 2), np.uint32)
    srcs = np.zeros((3, wb), np.int32)
    xa1 = jnp.stack([srv.tenant("a").admit().x])
    xa2 = jnp.stack([srv.tenant(nm).admit().x for nm in "aab"])
    grid1 = _pallas_grids(_ops.batched_fused_sample, xa1,
                          jnp.sum(xa1 * xa1, -1), tidx, srcs, keys, **cfg)
    grid2 = _pallas_grids(_ops.batched_fused_sample, xa2,
                          jnp.sum(xa2 * xa2, -1), tidx, srcs, keys, **cfg)
    nbk = cfg["num_blocks"]
    assert grid1 == [(1, nbk)] and grid2 == [(3, 1, nbk)]
    for r, nm in zip(one[:3] + two, "aaa" + "aab"):
        nbr = srv.tenant(nm).admit()
        nb0, p0, _, _ = _ops.fused_sample(
            nbr.x, nbr.x_sq, jnp.asarray(r.payload["src"], jnp.int32),
            jax.random.PRNGKey(r.seed), **nbr._cfg)
        np.testing.assert_array_equal(r.result[0], np.asarray(nb0))
        np.testing.assert_array_equal(r.result[1], np.asarray(p0))


_QUERY_KEYS = ("kind", "inv_bw", "beta", "pairwise", "block_size",
               "num_blocks", "n", "s", "exact", "use_pallas", "interpret",
               "bm", "level1", "precision")


@pytest.mark.parametrize("R,w,bm", [(3, 5, 8), (4, 8, 8), (2, 3, 16)])
def test_packed_query_pass_matches_per_request_reads(R, w, bm):
    """On a one-tenant exact Pallas arena ``batched_kde_query`` reads the
    R w query points in ONE blocksum pass (grid ``ceil(R w / bm)`` row
    tiles; a request may straddle two): each lane's block sums are bitwise
    a per-request ``kde_blocksum``, and its estimates, status words
    (``ZERO_MASS`` for a point far from every row included) and counter
    words match the jnp sweep's."""
    from repro.kernels.kde_rowsum.ops import kde_blocksum
    x = jnp.asarray(_data("packed-query"))
    x_sq = jnp.sum(x * x, -1)
    xa, xa_sq = x[None], x_sq[None]
    cfg = _exact_pallas_cfg(bm)
    q = {k: cfg[k] for k in _QUERY_KEYS}
    rng = np.random.default_rng(stats.derive_seed("serving", "pq", R, w))
    y = rng.normal(0, 0.6, size=(R, w, D)).astype(np.float32)
    y[-1, -1] = 50.0
    keys = np.zeros((R, 2), np.uint32)
    tidx = np.zeros(R, np.int32)
    nb = cfg["num_blocks"]
    assert _pallas_grids(_ops.batched_kde_query, xa, xa_sq, tidx, y, keys,
                         **q) == [(-(-R * w // bm), nb)]
    est, cw = _ops.batched_kde_query(xa, xa_sq, tidx, y, keys, **q)
    est0, cw0 = _ops.batched_kde_query(xa, xa_sq, tidx, y, keys,
                                       **dict(q, use_pallas=False))
    bs = _ops._query_rows(
        x, jnp.asarray(y.reshape(R * w, D)), kind="gaussian", inv_bw=1.0,
        beta=1.0, block_size=16, interpret=True, bm=bm,
        precision="f32").reshape(R, w, nb)
    for r in range(R):
        want = kde_blocksum(y[r], x, gaussian(1.0), bm=bm, bn=16,
                            interpret=True)
        np.testing.assert_array_equal(np.asarray(bs[r]), np.asarray(want))
    np.testing.assert_allclose(np.asarray(est), np.asarray(est0), rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(cw), np.asarray(cw0))
    status = np.asarray(_c.status_of(cw))
    assert status[-1] & _g.ZERO_MASS and not status[:-1].any()
    assert np.asarray(cw)[:, 1].tolist() == [w * N] * R


def test_query_pass_dispatch_rule_and_counter(monkeypatch):
    """Both sides of the dispatch rule for queries through the servable,
    with Pallas on (interpreted): a one-tenant query group packs into
    ``ceil(R wb / bm)`` passes, a two-tenant group keeps the per-request
    jnp sweep and counts 0; both answer the exact row sums."""
    from repro.kernels import platform
    from repro.obs import metrics as M

    monkeypatch.setattr(platform, "resolve", lambda *a, **k: (True, True))
    srv = KernelGraphServable(max_resident=4)
    for name, shift, seed in (("a", 0.0, 3), ("b", 0.8, 4)):
        srv.add_tenant(name, _data(name, shift), gaussian(1.0),
                       block_size=16, exact_blocks=True, seed=seed)
    assert _cfg(srv, "a")["use_pallas"]
    rng = np.random.default_rng(stats.derive_seed("serving", "pq-rule"))
    ys = rng.normal(0, 0.6, size=(6, 8, D)).astype(np.float32)
    M.reset()
    M.enable()
    try:
        one = [srv.submit("a", "query", y=ys[i], seed=600 + i)
               for i in range(3)]
        st1 = srv.tick()
        two = [srv.submit(nm, "query", y=ys[3 + i], seed=610 + i)
               for i, nm in enumerate("aab")]
        st2 = srv.tick()
        counted = M.get_registry()["counters"]["serve.level1_passes"]
    finally:
        M.disable()
        M.reset()
    assert st1["failed"] == st2["failed"] == 0
    assert st1["groups"] == st2["groups"] == 1
    assert st1["level1_passes"] == -(-3 * 8 // 128) == 1
    assert st2["level1_passes"] == 0 and counted == 1
    for r, nm in zip(one + two, "aaa" + "aab"):
        xt = np.asarray(srv.tenant(nm).admit().x, np.float64)
        d2 = ((r.payload["y"][:, None, :] - xt[None]) ** 2).sum(-1)
        np.testing.assert_allclose(r.result, np.exp(-d2).sum(-1),
                                   rtol=1e-5)


# ------------------------------------------------------------------- #
# distributional parity at non-bucket widths (padded lanes)
# ------------------------------------------------------------------- #
def _tv_parity(level1, label, alpha=1e-3):
    """Empirical TV between served draws (padded: width 100 -> bucket 128)
    and sequential draws from one source, against the stats.py tolerance."""
    srv = KernelGraphServable()
    srv.add_tenant("t", _data(label), gaussian(1.0), level1=level1,
                   block_size=16, seed=9)
    nbr = srv.tenant("t").admit()
    cap = srv.dataset("t").capacity
    u0, w, reps = 7, 100, 8
    src = np.full(w, u0)
    h_srv = np.zeros(cap)
    h_seq = np.zeros(cap)
    for i in range(reps):
        r = srv.submit("t", "sample", src=src,
                       seed=stats.derive_seed(label, "srv", i))
        srv.tick()
        assert r.error is None
        h_srv += np.bincount(r.result[0], minlength=cap)
        nb, _, _, _ = _ops.fused_sample(
            nbr.x, nbr.x_sq, jnp.asarray(src, jnp.int32),
            jax.random.PRNGKey(stats.derive_seed(label, "seq", i)),
            hstate=nbr._hstate, **nbr._cfg)
        h_seq += np.bincount(np.asarray(nb), minlength=cap)
    tv = stats.tv_distance(h_srv, h_seq)
    tol = stats.tv_tolerance(cap, w * reps, alpha=alpha)
    assert tv < tol, (tv, tol)


def test_sample_tv_parity_stratified_padded():
    """Padded stratified draws are distribution-identical to sequential
    ones (alpha = 1e-3 documented in tests/stats.py)."""
    _tv_parity("blocked", "tv-blocked")


def test_sample_tv_parity_hash_padded():
    """Padded hashed-level-1 draws are distribution-identical to
    sequential ones."""
    _tv_parity("hash", "tv-hash")


def test_padding_non_bucket_widths_share_group(srv2):
    """Requests of widths 10 and 13 pad to the same 16-bucket, ride one
    group, and return exactly their own lanes."""
    ra = srv2.submit("a", "sample", src=np.arange(10), seed=301)
    rb = srv2.submit("b", "sample", src=np.arange(13), seed=302)
    st = srv2.tick()
    assert st["groups"] == 1
    assert ra.result[0].shape == (10,) and rb.result[0].shape == (13,)
    assert np.isfinite(ra.result[1]).all() and np.isfinite(rb.result[1]).all()
    assert shape_bucket(10) == shape_bucket(13) == 16
    assert shape_bucket(DEFAULT_BUCKETS[-1] + 1) == 512


# ------------------------------------------------------------------- #
# tenant lifecycle + guards
# ------------------------------------------------------------------- #
def test_lru_admission_eviction_readmission():
    """max_resident=1: serving tenant b evicts a's device state; a's next
    request transparently rebuilds (builds counter) and still serves."""
    srv = KernelGraphServable(max_resident=1)
    srv.add_tenant("a", _data("lru-a"), gaussian(1.0), block_size=16)
    srv.add_tenant("b", _data("lru-b"), gaussian(1.0), block_size=16)
    srv.submit("a", "sample", src=np.arange(8), seed=1)
    srv.tick()
    assert srv.tenant("a").resident and not srv.tenant("b").resident
    srv.submit("b", "sample", src=np.arange(8), seed=2)
    srv.tick()
    assert not srv.tenant("a").resident and srv.tenant("b").resident
    assert srv.evictions == 1
    r = srv.submit("a", "sample", src=np.arange(8), seed=3)
    srv.tick()
    assert r.error is None and srv.tenant("a").builds == 2
    assert srv.report()["admissions"] == 3


def test_epoch_stale_isolated_per_request(monkeypatch):
    """REPRO_CHECKS=1: a request whose frontier row died gets ITS OWN
    EstimationError (EPOCH_STALE); the co-submitted healthy request on the
    same tenant is served normally."""
    monkeypatch.setenv("REPRO_CHECKS", "1")
    srv = KernelGraphServable()
    srv.add_tenant("t", _data("stale"), gaussian(1.0), block_size=16)
    srv.dataset("t").delete_rows(np.array([5]))
    bad = srv.submit("t", "sample", src=np.array([4, 5, 6, 7]), seed=1)
    ok = srv.submit("t", "sample", src=np.array([10, 11, 12, 13]), seed=2)
    st = srv.tick()
    assert st["stale"] == 1 and st["failed"] == 1 and st["served"] == 1
    assert bad.error is not None and "EPOCH_STALE" in str(bad.error)
    assert bad.result is None
    assert ok.error is None and np.isfinite(ok.result[1]).all()
    assert srv.dataset("t").is_live(ok.result[0])


def test_stale_flag_advisory_when_checks_off(monkeypatch):
    """Checks off: the stale request is still served, carrying the
    EPOCH_STALE bit on its own status word only."""
    monkeypatch.delenv("REPRO_CHECKS", raising=False)
    from repro.ft import guards as g
    srv = KernelGraphServable()
    srv.add_tenant("t", _data("stale2"), gaussian(1.0), block_size=16)
    srv.dataset("t").delete_rows(np.array([3]))
    bad = srv.submit("t", "sample", src=np.array([3, 8, 9, 10]), seed=1)
    ok = srv.submit("t", "sample", src=np.array([20, 21, 22, 23]), seed=2)
    srv.tick()
    assert bad.error is None and bad.result is not None
    assert bad.status & g.EPOCH_STALE
    assert not (ok.status & g.EPOCH_STALE)


def test_no_retrace_across_ticks(srv2):
    """Second tick at already-seen group shapes compiles nothing new."""
    src = np.arange(16)
    srv2.submit("a", "sample", src=src, seed=41)
    srv2.submit("b", "walk", starts=np.arange(8), length=5, seed=42)
    srv2.tick()
    before = dict(_ops.TRACE_COUNTS)
    srv2.submit("a", "sample", src=src + 1, seed=43)
    srv2.submit("b", "walk", starts=np.arange(8) + 1, length=5, seed=44)
    st = srv2.tick()
    assert st["failed"] == 0
    assert dict(_ops.TRACE_COUNTS) == before


def test_prob_of_width_mismatch_rejected_at_submit(srv2):
    """len(src) != len(dst) is a caller error surfaced at submit() --
    the malformed request never reaches (or poisons) a tick."""
    with pytest.raises(ValueError, match="widths differ"):
        srv2.submit("a", "prob_of", src=np.arange(4), dst=np.arange(5))
    assert srv2.pending() == 0


def test_group_failure_isolated_per_request(srv2):
    """Per-group fault isolation: a group that blows up on device (query
    points with the wrong feature dimension) attaches the exception to
    ITS requests only -- the healthy group of the same tick still serves
    and tick() itself never raises."""
    bad = srv2.submit("a", "query", y=np.zeros((4, D + 3), np.float32),
                      seed=881)
    ok = srv2.submit("b", "sample", src=np.arange(8), seed=882)
    st = srv2.tick()
    assert st["failed"] == 1 and st["served"] == 1
    assert bad.error is not None and bad.result is None and bad.done
    assert ok.error is None and np.isfinite(ok.result[1]).all()


def test_malformed_payload_isolated_per_request(srv2):
    """A request whose payload breaks grouping (walk without length)
    fails alone; the co-submitted request is served."""
    bad = srv2.submit("a", "walk", starts=np.arange(8), seed=883)
    ok = srv2.submit("a", "sample", src=np.arange(8), seed=884)
    st = srv2.tick()
    assert st["failed"] == 1 and st["served"] == 1
    assert isinstance(bad.error, KeyError) and bad.done
    assert ok.error is None


def test_different_feature_dims_do_not_share_group():
    """Tenants with identical static config but different feature
    dimension d carry d in their signature, so they form SEPARATE groups
    (stacking their arenas would be a shape error) and both serve."""
    srv = KernelGraphServable()
    srv.add_tenant("d4", _data("d4"), gaussian(1.0), block_size=16)
    rng = np.random.default_rng(stats.derive_seed("serving", "d6"))
    srv.add_tenant("d6", rng.normal(0, 0.6, (N, 6)).astype(np.float32),
                   gaussian(1.0), block_size=16)
    ra = srv.submit("d4", "sample", src=np.arange(8), seed=871)
    rb = srv.submit("d6", "sample", src=np.arange(8), seed=872)
    st = srv.tick()
    assert st["groups"] == 2 and st["failed"] == 0
    assert ra.error is None and rb.error is None


def test_mutation_between_ticks_refreshes_arena():
    """Mutating a tenant's dataset between ticks invalidates the stacked
    arena via the epoch key: post-mutation draws land on live rows."""
    srv = KernelGraphServable()
    srv.add_tenant("t", _data("mut"), gaussian(1.0), block_size=16)
    srv.submit("t", "sample", src=np.arange(8), seed=1)
    srv.tick()
    ds = srv.dataset("t")
    ds.delete_rows(np.arange(32, 64))
    r = srv.submit("t", "sample", src=np.arange(8), seed=2)
    st = srv.tick()
    assert st["failed"] == 0
    assert ds.is_live(r.result[0]), "sampled a deleted row"


# ------------------------------------------------------------------- #
# serve CLI: graph-stream backfill + multi-tenant path
# ------------------------------------------------------------------- #
def _metrics(capsys):
    out = capsys.readouterr().out
    line = [l for l in out.splitlines() if l.startswith("[serve] metrics ")]
    assert len(line) == 1, out
    return json.loads(line[0][len("[serve] metrics "):])


def test_serve_cli_graph_stream_clean_exit(capsys, monkeypatch):
    """`serve --graph-stream` over a random trace: exit 0 and a parsable
    metrics line with per-tick latencies and zero flags."""
    monkeypatch.delenv("REPRO_CHECKS", raising=False)
    from repro.launch.serve import main
    rc = main(["--graph-stream", "192", "--ticks", "2",
               "--mutate-frac", "0.02"])
    m = _metrics(capsys)
    assert rc == 0 and m["error"] is None
    assert m["mode"] == "graph-stream" and m["ticks"] == 2
    assert m["mutation_ms_per_tick"] > 0 and m["query_ms_per_tick"] > 0
    assert m["flags"] == [] and m["live"] == 192


def test_serve_cli_graph_stream_epoch_stale_exit3(capsys, monkeypatch):
    """Scripted trace: tick 2 deletes tick 1's (reused) query frontier;
    under REPRO_CHECKS=1 the consumer-side EPOCH_STALE check promotes to
    an EstimationError -> exit 3, recorded in the metrics line."""
    monkeypatch.setenv("REPRO_CHECKS", "1")
    import argparse

    from repro.launch.serve import run_graph_stream
    rng = np.random.default_rng(stats.derive_seed("serving", "cli-stale"))
    args = argparse.Namespace(graph_stream=192, ticks=3, mutate_frac=0.02,
                              level1="blocked", seed=0, reuse_frontier=True)
    trace = [dict(insert=rng.normal(size=(4, 16)).astype(np.float32)),
             dict(delete="frontier"), dict()]
    rc = run_graph_stream(args, trace=trace)
    m = _metrics(capsys)
    assert rc == 3
    assert "EPOCH_STALE" in (m["error"] or "")
    assert m["ticks"] < m["ticks_planned"]


def test_serve_cli_multi_tenant_metrics(capsys, monkeypatch):
    """`serve --serve-tenants`: mixed-op batched ticks end-to-end, p50/p99
    latency and throughput in the metrics line, exit 0."""
    monkeypatch.delenv("REPRO_CHECKS", raising=False)
    from repro.launch.serve import main
    rc = main(["--serve-tenants", "2", "--requests", "16", "--ticks", "2",
               "--max-resident", "2"])
    m = _metrics(capsys)
    assert rc == 0 and m["mode"] == "multi-tenant"
    assert m["served"] == 32 and m["failed"] == 0
    assert m["p50_ms"] > 0 and m["p99_ms"] >= m["p50_ms"]
    assert m["throughput_rps"] > 0


# ------------------------------------------------------------------- #
# 8 simulated devices: the batching layer adds zero extra collectives
# ------------------------------------------------------------------- #
def test_mesh_serving_one_psum_subprocess():
    """A mesh tenant's served draw batch (4 concatenated requests) is ONE
    engine program with exactly one psum and zero ppermute -- the §9
    schedule survives the batching layer -- and its per-request slices are
    bitwise the direct engine call under the documented group key stream
    (first seed -> PRNGKey, co-batched seeds folded in).  A second tick
    exercises every other mesh op -- walk, query, and prob_of (served
    alone: bitwise the direct masked_block_sums + prob_of read)."""
    code = """
import jax, jax.numpy as jnp, numpy as np
from repro.core.kernels_fn import gaussian
from repro.core.serving import KernelGraphServable
from repro.kernels.kde_sampler.sharded import collective_counts
mesh = make_mesh((8,), ("data",))
rng = np.random.default_rng(%d)
x = rng.normal(0, 0.6, (192, 4)).astype(np.float32)
srv = KernelGraphServable()
srv.add_tenant("m", x, gaussian(1.0), block_size=16, mesh=mesh)
reqs = [srv.submit("m", "sample", src=np.arange(16) + 16 * i, seed=900 + i)
        for i in range(4)]
st = srv.tick()
assert st["failed"] == 0 and st["groups"] == 1, st
eng = srv.tenant("m").admit()._engine
cat = jnp.asarray(np.concatenate([np.arange(16) + 16 * i
                                  for i in range(4)]), jnp.int32)
key = jax.random.PRNGKey(reqs[0].seed)
for r in reqs[1:]:
    key = jax.random.fold_in(key, r.seed)
cc = collective_counts(lambda s, k: eng.fused_sample(s, k), cat, key)
assert cc["psum_total"] == 1 and cc["ppermute_total"] == 0, cc
nb, prob, _, _ = eng.fused_sample(cat, key)
nb, prob = np.asarray(nb), np.asarray(prob)
for i, r in enumerate(reqs):
    np.testing.assert_array_equal(r.result[0], nb[16 * i:16 * (i + 1)])
    np.testing.assert_array_equal(r.result[1], prob[16 * i:16 * (i + 1)])
rw = srv.submit("m", "walk", starts=np.arange(8), length=3, seed=950)
rq = srv.submit("m", "query", y=rng.normal(0, 0.6, (6, 4)).astype(np.float32))
src_p, dst_p = np.arange(8), np.arange(8) + 24
rp = srv.submit("m", "prob_of", src=src_p, dst=dst_p, seed=960)
st2 = srv.tick()
assert st2["failed"] == 0, [str(r.error) for r in (rw, rq, rp)]
assert rw.result[0].shape == (8,)
assert np.isfinite(rq.result).all() and rq.result.shape == (6,)
assert rp.error is None and rp.status == 0
bs, _ = eng.masked_block_sums(jnp.asarray(src_p, jnp.int32),
                              jax.random.PRNGKey(rp.seed))
p0, _ = eng.prob_of_from_block_sums(jnp.asarray(src_p, jnp.int32),
                                    jnp.asarray(dst_p, jnp.int32), bs)
np.testing.assert_array_equal(rp.result, np.asarray(p0))
assert np.isfinite(rp.result).all() and (rp.result > 0).all()
print("MESH_SERVE_OK")
""" % stats.derive_seed("serving", "mesh")
    assert "MESH_SERVE_OK" in subproc.run_devices(code, 8)
