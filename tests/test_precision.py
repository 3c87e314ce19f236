"""Mixed-precision policy tests (DESIGN.md §14).

Three contracts:

* **bf16 bitwise parity** -- the interpret-mode Pallas kernels under
  ``precision="bf16"`` are bitwise-equal to a jnp reference that mirrors
  the exact (bm, bn) tile decomposition and calls the shared
  ``_tile_kernel_values``; the bf16 path is a pure function of the
  bf16-rounded operands, so there is no tolerance to negotiate.
* **bf16 accuracy** -- every estimator that accepts ``precision="bf16"``
  stays within ``2 * BF16_REL_ERR`` of its f32 twin when both run the same
  seed (identical sample draws, so the only difference is kernel-eval
  precision).  The bound is the input-rounding error model documented next
  to ``BF16_REL_ERR``.
* **f32 bitwise stability** -- threading ``precision`` through the stack
  must not perturb the default path: ``precision="f32"`` output is
  bitwise-identical to the precision-less call.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.kde.base import ExactKDE, make_estimator
from repro.core.kernels_fn import gaussian, laplacian, rational_quadratic
from repro.kernels import tuning
from repro.kernels.kde_rowsum import kernel as rk
from repro.kernels.kde_rowsum import ops as rs
from repro.kernels.kde_sampler import ops as sops
from repro.launch.mesh import make_mesh
from repro.kernels.kde_sampler import ref as sref

RNG = np.random.default_rng(7)
BOUND = 2.0 * sref.BF16_REL_ERR


def _tiled_rowsum_ref(q, x, kind, inv_bw, beta, bm, bn, precision):
    """Mirror of ``ops._rowsum``: same padding, same (bm, bn) tile loop in
    the same accumulation order, calling the kernel's own tile body.  Run
    under jit like the real entry point -- eager transcendentals can
    differ from the compiled ones by an ulp."""
    def mirror(q, x):
        m = q.shape[0]
        qp = rs._pad_rows(q, bm, 0.0)
        xp = rs._pad_rows(x, bn, rs._PAD_OFFSET)
        rows = []
        for i in range(qp.shape[0] // bm):
            acc = jnp.zeros((bm,), jnp.float32)
            for j in range(xp.shape[0] // bn):
                kv = rk._tile_kernel_values(qp[i * bm:(i + 1) * bm],
                                            xp[j * bn:(j + 1) * bn],
                                            kind, inv_bw, beta,
                                            precision=precision)
                acc = acc + jnp.sum(kv, axis=1)
            rows.append(acc)
        return jnp.concatenate(rows)[:m]

    return jax.jit(mirror)(jnp.asarray(q), jnp.asarray(x))


@pytest.mark.parametrize("ker", [gaussian(1.3),
                                 rational_quadratic(bandwidth=2.0)])
@pytest.mark.parametrize("m,n,d", [(37, 300, 19), (64, 512, 16)])
def test_bf16_rowsum_bitwise_parity(ker, m, n, d):
    q = RNG.normal(0, 0.5, (m, d)).astype(np.float32)
    x = RNG.normal(0, 0.5, (n, d)).astype(np.float32)
    bm, bn = 32, 128
    out = rs.kde_rowsum(q, x, ker, bm=bm, bn=bn, interpret=True,
                        precision="bf16")
    ref = _tiled_rowsum_ref(q, x, ker.name, 1.0 / ker.bandwidth,
                            getattr(ker, "beta", 1.0), bm, bn, "bf16")
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_bf16_blocksum_bitwise_parity():
    ker = gaussian(2.0)
    q = RNG.normal(0, 0.5, (17, 8)).astype(np.float32)
    x = RNG.normal(0, 0.5, (256, 8)).astype(np.float32)
    out = rs.kde_blocksum(q, x, ker, bm=16, bn=64, interpret=True,
                          precision="bf16")
    # blocksum has no cross-tile carry: each (bm, 1) cell is one tile call
    ref = rs.blocksum_ref(jnp.asarray(q), jnp.asarray(x), "gaussian",
                          1.0 / ker.bandwidth, bn=64, precision="bf16")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-6)


def test_f32_rowsum_bitwise_parity_with_tile_mirror():
    ker = gaussian(1.3)
    q = RNG.normal(0, 0.5, (37, 19)).astype(np.float32)
    x = RNG.normal(0, 0.5, (300, 19)).astype(np.float32)
    out = rs.kde_rowsum(q, x, ker, bm=32, bn=128, interpret=True,
                        precision="f32")
    ref = _tiled_rowsum_ref(q, x, "gaussian", 1.0 / ker.bandwidth, 1.0,
                            32, 128, "f32")
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_bf16_rowsum_accuracy_vs_f32():
    ker = gaussian(4.0)
    q = RNG.normal(0, 0.5, (32, 16)).astype(np.float32)
    x = RNG.normal(0, 0.5, (4096, 16)).astype(np.float32)
    f32 = np.asarray(rs.kde_rowsum(q, x, ker, bm=32, bn=256, interpret=True),
                     np.float64)
    b16 = np.asarray(rs.kde_rowsum(q, x, ker, bm=32, bn=256, interpret=True,
                                   precision="bf16"), np.float64)
    assert np.max(np.abs(b16 / f32 - 1.0)) < BOUND


@pytest.mark.parametrize("name", ["exact", "rs", "stratified", "hash"])
def test_estimator_bf16_within_documented_tolerance(name):
    """Same seed => identical sample draws, so f32 vs bf16 isolates the
    kernel-eval precision; the per-query ratio must stay inside the
    documented input-rounding bound."""
    n, d, m = 4096, 16, 32
    rng = np.random.default_rng(0)
    x = rng.normal(0, 0.5, (n, d)).astype(np.float32)
    q = rng.normal(0, 0.5, (m, d)).astype(np.float32)
    ker = gaussian(4.0)
    f32 = make_estimator(name, x, ker, seed=3, tau=0.05, eps=0.3)
    b16 = make_estimator(name, x, ker, seed=3, tau=0.05, eps=0.3,
                         precision="bf16")
    v32 = np.asarray(f32.query(jnp.asarray(q)), np.float64)
    v16 = np.asarray(b16.query(jnp.asarray(q)), np.float64)
    assert np.max(np.abs(v16 / v32 - 1.0)) < BOUND, name


def test_f32_estimator_bitwise_unchanged_by_precision_kwarg():
    n, d = 1024, 8
    rng = np.random.default_rng(1)
    x = rng.normal(0, 0.5, (n, d)).astype(np.float32)
    q = rng.normal(0, 0.5, (16, d)).astype(np.float32)
    ker = gaussian(2.0)
    a = ExactKDE(x, ker).query(jnp.asarray(q))
    b = ExactKDE(x, ker, precision="f32").query(jnp.asarray(q))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_bf16_rejected_for_non_l2_kernels_and_mesh():
    n, d = 256, 4
    x = RNG.normal(0, 0.5, (n, d)).astype(np.float32)
    with pytest.raises(ValueError):
        ExactKDE(x, laplacian(2.0), precision="bf16")
    ndev = len(jax.devices())
    if ndev >= 2:
        from repro.core.sampling.edge import NeighborSampler
        mesh = make_mesh((ndev,), ("data",))
        with pytest.raises(ValueError):
            NeighborSampler(x, gaussian(2.0), mode="blocked", mesh=mesh,
                            precision="bf16")


# ------------------------------------------------------------------ layout
def test_walk_layout_small_problems_unchanged():
    """Counter-parity contract: when the sampler's own cache already fits
    the column budget the walk layout is the sampler layout, so mesh and
    single-device walks keep identical per-step eval counts."""
    assert sops.walk_layout(4096, 64, 64, 16) == (64, 64, 16)


def test_walk_layout_large_problems_capped():
    wbs, wb, s = sops.walk_layout(65536, 256, 256, 16)
    assert (wbs, wb, s) == (128, 512, 2)
    assert wb * s <= tuning.WALK_CACHE_COLS
    assert wbs * wb >= 65536
    wbs, wb, s = sops.walk_layout(1048576, 1024, 1024, 16)
    assert wbs == 512 and wbs * wb >= 1048576
    # the column cap binds: s bottoms out at the variance-reduction floor
    assert s == tuning.WALK_CACHE_MIN_S


def test_grouped_inverse_cdf_matches_flat_on_exact_sums():
    """With integer-valued weights every partial sum is exact in f32, so
    the two-level grouped draw must pick the identical index as the flat
    inverse-CDF for any u (the law differs only by fp regrouping)."""
    rng = np.random.default_rng(2)
    w, m = 64, 48
    vals = jnp.asarray(rng.integers(0, 64, (w, m)).astype(np.float32))
    u = jnp.asarray(rng.uniform(size=(w,)).astype(np.float32))
    g = sref.cdf_group(m)
    assert m % g == 0
    idx, val, tot = sref.grouped_inverse_cdf(vals, u, g)
    c = jnp.cumsum(vals, axis=1)
    flat = jnp.sum((u * c[:, -1])[:, None] > c, axis=1).clip(0, m - 1)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(flat))
    np.testing.assert_array_equal(
        np.asarray(val),
        np.asarray(jnp.take_along_axis(vals, idx[:, None], axis=1)[:, 0]))
    np.testing.assert_array_equal(np.asarray(tot), np.asarray(c[:, -1]))


def test_pallas_tile_tuner_static_and_wider_for_bf16():
    t1 = tuning.pallas_tiles(1024, 262144, 64, "f32")
    t2 = tuning.pallas_tiles(1024, 262144, 64, "bf16")
    assert t1 == tuning.pallas_tiles(1024, 262144, 64, "f32")  # memoized
    assert t2[1] >= t1[1]  # halved operand bytes never narrow the x tile
