"""End-to-end behaviour tests: the paper's pipelines composed, data layer,
and the serving driver."""
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

import subproc
from repro.configs.base import SHAPES, ShapeConfig, get_reduced
from repro.core.cluster.spectral import cluster_accuracy, spectral_cluster
from repro.core.kernels_fn import gaussian, laplacian, median_bandwidth
from repro.core.laplacian import cg_laplacian, laplacian_dense
from repro.core.lowrank import fkv_lowrank, projection_error
from repro.core.sparsify import spectral_sparsify
from repro.data.pipeline import make_batch, token_split
from repro.data.synthetic_points import glove_like, mnist_like, nested, rings


def test_paper_pipeline_end_to_end():
    """Nested dataset -> sparsify (few-percent edge budget) -> spectral
    cluster -> solve a Laplacian system on the sparsifier.  The Section 7
    pipeline in miniature."""
    x, lab = nested(n=800, seed=0)
    ker = gaussian(bandwidth=0.3)
    n = x.shape[0]
    budget = int(0.06 * n * (n - 1) / 2)     # a few percent of all edges
    g = spectral_sparsify(x, ker, num_edges=budget, estimator="exact",
                          exact_blocks=True, seed=0)
    assert g.num_edges == budget
    res = spectral_cluster(g, 2, seed=0)
    acc = cluster_accuracy(res.labels, lab, 2)
    assert acc > 0.97, acc
    rng = np.random.default_rng(0)
    b = rng.standard_normal(n)
    b -= b.mean()
    sol, _ = cg_laplacian(g, b, iters=300)
    assert np.isfinite(sol).all()
    # edge-budget savings direction of the 41x claim: edges << n^2/2
    assert g.num_edges < 0.1 * n * n / 2


def test_rings_dataset_clusterable():
    x, lab = rings(n=600, seed=0)
    ker = gaussian(bandwidth=median_bandwidth(jnp.asarray(x)) * 0.25)
    g = spectral_sparsify(x, ker, num_edges=30000, estimator="exact",
                          exact_blocks=True, seed=0)
    res = spectral_cluster(g, 2, seed=1)
    assert cluster_accuracy(res.labels, lab, 2) > 0.9


def test_lra_on_paper_style_datasets():
    """MNIST-like / GloVe-like LRA with the paper's 25*rank rows setting."""
    for maker in (mnist_like, glove_like):
        x = maker(n=700)
        ker = laplacian(bandwidth=median_bandwidth(jnp.asarray(x), ord=1))
        k = np.asarray(ker.matrix(jnp.asarray(x)), np.float64)
        res = fkv_lowrank(x, ker, rank=8, num_rows=200, estimator="rs",
                          seed=0)
        err = projection_error(k, res.u)
        fro2 = np.linalg.norm(k, "fro") ** 2
        assert err / fro2 < 0.35, err / fro2
        assert res.kernel_evals < 0.7 * k.size


def test_data_pipeline_determinism():
    cfg = get_reduced("yi_6b")
    shape = ShapeConfig("t", 64, 4, "train")
    b1 = make_batch(cfg, shape, step=3, seed=9)
    b2 = make_batch(cfg, shape, step=3, seed=9)
    b3 = make_batch(cfg, shape, step=4, seed=9)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    assert not np.array_equal(b1["tokens"], b3["tokens"])
    assert b1["tokens"].min() >= 0
    assert b1["tokens"].max() < cfg.vocab_size


def test_token_split_covers_shapes():
    for arch in ("internvl2_1b", "seamless_m4t_medium", "yi_6b"):
        cfg = get_reduced(arch)
        for shape in SHAPES.values():
            sp = token_split(cfg, shape)
            assert sp["tokens"] + sp["frontend"] == shape.seq_len


def test_serve_driver_runs():
    env = subproc.child_env()
    p = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--arch", "yi_6b",
         "--reduced", "--batch", "2", "--prompt-len", "16", "--gen", "4"],
        capture_output=True, text=True, cwd=".", env=env)
    assert p.returncode == 0, p.stderr[-800:]
    assert "tok/s" in p.stdout


def test_serve_driver_kde_attention():
    env = subproc.child_env()
    p = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--arch", "yi_6b",
         "--reduced", "--batch", "2", "--prompt-len", "32", "--gen", "4",
         "--attention", "kde"],
        capture_output=True, text=True, cwd=".", env=env)
    assert p.returncode == 0, p.stderr[-800:]
