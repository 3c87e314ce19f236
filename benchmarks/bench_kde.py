"""Table 1 analog: KDE query cost per estimator x kernel.

derived = "evals_per_query=<n>;rel_err=<e>" -- the paper's cost model is
kernel evaluations (query time ~ d / (eps^2 tau^p)); we report both wall
time and the hardware-independent eval count.

Sections (all written to ``BENCH_kde.json``):

* ``matrix``    -- every estimator backend (exact / rs / stratified /
  host ``GridHBE`` / device ``kde_hash``) on every Table-1 kernel;
* ``mesh``      -- the sharded backends (``ShardedKDE`` exact ring,
  ``HashedKDE(mesh=)`` one-psum hashed table) when >= 2 devices are
  visible (CI runs this under
  ``XLA_FLAGS=--xla_force_host_platform_device_count=8``);
* ``pipelines`` -- the acceptance numbers for ``estimator="hash"``:
  degrees->sparsify and degrees->triangles eval counters vs the
  ``StratifiedKDE`` baseline at n=16384 (full mode), plus the sparsifier
  spectral-error comparison at a size where the dense Laplacian is
  materializable.
"""
from __future__ import annotations

import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, timeit
from repro.core.kde.base import ExactKDE, make_estimator
from repro.core.kernels_fn import (exponential, gaussian, laplacian,
                                   rational_quadratic)
from repro.launch.mesh import make_mesh
from repro.obs.export import telemetry_block

_JSON_PATH = Path(__file__).resolve().parent.parent / "BENCH_kde.json"


def _matrix(quick: bool, rows, results):
    n = 2000 if quick else 4000
    d = 16 if quick else 32
    m = 32
    rng = np.random.default_rng(0)
    x = rng.normal(0, 0.4, (n, d)).astype(np.float32)
    q = rng.normal(0, 0.4, (m, d)).astype(np.float32)
    kernels = [gaussian(2.0), exponential(2.0), laplacian(4.0),
               rational_quadratic(bandwidth=2.0)]
    out = []
    for ker in kernels:
        oracle = ExactKDE(x, ker)
        truth = np.asarray(oracle.query(q))
        for name in ("exact", "rs", "stratified", "grid_hbe", "hash"):
            if name == "grid_hbe" and ker.name != "laplacian":
                continue            # host loop: keep one representative
            est = make_estimator(name, x, ker, seed=0, tau=0.05, eps=0.3)
            est.evals = 0
            reps = 2 if name == "grid_hbe" else 3
            us = timeit(lambda: np.asarray(est.query(q)), repeats=reps)
            evals_per_q = est.evals / max(m * (reps + 1), 1)
            vals = np.asarray(est.query(q))
            rel = float(np.mean(np.abs(vals / truth - 1)))
            rows.append(emit(
                f"kde_query/{ker.name}/{name}", us / m,
                f"evals_per_query={evals_per_q:.0f};rel_err={rel:.4f}"))
            out.append(dict(kernel=ker.name, estimator=name,
                            us_per_query=us / m,
                            evals_per_query=evals_per_q, rel_err=rel))
    results["matrix"] = dict(n=n, d=d, m=m, entries=out)


def _mesh(quick: bool, rows, results):
    ndev = len(jax.devices())
    if ndev < 2:
        results["mesh"] = dict(skipped=True, devices=ndev)
        rows.append(emit("kde_query/mesh", 0.0,
                         f"skipped=1_device (run under XLA_FLAGS="
                         f"--xla_force_host_platform_device_count=8)"))
        return
    from repro.core.kde.distributed import ShardedKDE
    from repro.core.kde.hashed import HashedKDE
    n = 2048 if quick else 8192
    d = 16
    m = 64
    rng = np.random.default_rng(0)
    x = rng.normal(0, 0.4, (n, d)).astype(np.float32)
    q = rng.normal(0, 0.4, (m, d)).astype(np.float32)
    ker = gaussian(2.0)
    truth = np.asarray(ExactKDE(x, ker).query(q))
    mesh = make_mesh((ndev,), ("data",))
    out = []
    for name, est in (("sharded_exact", ShardedKDE(mesh, x, ker,
                                                   exact=True)),
                      ("sharded_hash", HashedKDE(x, ker, mesh=mesh,
                                                 num_far_samples=128))):
        est.evals = 0
        us = timeit(lambda: np.asarray(est.query(q)), repeats=3)
        evals_per_q = est.evals / (m * 4)
        rel = float(np.mean(np.abs(np.asarray(est.query(q)) / truth - 1)))
        rows.append(emit(
            f"kde_query/mesh{ndev}/{name}", us / m,
            f"evals_per_query={evals_per_q:.0f};rel_err={rel:.4f}"))
        out.append(dict(estimator=name, us_per_query=us / m,
                        evals_per_query=evals_per_q, rel_err=rel))
    results["mesh"] = dict(n=n, d=d, m=m, devices=ndev, entries=out)


def _spectral_error(g, l_true, probes: int = 24, seed: int = 1) -> float:
    rng = np.random.default_rng(seed)
    v = rng.standard_normal((l_true.shape[0], probes))
    v -= v.mean(0)
    ratios = np.einsum("ij,ij->j", v, g.laplacian_dense() @ v) / \
        np.einsum("ij,ij->j", v, l_true @ v)
    return float(np.abs(ratios - 1.0).max())


def _pipelines(quick: bool, rows, results):
    from repro.core.graph.triangles import estimate_triangle_weight
    from repro.core.sparsify import spectral_sparsify
    # -------- eval counters at scale (the acceptance numbers) -------- #
    n = 2048 if quick else 16384
    d = 16
    rng = np.random.default_rng(0)
    x = rng.normal(0, 0.5, (n, d)).astype(np.float32)
    ker = gaussian(bandwidth=4.0)
    t = 4 * n
    counters = {}
    for name in ("stratified", "hash"):
        t0 = time.perf_counter()
        g = spectral_sparsify(x, ker, num_edges=t, estimator=name, seed=0)
        sp_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        tri = estimate_triangle_weight(x, ker, 2048, 16, estimator=name,
                                       seed=0)
        tri_s = time.perf_counter() - t0
        counters[name] = dict(
            sparsify_evals=int(g.kernel_evals),
            sparsify_queries=int(g.kde_queries), sparsify_sec=sp_s,
            triangles_evals=int(tri.kernel_evals), triangles_sec=tri_s)
    sp_ratio = counters["hash"]["sparsify_evals"] \
        / counters["stratified"]["sparsify_evals"]
    tri_ratio = counters["hash"]["triangles_evals"] \
        / counters["stratified"]["triangles_evals"]
    rows.append(emit(
        f"kde_pipelines/evals/n={n}", 0.0,
        f"sparsify_hash_over_stratified={sp_ratio:.3f};"
        f"triangles_hash_over_stratified={tri_ratio:.3f}"))
    # -------- spectral error where L is materializable --------------- #
    n_sp = 1024 if quick else 2048
    x_sp = rng.normal(0, 0.35, (n_sp, 8)).astype(np.float32)
    ker_sp = gaussian(bandwidth=3.0)
    k_sp = np.asarray(ker_sp.matrix(jnp.asarray(x_sp)), np.float64)
    np.fill_diagonal(k_sp, 0.0)
    l_true = np.diag(k_sp.sum(1)) - k_sp
    errs = {}
    for name in ("stratified", "hash"):
        g = spectral_sparsify(x_sp, ker_sp, num_edges=16 * n_sp,
                              estimator=name, seed=0)
        errs[name] = _spectral_error(g, l_true)
    rows.append(emit(
        f"kde_pipelines/spectral_error/n={n_sp}", 0.0,
        f"stratified={errs['stratified']:.4f};hash={errs['hash']:.4f};"
        f"ratio={errs['hash'] / errs['stratified']:.2f}"))
    results["pipelines"] = dict(
        n=n, t=t, counters=counters,
        evals_ratio=dict(sparsify=sp_ratio, triangles=tri_ratio),
        spectral_error=dict(n=n_sp, t=16 * n_sp, **errs,
                            ratio=errs["hash"] / errs["stratified"]))


def _precision_scaling(quick: bool, rows, results):
    """f32 vs bf16 level-1 sweep throughput, n-sweep up to ~10^6.

    The bf16 policy (DESIGN.md §14) halves the dataset bytes the level-1
    sweep streams while keeping f32 accumulation, so the speedup target is
    >= 1.5x at n >= 262144 with rel-err within ``2 * BF16_REL_ERR``.  Each
    entry carries a measured-roofline fraction from the modeled sweep
    traffic (n * d operand bytes per query batch) against the backend
    peaks.
    """
    from repro.kernels.kde_sampler.ref import BF16_REL_ERR
    from repro.roofline import analysis as _roofline
    sizes = [65536, 262144, 1048576] if quick else [
        65536, 262144, 524288, 1048576]
    d, m = 16, 64
    spec = _roofline.device_chip_spec()
    entries = []
    for n in sizes:
        rng = np.random.default_rng(0)
        x = rng.normal(0, 0.5, (n, d)).astype(np.float32)
        q = rng.normal(0, 0.5, (m, d)).astype(np.float32)
        ker = gaussian(bandwidth=4.0)
        per = {}
        for prec in ("f32", "bf16"):
            est = ExactKDE(x, ker, precision=prec)
            reps = 3 if n >= 1048576 else 5
            us = timeit(lambda: np.asarray(est.query(q)), repeats=reps)
            t = us * 1e-6
            in_bytes = _roofline.dtype_bytes(
                "bfloat16" if prec == "bf16" else "float32")
            # Sweep traffic: the dataset tile stream dominates (queries and
            # the f32 accumulator are tile-resident).
            bytes_moved = float(n) * d * in_bytes + m * d * 4 + m * 4
            flops = 2.0 * n * m * d
            rl = _roofline.roofline_summary(spec, t, flops, bytes_moved)
            per[prec] = dict(us_per_batch=us,
                             evals_per_sec=n * m / t,
                             vals=np.asarray(est.query(q), np.float64),
                             roofline=rl)
        rel = float(np.max(np.abs(per["bf16"]["vals"] / per["f32"]["vals"]
                                  - 1.0)))
        speedup = per["f32"]["us_per_batch"] / per["bf16"]["us_per_batch"]
        rows.append(emit(
            f"kde_precision/n={n}", per["bf16"]["us_per_batch"] / m,
            f"bf16_speedup={speedup:.2f}x;rel_err={rel:.2e};"
            f"bound={2 * BF16_REL_ERR:.2e};"
            f"roofline_frac={per['bf16']['roofline']['fraction']}"))
        entries.append(dict(
            n=n, d=d, m=m, bf16_speedup=speedup, bf16_rel_err=rel,
            rel_err_bound=2 * BF16_REL_ERR,
            f32={k: v for k, v in per["f32"].items() if k != "vals"},
            bf16={k: v for k, v in per["bf16"].items() if k != "vals"}))
    results["precision"] = dict(
        kernel="gaussian",
        spec=spec.as_dict() if spec else _roofline.NOT_MEASURED,
        entries=entries)


def run(quick: bool = False):
    rows, results = [], {}
    _matrix(quick, rows, results)
    _mesh(quick, rows, results)
    _pipelines(quick, rows, results)
    _precision_scaling(quick, rows, results)
    _JSON_PATH.write_text(json.dumps(dict(
        benchmark="bench_kde", backend=jax.default_backend(), quick=quick,
        telemetry=telemetry_block(),
        results=results), indent=2) + "\n")
    return rows
