#!/usr/bin/env python3
"""Smoke test of the kernel-graph system on a TPU, through its entry points.

    python3 chip_smoke.py              # one chip: phases A and B
    python3 chip_smoke.py --chips 4    # four chips: phase C only

Phase A -- the served path.  ``launch.serve``'s multi-tenant loop
(``build_servable`` / ``serve_mix``) over two tenants of 10^6 x 128 points
at the SIFT-1M shape (ann-benchmarks ``sift-128-euclidean``; generated
from ``--seed``), one with the blocked exact level-1 (Pallas block sweeps)
and one with the hashed level-1 (Pallas bucket kernel).  Gaussian kernel,
median-distance bandwidth.  A warm-up tick compiles every program, then
``--ticks`` ticks of 32 mixed query/sample/walk/prob_of requests.
Checked against a plain chunked jnp reference: query answers vs exact row
sums, draws live and in range, no failed request, no status flag outside
the benign accuracy signals.

Phase B -- a Table-1 pipeline past the dense wall.  ``spectral_sparsify``
with 10 n edges on the ``nested`` point set at n = 65,536 (the dense f32
kernel matrix would take 17.2 GB, more than the chip's 16 GB).  Checked by
quadratic forms z^T L_H z against z^T L_G z for 8 random z, with L_G z from
a blockwise jnp matvec.

Phase C (``--chips 4``) -- the mesh engine.  The sharded
``NeighborSampler(mesh=...)`` draws and walks and ``ShardedHashTable``
queries at 4,194,304 x 128 points, against the single-chip oracles and the
flat engine: integers bitwise, block sums to f32 tolerance, walk endpoints
by total variation, one psum per draw batch, shards on every chip.

Each phase prints one JSON line; the last line is
``{"ok": true, "device": {...}}`` only when every phase passed.  Without a
TPU (or without the repository's ``src`` next to this file) the script
exits non-zero before running anything.
"""
from __future__ import annotations

import argparse
import functools
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

# phase sizes (module constants: the phases take them as defaults, so a
# rehearsal can call a phase at a small size)
SERVE_POINTS = 1_000_000    # per tenant, SIFT-1M
SERVE_TICKS = 3             # measured ticks after the warm-up tick
SPARSIFY_POINTS = 65_536    # dense f32 K = 17.2 GB > 16 GB
MESH_POINTS = 4_194_304     # 2 GB f32 at d = 128, 512 MB per chip

# stated bounds (see the phase docstrings for what each compares)
EXACT_QUERY_REL = 1e-4      # exact blocked tenant: f32 summation order only
HASH_QUERY_REL = 0.15       # hashed tenant: NEAR + 256 HT FAR samples
SPARSIFY_QF_REL = 0.05      # sparsifier quadratic forms, t = 10 n
BLOCK_SUM_RTOL = 1e-4       # sharded vs flat level-1 sums (f32 order)
WALK_TV = 0.15              # sharded vs flat walk endpoints, 16 bins
                            # (2048 walkers: sampling noise ~0.05)


def _device_line(jax, name: str, t0: float, **fields) -> dict:
    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    return dict(phase=name, platform=dev.platform, device_kind=dev.device_kind,
                devices=len(jax.devices()),
                peak_bytes_in_use=stats.get("peak_bytes_in_use"),
                phase_s=time.perf_counter() - t0, **fields)


def sift_like(jax, key, n: int, d: int = 128, clusters: int = 1024):
    """SIFT-shaped descriptors on the device: non-negative integer-valued
    coordinates in [0, 255] around ``clusters`` random centers."""
    jnp = jax.numpy
    kc, kl, kn = jax.random.split(key, 3)
    centers = 48.0 * jnp.abs(jax.random.normal(kc, (clusters, d)))
    lab = jax.random.randint(kl, (n,), 0, clusters)
    x = centers[lab] + 12.0 * jax.random.normal(kn, (n, d))
    return jnp.round(jnp.clip(x, 0.0, 255.0))


def exact_rowsums(jax, y, x, bandwidth: float, chunk: int = 2048):
    """Reference Gaussian row sums sum_j exp(-|y_i - x_j|^2 / bw^2): a
    chunked scan over x with direct coordinate differences (no Pallas, no
    code under test)."""
    jnp = jax.numpy
    pad = -x.shape[0] % chunk
    xp = jnp.pad(x, ((0, pad), (0, 0)), constant_values=1e30)
    xs = xp.reshape(-1, chunk, x.shape[1])

    @jax.jit
    def run(y, xs):
        def body(acc, xc):
            d2 = jnp.sum(jnp.square(y[:, None, :] - xc[None]), axis=-1)
            return acc + jnp.sum(jnp.exp(-d2 / bandwidth ** 2), axis=1), None
        return jax.lax.scan(body, jnp.zeros(y.shape[0]), xs)[0]

    return run(jnp.asarray(y), xs)


def kernel_matvec(jax, x, z, bandwidth: float, chunk: int = 1024):
    """Reference (K - I) [1, z] for the Gaussian kernel graph, row chunk by
    row chunk (never the dense n x n matrix)."""
    jnp = jax.numpy
    rhs = jnp.concatenate([jnp.ones((x.shape[0], 1)), z], axis=1)
    rows = x.reshape(-1, chunk, x.shape[1])

    @jax.jit
    def run(rows, x, rhs):
        def body(_, xr):
            d2 = jnp.sum(jnp.square(xr[:, None, :] - x[None]), axis=-1)
            k = jnp.exp(-d2 / bandwidth ** 2)
            return None, jnp.matmul(k, rhs,
                                    precision=jax.lax.Precision.HIGHEST)
        return jax.lax.scan(body, None, rows)[1].reshape(x.shape[0], -1)

    return run(rows, x, rhs) - rhs          # k(x, x) = 1 on the diagonal


def phase_a(jax, seed: int, n: int = SERVE_POINTS,
            ticks: int = SERVE_TICKS) -> dict:
    """Served path: two 10^6 x 128 tenants, mixed requests."""
    import numpy as np
    from repro.core.kernels_fn import gaussian, median_bandwidth
    from repro.ft import guards
    from repro.launch.serve import build_servable, serve_mix

    t0 = time.perf_counter()
    d = 128
    keys = jax.random.split(jax.random.PRNGKey(seed), 2)
    points = [sift_like(jax, k, n, d) for k in keys]
    bw = median_bandwidth(points[0])
    ker = gaussian(bw)
    opts = [dict(level1="blocked", exact_blocks=True),
            dict(level1="hash",
                 hash_opts=dict(num_far_samples=256, overflow_cap=256))]
    srv, names = build_servable(points, ker, opts, max_resident=2,
                                seed=seed)
    run = serve_mix(srv, names, requests=32, ticks=ticks, seed=seed)
    reqs = run["reqs"] + run["warm_reqs"]
    failed = [repr(r.error)[:200] for r in reqs if r.error is not None]
    bad_flags = sorted({f for r in reqs for f in guards.decode_status(
        r.status & ~(guards.BUCKET_OVERFLOW | guards.HT_HEAVY
                     | guards.REJECT_EXHAUSTED))})
    flags = sorted({f for r in reqs for f in guards.decode_status(r.status)})
    draws_ok = True
    rel = {}
    for i, name in enumerate(names):
        ds = srv.dataset(name)
        mine = [r for r in reqs if r.tenant == name and r.error is None]
        for r in mine:
            out = (r.result[0] if r.op in ("sample", "walk")
                   else r.result if r.op == "prob_of" else None)
            if out is None:
                continue
            out = np.asarray(out)
            if r.op == "prob_of":
                draws_ok &= bool(np.all(np.isfinite(out) & (out >= 0)
                                        & (out <= 1)))
            else:
                draws_ok &= bool(np.all((out >= 0) & (out < n))
                                 and ds.is_live(out))
        q = [r for r in mine if r.op == "query"]
        y = np.concatenate([np.asarray(r.payload["y"]) for r in q])
        got = np.concatenate([np.asarray(r.result) for r in q])
        want = np.asarray(exact_rowsums(jax, y, points[i], bw))
        rel[name] = float(np.mean(np.abs(got / want - 1.0)))
    bounds = {names[0]: EXACT_QUERY_REL, names[1]: HASH_QUERY_REL}
    ok = (not failed and not bad_flags and draws_ok
          and all(rel[k] < bounds[k] for k in names))
    return _device_line(
        jax, "A-served", t0, ok=ok, n=n, d=d, bandwidth=bw,
        tenants={nm: o["level1"] for nm, o in zip(names, opts)},
        compile_s=run["warm_s"], wall_s=run["wall_s"], ticks=ticks,
        requests_served=run["served"], requests_failed=run["failed"],
        warmup_failed=sum(r.error is not None for r in run["warm_reqs"]),
        kernel_evals=run["realized_evals"],
        query_mean_rel_err=rel, query_rel_bound=bounds, draws_ok=draws_ok,
        flags=flags, errors=failed[:3])


def phase_b(jax, seed: int, n: int = SPARSIFY_POINTS) -> dict:
    """spectral_sparsify past the dense wall (n = 65,536, t = 10 n)."""
    import numpy as np
    from repro.core.kernels_fn import gaussian, median_bandwidth
    from repro.core.sparsify import spectral_sparsify
    from repro.data.synthetic_points import nested

    t0 = time.perf_counter()
    x, _ = nested(n=n, seed=seed)
    xd = jax.numpy.asarray(x)
    bw = median_bandwidth(xd)
    ker = gaussian(bw)
    run = functools.partial(spectral_sparsify, x, ker, num_edges=10 * n,
                            estimator="exact", exact_blocks=True,
                            seed=seed)
    t1 = time.perf_counter()
    run()                                     # compiles every program
    first_s = time.perf_counter() - t1
    t1 = time.perf_counter()
    g = run()                                 # host arrays: fenced
    wall_s = time.perf_counter() - t1
    z = np.random.default_rng(seed).normal(size=(n, 8))
    kz = np.asarray(kernel_matvec(jax, xd, jax.numpy.asarray(z, np.float32),
                                  bw), np.float64)
    deg, az = kz[:, 0], kz[:, 1:]
    qf_g = np.einsum("ij,ij->j", z, deg[:, None] * z - az)
    qf_h = np.array([z[:, j] @ g.matvec(z[:, j]) for j in range(8)])
    err = float(np.max(np.abs(qf_h / qf_g - 1.0)))
    return _device_line(
        jax, "B-sparsify", t0, ok=err < SPARSIFY_QF_REL, n=n, d=2,
        bandwidth=bw, edges=g.num_edges, compile_s=first_s - wall_s,
        first_call_s=first_s, wall_s=wall_s, kernel_evals=g.device_evals,
        quadratic_form_max_rel_err=err, bound=SPARSIFY_QF_REL)


def phase_c(jax, seed: int, n: int = MESH_POINTS) -> dict:
    """Mesh engine on four chips vs the flat engine and oracles."""
    import numpy as np
    from repro.core.kernels_fn import gaussian, median_bandwidth
    from repro.core.sampling.edge import NeighborSampler
    from repro.kernels.kde_hash import ref as href
    from repro.kernels.kde_hash.sharded import ShardedHashTable
    from repro.kernels.kde_sampler import ops as sops
    from repro.kernels.kde_sampler import ref as sref
    from repro.launch.mesh import make_mesh

    t0 = time.perf_counter()
    d = 128
    devs = jax.devices()[:4]
    mesh = make_mesh((4,), ("data",), devices=devs)
    x = sift_like(jax, jax.random.PRNGKey(seed), n, d)
    bw = median_bandwidth(x)
    ker = gaussian(bw)
    rng = np.random.default_rng(seed)

    # draws + level-1 sums: sharded engine vs its single-chip oracle and
    # vs the flat engine's exact level-1 read
    sh = NeighborSampler(x, ker, exact_blocks=True, mesh=mesh,
                         seed=seed)
    flat = NeighborSampler(x, ker, exact_blocks=True, seed=seed)
    eng = sh.blocks.engine
    src = jax.numpy.asarray(rng.integers(0, n, 64), jax.numpy.int32)
    key = jax.random.PRNGKey(seed + 1)
    nb, prob, sums, cw = eng.fused_sample(src, key)
    rnb, rprob, rsums = sref.sharded_fused_sample_ref(
        eng.x_rep, eng.x_sq_rep, src, key, ker.name, 1.0 / bw, 1.0,
        eng.block_size, eng.blocks_per_shard, eng.num_shards, n, exact=True)
    draws_bitwise = bool(np.array_equal(np.asarray(nb), np.asarray(rnb)))
    fsums, _ = sops.masked_block_sums(flat.x, flat.x_sq, src, key,
                                      **flat._cfg)
    nbk = flat.num_blocks
    sums_rel = float(np.max(np.abs(np.asarray(sums)[:, :nbk]
                                   / np.asarray(fsums) - 1.0)))
    draw_psums = int(np.asarray(cw)[7])

    # walks: same starts on both engines, endpoint law by TV on 16 bins
    steps, w, batches = 4, 128, 16
    starts = rng.integers(0, n, w)
    ends = {"mesh": [], "flat": []}
    for b in range(batches):
        k = jax.random.PRNGKey(1000 + b)
        ends["mesh"].append(sh.walk(starts, steps, key=k)[0])
        ends["flat"].append(flat.walk(starts, steps,
                                      key=jax.random.fold_in(k, 1))[0])
    hist = {k: np.bincount(np.concatenate(v) * 16 // n, minlength=16)
            / (w * batches) for k, v in ends.items()}
    tv = float(0.5 * np.abs(hist["mesh"] - hist["flat"]).sum())
    walk_psums = sh.device_counters["psums"]

    # hashed queries: sharded table vs its single-chip oracle
    tab = ShardedHashTable(mesh, np.asarray(x), ker, seed=seed)
    y = x[jax.numpy.asarray(rng.integers(0, n, 256))]
    hkey = jax.random.PRNGKey(seed + 2)
    est, cnt, hcw = tab.query(y, hkey)
    ref_est, ref_cnt = href.sharded_hashed_query_ref(
        tab.x_pad, y, tab.shard_states, hkey, ker.name, 1.0 / bw, 1.0,
        tab.spec.cell_width, tab.num_far, n, tab.shard_size)
    counts_bitwise = bool(np.array_equal(np.asarray(cnt),
                                         np.asarray(ref_cnt)))
    est_rel = float(np.max(np.abs(np.asarray(est) / np.asarray(ref_est)
                                  - 1.0)))
    # the sharded dataset must hold one quarter on each chip, not all of
    # it on device 0
    shard_bytes = {str(dv.id): 0 for dv in devs}
    for shard in eng.x_sh.addressable_shards:
        shard_bytes[str(shard.device.id)] += int(shard.data.nbytes)
    in_use = [(dv.memory_stats() or {}).get("bytes_in_use") for dv in devs]
    ok = (draws_bitwise and sums_rel < BLOCK_SUM_RTOL and draw_psums == 1
          and walk_psums == batches * steps and tv < WALK_TV
          and counts_bitwise and est_rel < BLOCK_SUM_RTOL
          and int(np.asarray(hcw)[7]) == 1
          and min(shard_bytes.values()) >= n * d)
    return _device_line(
        jax, "C-mesh", t0, ok=ok, n=n, d=d, bandwidth=bw,
        draws_bitwise=draws_bitwise, block_sum_max_rel=sums_rel,
        psums_per_draw_batch=draw_psums,
        walk_psums=walk_psums, walk_steps=batches * steps,
        walk_endpoint_tv=tv, walk_tv_bound=WALK_TV,
        hash_counts_bitwise=counts_bitwise, hash_est_max_rel=est_rel,
        hash_query_psums=int(np.asarray(hcw)[7]),
        dataset_shard_bytes=shard_bytes, bytes_in_use_per_device=in_use)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of every generated point set and draw")
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro.launch.cache import use_compile_cache
    except ImportError:
        print("chip_smoke: the repository's src/ is not next to this file",
              file=sys.stderr)
        return 2
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < args.chips:
        print(f"chip_smoke: needs {args.chips} TPU chip(s); JAX found "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        return 2
    use_compile_cache()
    phases = [phase_c] if args.chips == 4 else [phase_a, phase_b]
    ok = True
    for phase in phases:
        line = phase(jax, args.seed)
        print(json.dumps(line), flush=True)
        ok &= bool(line["ok"])
    if not ok:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
