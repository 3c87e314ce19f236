"""Fused sampling engine vs the seed host-loop sampler (DESIGN.md §3).

Measures random-walk stepping throughput (walk-steps/sec = walkers * steps /
wall-clock) and the sparsifier's inner loop (neighbor sample + prob_of
recompute per batch) for the device-resident engine against a frozen copy
of the seed's host-loop ``NeighborSampler``.

derived = "steps_per_sec=<new>;seed_steps_per_sec=<old>;speedup=<x>"

Also writes ``BENCH_sampling.json`` at the repo root so the perf trajectory
of the sampling engine is tracked from PR 1 onward.
"""
from __future__ import annotations

import json
import time
from pathlib import Path
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit
from repro.core.kernels_fn import Kernel, gaussian
from repro.core.sampling.edge import NeighborSampler
from repro.kernels.kde_sampler import ops as _sampler_ops
from repro.roofline import analysis as _roofline
from repro.obs.export import telemetry_block

_JSON_PATH = Path(__file__).resolve().parent.parent / "BENCH_sampling.json"


# --------------------------------------------------------------------- #
# Frozen seed implementation (host loop over blocks, numpy RNG) -- the
# baseline every future engine change is measured against.
# --------------------------------------------------------------------- #
class SeedHostSampler:
    def __init__(self, x, kernel: Kernel, samples_per_block: int = 16,
                 seed: int = 0):
        self.x = jnp.asarray(x, jnp.float32)
        self.kernel = kernel
        self.n = int(x.shape[0])
        self.block_size = max(int(np.sqrt(self.n)), 16)
        self.num_blocks = (self.n + self.block_size - 1) // self.block_size
        self.samples_per_block = min(samples_per_block, self.block_size)
        self._rng = np.random.default_rng(seed)

    def _block_sums(self, q):
        cols, sizes = [], []
        for b in range(self.num_blocks):           # the seed's host loop
            lo = b * self.block_size
            hi = min(lo + self.block_size, self.n)
            size = hi - lo
            s = min(self.samples_per_block, size)
            idx = lo + self._rng.choice(size, size=s, replace=False)
            cols.append(np.pad(idx, (0, self.samples_per_block - s),
                               constant_values=idx[0] if s else lo))
            sizes.append(size * (1.0 / max(s, 1)))
        idx = jnp.asarray(np.stack(cols))
        scale = np.asarray(sizes, np.float32)
        sub = self.x[idx.reshape(-1)]
        kv = np.asarray(self.kernel.pairwise(q, sub))
        kv = kv.reshape(q.shape[0], self.num_blocks, self.samples_per_block)
        return kv.sum(-1) * scale[None, :]

    def _masked_block_sums(self, src):
        bs = self._block_sums(self.x[jnp.asarray(src)])
        own = src // self.block_size
        bs[np.arange(len(src)), own] = np.maximum(
            bs[np.arange(len(src)), own] - 1.0, 1e-12)
        return np.maximum(bs, 1e-12)

    def _in_block_row(self, src, blk):
        w = len(src)
        lo = blk * self.block_size
        cols = lo[:, None] + np.arange(self.block_size)[None, :]
        valid = cols < self.n
        cols_c = np.minimum(cols, self.n - 1)
        xs = self.x[jnp.asarray(src)]
        xb = self.x[jnp.asarray(cols_c.reshape(-1))].reshape(
            w, self.block_size, -1)
        kv = np.asarray(jax.vmap(
            lambda a, b: self.kernel.pairwise(a[None, :], b)[0])(xs, xb))
        kv = kv * valid
        kv[cols_c == src[:, None]] = 0.0
        return kv, cols_c

    def _cat_rows(self, p):
        c = np.cumsum(p, axis=1)
        c = c / c[:, -1:]
        u = self._rng.uniform(size=(p.shape[0], 1))
        return (u > c).sum(axis=1).clip(0, p.shape[1] - 1)

    def sample(self, src) -> Tuple[np.ndarray, np.ndarray]:
        src = np.asarray(src)
        bs = self._masked_block_sums(src)
        pb = bs / bs.sum(axis=1, keepdims=True)
        blk = self._cat_rows(pb)
        kv, cols = self._in_block_row(src, blk)
        pin = kv / np.maximum(kv.sum(axis=1), 1e-30)[:, None]
        j = self._cat_rows(pin)
        nb = cols[np.arange(len(src)), j]
        return nb, pb[np.arange(len(src)), blk] * pin[np.arange(len(src)), j]

    def prob_of(self, src, dst):
        src, dst = np.asarray(src), np.asarray(dst)
        bs = self._masked_block_sums(src)
        pb = bs / bs.sum(axis=1, keepdims=True)
        blk = dst // self.block_size
        kv, _ = self._in_block_row(src, blk)
        rowsum = np.maximum(kv.sum(axis=1), 1e-30)
        kd = kv[np.arange(len(src)), dst - blk * self.block_size]
        return pb[np.arange(len(src)), blk] * kd / rowsum


def _walk_seed(sampler, starts, steps):
    cur = starts.copy()
    for _ in range(steps):
        cur, _ = sampler.sample(cur)
    return cur


def _time(fn, repeats=3, warmup=1):
    """Best-of-N FENCED wall seconds via ``obs.Timer`` (the return value
    of ``fn`` is ``block_until_ready``'d before the clock stops); min is
    robust against background load on shared CPUs."""
    from repro.obs.metrics import Timer
    return Timer("bench").timeit(fn, repeats=repeats, warmup=warmup,
                                 reduce="min") / 1e6


def _walk_scaling(quick: bool, rows: list):
    """n-sweep of walk throughput up to ~10^6 points (DESIGN.md §14).

    The fused walk's per-step cost under the walk-resident layout is
    O(cached cols) at level 1 plus O(walk_block_size) at level 2, both flat
    or sqrt-ish in n -- so walk-steps/sec should degrade only gently with n.
    ``cliff_ratio`` records thr(4096) / thr(n); the acceptance bound for
    this series is cliff_ratio <= 2 at n = 65536.

    Each entry also carries a measured-roofline fraction: modeled per-step
    operand bytes (cached level-1 read + level-2 stratum slab + CDF lanes)
    and kernel-eval flops against the backend's
    chip's published peaks (``roofline.analysis.CHIP_PEAKS``; "not
    measured" on the CPU backend).
    """
    sizes = [4096, 65536, 1048576] if quick else [
        4096, 16384, 65536, 262144, 1048576]
    walkers, steps, d = 256, 4, 16
    fb = _roofline.dtype_bytes("float32")
    spec = _roofline.device_chip_spec()
    entries = []
    base_sps = None
    for n in sizes:
        rng = np.random.default_rng(0)
        x = rng.normal(0, 0.5, (n, d)).astype(np.float32)
        ns = NeighborSampler(x, gaussian(bandwidth=4.0), mode="blocked",
                             samples_per_block=16, seed=0)
        starts = rng.integers(0, n, walkers).astype(np.int64)
        t = _time(lambda: ns.walk(starts, steps), repeats=3, warmup=1)
        sps = walkers * steps / t
        if base_sps is None:
            base_sps = sps
        cliff = base_sps / sps

        wbs, w_blocks, s_eff = _sampler_ops.walk_layout(
            ns.n, ns.block_size, ns.num_blocks, ns._cfg["s"])
        cols = w_blocks * s_eff
        evals_per_step = walkers * (cols + wbs)
        # Operand traffic per step: the cached level-1 read, the exact
        # level-2 stratum slab, and the grouped-CDF sum lanes.
        bytes_per_step = walkers * (cols * d + wbs * d
                                    + 4 * (w_blocks + wbs)) * fb
        flops_per_step = 2.0 * walkers * (cols + wbs) * d
        rl = _roofline.roofline_summary(spec, t / steps, flops_per_step,
                                        bytes_per_step)
        rows.append(emit(
            f"sampling/walk_scaling/n={n}", t / steps * 1e6,
            f"steps_per_sec={sps:.0f};cliff_ratio={cliff:.2f};"
            f"evals_per_step={evals_per_step};"
            f"roofline_frac={rl['fraction']}"))
        entries.append(dict(
            n=n, walkers=walkers, steps=steps, d=d,
            steps_per_sec=sps, us_per_step=t / steps * 1e6,
            cliff_ratio_vs_4096=cliff,
            walk_layout=dict(block_size=wbs, num_blocks=w_blocks,
                             samples_per_block=s_eff, cached_cols=cols),
            kernel_evals_per_step=evals_per_step,
            modeled_bytes_per_step=bytes_per_step,
            modeled_flops_per_step=flops_per_step,
            roofline=rl))
    return dict(walkers=walkers, steps=steps, d=d,
                spec=spec.as_dict() if spec else _roofline.NOT_MEASURED,
                entries=entries,
                cliff_ratio_65536=next(
                    (e["cliff_ratio_vs_4096"] for e in entries
                     if e["n"] == 65536), None))


def run(quick: bool = False):
    sizes = [4096] if quick else [4096, 16384, 65536]
    walkers = 256 if quick else 1024
    d = 16
    rows, results = [], []
    for n in sizes:
        rng = np.random.default_rng(0)
        x = rng.normal(0, 0.5, (n, d)).astype(np.float32)
        ker = gaussian(bandwidth=4.0)
        starts = rng.integers(0, n, walkers).astype(np.int64)

        new = NeighborSampler(x, ker, mode="blocked", samples_per_block=16,
                              seed=0)
        steps_new = 4 if quick else 8
        # record_path=True pins the PR-1 measurement semantics (the path
        # stack + transfer stays in the timed region) so the JSON series
        # remains comparable across PRs.
        t_new = _time(lambda: new.walk(starts, steps_new, record_path=True),
                      repeats=5, warmup=1)
        sps_new = walkers * steps_new / t_new

        old = SeedHostSampler(x, ker, samples_per_block=16, seed=0)
        steps_old = 2
        t_old = _time(lambda: _walk_seed(old, starts, steps_old), repeats=3,
                      warmup=1)
        sps_old = walkers * steps_old / t_old

        # sparsifier inner loop: neighbor sample + reverse prob recompute
        u = rng.integers(0, n, 512)
        v, _ = new.sample(u)
        t_sp_new = _time(lambda: (new.sample(u), new.prob_of(v, u)),
                         repeats=5, warmup=1)
        t_sp_old = _time(lambda: (old.sample(u), old.prob_of(v, u)),
                         repeats=2, warmup=0)

        speedup = sps_new / sps_old
        rows.append(emit(
            f"sampling/walk/n={n}", t_new / steps_new * 1e6 / 1.0,
            f"steps_per_sec={sps_new:.0f};seed_steps_per_sec={sps_old:.0f};"
            f"speedup={speedup:.1f}x"))
        rows.append(emit(
            f"sampling/sparsify_inner/n={n}", t_sp_new * 1e6,
            f"seed_us={t_sp_old * 1e6:.0f};speedup={t_sp_old / t_sp_new:.1f}x"))
        results.append(dict(
            n=n, walkers=walkers, d=d,
            walk_steps_per_sec=dict(fused=sps_new, seed_host_loop=sps_old),
            walk_speedup=speedup,
            sparsify_inner_sec=dict(fused=t_sp_new, seed_host_loop=t_sp_old),
            sparsify_inner_speedup=t_sp_old / t_sp_new))
    scaling = _walk_scaling(quick, rows)
    _JSON_PATH.write_text(json.dumps(dict(
        benchmark="bench_sampling", backend=jax.default_backend(),
        quick=quick, telemetry=telemetry_block(),
        results=results, scaling=scaling), indent=2) + "\n")
    return rows


if __name__ == "__main__":
    run(quick=True)
