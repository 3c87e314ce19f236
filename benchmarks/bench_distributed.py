"""Sharded engine benchmark: fused collective draws vs the frozen
host-orchestrated psum loop (DESIGN.md §9).

Baseline = the pre-PR-4 distributed pattern this PR deleted: level-1 block
sums come back to the host as one psum'd/concatenated array per step, the
host makes every sampling decision with numpy (block draw against the
totals, gather of the chosen block's rows, level-2 kernel evals + draw),
and the next step dispatches again -- one full device->host round-trip per
walk step per stage.  Do not "fix" this copy; it is the reference the
sharded engine is measured against.

New path = ``ShardedBlocks.walk_scan``: T steps, one program, one psum per
step, one transfer out.

Measured at n = 16384 (quick: n = 4096) on however many devices the
process sees -- run under ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
for the CI 8-shard configuration.  Writes ``BENCH_distributed.json``.

derived = "steps_per_sec=<new>;host_steps_per_sec=<old>;speedup=<x>"
"""
from __future__ import annotations

import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit
from repro.launch.mesh import make_mesh
from repro.core.kernels_fn import gaussian
from repro.kernels.kde_sampler.sharded import ShardedBlocks
from repro.obs.export import telemetry_block

_JSON_PATH = Path(__file__).resolve().parent.parent / "BENCH_distributed.json"


# --------------------------------------------------------------------- #
# Frozen host-orchestrated baseline (the deleted code path)
# --------------------------------------------------------------------- #
def _frozen_block_sums(mesh, kernel, num_blocks_per_shard, data_axes=("data",)):
    """Frozen copy of the pre-PR-4 ``sharded_block_sums``: local per-block
    sums concatenated over shards, consumed by the host."""
    from jax.sharding import PartitionSpec as P
    axes = tuple(data_axes)

    def local(y, x_shard):
        ns = x_shard.shape[0]
        bs = ns // num_blocks_per_shard
        kv = kernel.pairwise(y, x_shard)
        return kv.reshape(y.shape[0], num_blocks_per_shard, bs).sum(-1)

    return jax.jit(jax.shard_map(local, mesh=mesh, in_specs=(P(), P(axes)),
                                 out_specs=P(None, axes)))


def _host_orchestrated_walk(mesh, x, xs, kernel, starts, length, bs, rng):
    """Frozen host loop: per step, one distributed level-1 read, then every
    sampling decision on the host against the psum'd/gathered totals."""
    n = x.shape[0]
    nbps = (n // len(jax.devices())) // bs
    f_bs = _frozen_block_sums(mesh, kernel, nbps)
    cur = starts.copy()
    xd = jnp.asarray(x)
    for _ in range(length):
        sums = np.array(f_bs(xd[jnp.asarray(cur)], xs))      # (w, B) to host
        own = cur // bs
        sums[np.arange(len(cur)), own] = np.maximum(
            sums[np.arange(len(cur)), own] - 1.0, 1e-12)
        c = np.cumsum(sums, axis=1)
        u = rng.uniform(size=(len(cur), 1)) * c[:, -1:]
        blk = (u > c).sum(axis=1).clip(0, sums.shape[1] - 1)
        nxt = np.zeros(len(cur), np.int64)
        for i, b in enumerate(blk):                          # host level-2
            lo, hi = b * bs, min((b + 1) * bs, n)
            kv = np.array(kernel.pairwise(xd[cur[i]][None], xd[lo:hi]))[0]
            kv[lo + np.arange(hi - lo) == cur[i]] = 0.0
            cc = np.cumsum(kv)
            nxt[i] = lo + int((rng.uniform() * cc[-1] > cc).sum())
        cur = nxt
    return cur


def _time(fn, repeats=3, warmup=1):
    """Best-of-N FENCED wall seconds via ``obs.Timer`` (the return value
    of ``fn`` is ``block_until_ready``'d before the clock stops); min is
    robust against background load on shared CPUs."""
    from repro.obs.metrics import Timer
    return Timer("bench").timeit(fn, repeats=repeats, warmup=warmup,
                                 reduce="min") / 1e6


def _scaling(quick: bool, mesh, devices: int) -> dict:
    """n-sweep of the fused sharded walk up to ~10^6 points (DESIGN.md §14).

    Uses the subsampled level-1 configuration (``exact=False``,
    s = 16 rows per block) so the per-step cost stays O(w * B * s / p)
    per shard and the sweep reaches 10^6 points in quick mode.  Each entry
    carries a measured-roofline fraction: per-device operand bytes (local
    level-1 subsample read + owner-shard level-2 slab) and the one-psum
    collective payload against the chip's published peaks ("not
    measured" on the CPU backend).
    """
    from repro.roofline.analysis import (NOT_MEASURED, device_chip_spec,
                                         roofline_summary)
    sizes = [4096, 65536, 1048576] if quick else [
        4096, 65536, 262144, 1048576]
    w, length, d, s = 256, 4, 8, 16
    spec = device_chip_spec()
    rng = np.random.default_rng(0)
    entries = []
    for n in sizes:
        x = rng.normal(0, 0.5, (n, d)).astype(np.float32)
        ker = gaussian(2.0)
        bs = max(int(np.sqrt(n)), 16)
        eng = ShardedBlocks(mesh, x, ker, block_size=bs,
                            samples_per_block=s, exact=False)
        starts = jnp.asarray(rng.integers(0, n, w), jnp.int32)
        keys = jax.random.split(jax.random.PRNGKey(3), length)

        def stepper():
            end, *_ = eng.walk_scan(starts, keys)
            np.asarray(end)

        t = _time(stepper, repeats=3, warmup=1)
        sps = w * length / t
        num_blocks = -(-n // bs)
        # Per-device operand traffic per step: this shard's slice of the
        # subsampled level-1 read plus the (owner-shard) level-2 slab,
        # amortized 1/p; the psum moves the (w, p) candidate table.
        bytes_dev = (w * (num_blocks * s // devices) * d * 4
                     + w * bs * d * 4 // devices)
        coll_dev = 3 * w * devices * 4
        flops_dev = 2.0 * w * (num_blocks * s // devices + bs // devices) * d
        rl = roofline_summary(spec, t / length, flops_dev, bytes_dev,
                              chips=devices, coll_bytes_per_device=coll_dev)
        emit(f"distributed_walk_scaling/n={n}_p{devices}",
             t * 1e6 / (w * length),
             f"steps_per_sec={sps:.0f};"
             f"roofline_frac={rl['fraction']};"
             f"dominant={rl.get('dominant', 'not measured')}")
        entries.append(dict(
            n=n, block_size=bs, walkers=w, length=length, d=d,
            samples_per_block=s, steps_per_sec=sps,
            us_per_step=t / length * 1e6,
            modeled_bytes_per_device_step=bytes_dev,
            psum_bytes_per_device_step=coll_dev,
            roofline=rl))
    return dict(devices=devices,
                spec=spec.as_dict() if spec else NOT_MEASURED,
                entries=entries)


def run(quick: bool = False) -> None:
    """Benchmark entry point (called by ``benchmarks.run``)."""
    n = 4096 if quick else 16384
    w, length = 256, 8
    d = 8
    rng = np.random.default_rng(0)
    x = rng.normal(0, 0.5, (n, d)).astype(np.float32)
    ker = gaussian(2.0)
    devices = len(jax.devices())
    mesh = make_mesh((devices,), ("data",))
    bs = max(int(np.sqrt(n)), 16)

    eng = ShardedBlocks(mesh, x, ker, block_size=bs, exact=True)
    starts = rng.integers(0, n, w)
    keys = jax.random.split(jax.random.PRNGKey(1), length)

    def fused():
        end, *_ = eng.walk_scan(jnp.asarray(starts, jnp.int32), keys)
        np.asarray(end)

    t_fused = _time(fused)

    from repro.core.kde.distributed import make_sharded_dataset
    xs = make_sharded_dataset(mesh, x)
    host_repeats = 1 if not quick else 2

    def host():
        _host_orchestrated_walk(mesh, x, xs, ker, starts.copy(), length, bs,
                                np.random.default_rng(2))

    t_host = _time(host, repeats=host_repeats, warmup=1)

    steps = w * length
    new_sps = steps / t_fused
    old_sps = steps / t_host
    speedup = new_sps / old_sps
    emit(f"distributed_walk_n{n}_p{devices}", t_fused * 1e6 / steps,
         f"steps_per_sec={new_sps:.0f};host_steps_per_sec={old_sps:.0f};"
         f"speedup={speedup:.1f}")

    payload = {
        "n": n, "devices": devices, "walkers": w, "length": length,
        "block_size": bs,
        "fused_steps_per_sec": new_sps,
        "host_orchestrated_steps_per_sec": old_sps,
        "speedup": speedup,
        "scaling": _scaling(quick, mesh, devices),
        "telemetry": telemetry_block(wall_us=1e6 / new_sps),
    }
    _JSON_PATH.write_text(json.dumps(payload, indent=2) + "\n")
    print(f"# wrote {_JSON_PATH.name}: {speedup:.1f}x over the "
          f"host-orchestrated psum loop on {devices} device(s)")


if __name__ == "__main__":
    run(quick=True)
