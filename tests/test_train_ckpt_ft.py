"""Training loop, checkpointing, fault tolerance, elastic restore."""
import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import subproc
from repro.ckpt import checkpoint as ckpt
from repro.configs.base import ShapeConfig, get_reduced
from repro.data.pipeline import make_batch
from repro.ft.watchdog import Watchdog
from repro.models import transformer as T
from repro.train import optimizer as opt
from repro.train.train_step import make_train_step

SHAPE = ShapeConfig("t", 64, 4, "train")


def _setup(arch="yi_6b", seed=0):
    cfg = dataclasses.replace(get_reduced(arch), dtype="float32")
    params = T.init_params(jax.random.PRNGKey(seed), cfg)
    return cfg, params


def test_loss_decreases():
    cfg, params = _setup()
    step = jax.jit(make_train_step(cfg, opt.AdamWConfig(lr=2e-3,
                                                        warmup_steps=5)))
    state = opt.init_adamw(params)
    losses = []
    for i in range(30):
        batch = {k: jnp.asarray(v) for k, v in make_batch(cfg, SHAPE, i).items()}
        params, state, m = step(params, state, batch)
        losses.append(float(m["loss"]))
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.2, losses


def test_microbatch_equivalence():
    """Gradient accumulation over 4 microbatches ~= one big batch."""
    cfg, params = _setup()
    batch = {k: jnp.asarray(v) for k, v in make_batch(cfg, SHAPE, 0).items()}
    s1 = jax.jit(make_train_step(cfg))
    s4 = jax.jit(make_train_step(cfg, microbatch=4))
    state = opt.init_adamw(params)
    p1, _, m1 = s1(params, state, batch)
    p4, _, m4 = s4(params, state, batch)
    assert abs(float(m1["loss"]) - float(m4["loss"])) < 1e-3
    d = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))), p1, p4)
    assert max(jax.tree.leaves(d)) < 5e-3


def test_checkpoint_roundtrip(tmp_path):
    cfg, params = _setup()
    state = opt.init_adamw(params)
    path = str(tmp_path / "ck")
    ckpt.save(path, 7, (params, state))
    assert ckpt.latest_step(path) == 7
    (p2, s2), step = ckpt.restore(path, (params, state))
    assert step == 7
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_prunes_and_atomic(tmp_path):
    cfg, params = _setup()
    path = str(tmp_path / "ck")
    for s in (1, 2, 3, 4, 5):
        ckpt.save(path, s, params)
    kept = sorted(d for d in os.listdir(path) if d.startswith("step_"))
    assert len(kept) == 3 and ckpt.latest_step(path) == 5
    assert not any(d.endswith(".tmp") for d in os.listdir(path))


def test_resume_determinism(tmp_path):
    """Train 10; vs train 5 + resume + train 5: identical parameters
    (restart-safe data + exact state roundtrip)."""
    cfg, params0 = _setup()
    step = jax.jit(make_train_step(cfg))

    def run(params, state, lo, hi):
        for i in range(lo, hi):
            batch = {k: jnp.asarray(v)
                     for k, v in make_batch(cfg, SHAPE, i).items()}
            params, state, _ = step(params, state, batch)
        return params, state

    pA, sA = run(params0, opt.init_adamw(params0), 0, 10)
    pB, sB = run(params0, opt.init_adamw(params0), 0, 5)
    path = str(tmp_path / "ck")
    ckpt.save(path, 5, (pB, sB))
    (pB, sB), _ = ckpt.restore(path, (pB, sB))
    pB, sB = run(pB, sB, 5, 10)
    for a, b in zip(jax.tree.leaves(pA), jax.tree.leaves(pB)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_failure_injection_and_resume(tmp_path):
    """Kill the driver mid-run (exit 17); rerun resumes and finishes."""
    env = subproc.child_env()
    ckdir = str(tmp_path / "ck")
    cmd = [sys.executable, "-m", "repro.launch.train", "--arch", "yi_6b",
           "--reduced", "--steps", "12", "--batch", "2", "--seq", "32",
           "--ckpt-dir", ckdir, "--ckpt-every", "4", "--log-every", "4"]
    p = subprocess.run(cmd + ["--fail-at-step", "6"], env=env,
                       capture_output=True, text=True, cwd=".")
    assert p.returncode == 17, p.stderr[-500:]
    assert ckpt.latest_step(ckdir) == 4
    p = subprocess.run(cmd, env=env, capture_output=True, text=True, cwd=".")
    assert p.returncode == 0, p.stderr[-500:]
    assert "resumed from step 4" in p.stdout
    assert ckpt.latest_step(ckdir) == 12


def test_elastic_restore_different_mesh(tmp_path):
    """Checkpoint written under one sharding restores onto another mesh
    (data-axis resize) -- subprocess with 8 fake devices."""
    code = f"""
import dataclasses, jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs.base import get_reduced
from repro.models import transformer as T
from repro.distributed import sharding as shard
from repro.ckpt import checkpoint as ckpt
cfg = dataclasses.replace(get_reduced("yi_6b"), dtype="float32")
params = T.init_params(jax.random.PRNGKey(0), cfg)
mesh4 = make_mesh((4, 2), ("data", "model"))
p4 = jax.tree.map(jax.device_put, params, shard.param_shardings(params, mesh4))
ckpt.save({str(tmp_path)!r}, 3, p4)
mesh2 = make_mesh((2, 4), ("data", "model"))
sh2 = shard.param_shardings(params, mesh2)
restored, step = ckpt.restore({str(tmp_path)!r}, params, shardings=sh2)
assert step == 3
for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
print("ELASTIC_OK")
"""
    assert "ELASTIC_OK" in subproc.run_devices(code, 8, tail=800)


def test_watchdog():
    wd = Watchdog(hosts=4, straggler_factor=1.5, heartbeat_timeout_s=10)
    for step in range(5):
        for h in range(4):
            wd.beat(h, 1.0 if h != 2 else 2.5, now=float(step))
    d = wd.decide(now=5.0)
    assert d["stragglers"] == [2] and d["dead"] == []
    # host 3 stops beating
    for step in range(5, 30):
        for h in (0, 1, 2):
            wd.beat(h, 1.0 if h != 2 else 2.5, now=float(step))
    d = wd.decide(now=30.0)
    assert 3 in d["dead"]


def test_watchdog_flags_host_that_never_heartbeats():
    """Regression: decide() used to skip hosts with steps == 0, so a host
    that died before its FIRST heartbeat was never declared dead.  The
    clock now starts at construction for every host."""
    wd = Watchdog(hosts=3, heartbeat_timeout_s=10, now=0.0)
    wd.beat(0, 1.0, now=12.0)
    wd.beat(1, 1.0, now=12.0)
    # host 2 never beats; inside the window nobody is dead yet
    assert wd.decide(now=9.0)["dead"] == []
    d = wd.decide(now=15.0)
    assert d["dead"] == [2], d
    # silent hosts never enter the straggler EWMA median
    assert d["stragglers"] == []


def test_gradient_compression_error_feedback():
    """int8 compression: biased per step, but error feedback keeps the
    accumulated gradient sum accurate."""
    rng = np.random.default_rng(0)
    g_true = [rng.normal(0, 1, (64, 64)).astype(np.float32)
              for _ in range(20)]
    resid = jnp.zeros((64, 64), jnp.float32)
    acc_comp = np.zeros((64, 64), np.float32)
    for g in g_true:
        q, scale, resid = opt.compress(jnp.asarray(g), resid)
        acc_comp += np.asarray(opt.decompress(q, scale))
    acc_true = np.sum(g_true, axis=0)
    rel = np.abs(acc_comp - acc_true).max() / np.abs(acc_true).max()
    assert rel < 0.02, rel
