"""Observability layer (DESIGN.md §15): counter-word algebra and scan-carry
folding, registry enable/disable semantics (disabled mode must be a no-op),
histogram determinism, exporter schema validation, and an 8-device
subprocess proof that the counter payload adds ZERO collectives to the §9
one-psum-per-draw schedule."""
import json
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import subproc
from repro.obs import counters as C
from repro.obs import export
from repro.obs import metrics as M


@pytest.fixture(autouse=True)
def _clean_registry():
    M.reset()
    M.disable()
    yield
    M.reset()
    M.disable()


# ------------------------------------------------------------- counters
def test_counter_word_algebra():
    """word/fold/fold_status/counter/totals: slot 0 ors, the rest add
    (mod 2^32 inside a word; HostTotals promotes to python ints)."""
    a = C.word(status=0x2, evals=10, draws=3)
    b = C.word(status=0x8, evals=5, retries=7)
    f = C.fold(a, b)
    t = C.totals(f)
    assert t["status"] == 0xA and t["evals"] == 15
    assert t["draws"] == 3 and t["retries"] == 7
    g = C.fold_status(a, 0x4)
    assert C.counter(g, "status") == 0x6
    assert C.counter(g, "evals") == 10     # fold_status touches slot 0 only
    s = C.scale(a, 3)
    assert C.counter(s, "evals") == 30 and C.counter(s, "status") == 0x2
    assert C.is_word(a) and not C.is_word(np.zeros(5, np.uint32))


def test_counter_word_uint32_wrap_and_host_totals():
    """Device slots wrap mod 2^32 by design; HostTotals accumulates in
    python ints so the serving ledger never wraps across calls."""
    big = C.word(evals=2**32 - 2)
    wrapped = C.fold(big, C.word(evals=5))
    assert C.counter(wrapped, "evals") == 3          # wrapped on device
    ht = C.HostTotals()
    for _ in range(3):
        ht.note(C.word(evals=2**31, status=0x1))
    assert ht["evals"] == 3 * 2**31                  # no wrap host-side
    assert ht.status == 0x1 and ht.words == 3
    d = ht.as_dict()
    assert d["evals"] == 3 * 2**31 and d["status"] == 0x1


def test_counter_word_scan_carry_interpret():
    """The walk_scan folding discipline -- per-step words fold-reduced
    through a ``lax.scan`` carry -- reproduces the host fold exactly, in
    interpret (eager, jit-disabled) AND compiled mode."""
    rng = np.random.default_rng(0)
    steps = np.stack([np.asarray(C.word(status=int(rng.integers(0, 4)),
                                        evals=int(rng.integers(0, 1000)),
                                        draws=int(rng.integers(0, 50)),
                                        retries=int(rng.integers(0, 9))))
                      for _ in range(16)])
    want = C.word()
    for w in steps:
        want = C.fold(want, w)
    want = C.totals(want)

    def scan_fold(ws):
        return jax.lax.scan(lambda c, w: (C.fold(c, w), None),
                            C.word(), ws)[0]

    with jax.disable_jit():                         # interpret mode
        eager = C.totals(scan_fold(jnp.asarray(steps)))
    compiled = C.totals(jax.jit(scan_fold)(jnp.asarray(steps)))
    assert eager == want and compiled == want


def test_walk_scan_word_matches_analytic(cloud=None):
    """End-to-end scan-carry check on the real program: a T-step walk's
    folded word must be exactly T times the per-step analytic word."""
    from repro.core.kernels_fn import gaussian
    from repro.core.sampling.edge import NeighborSampler
    rng = np.random.default_rng(1)
    x = rng.normal(0, 0.5, (128, 4)).astype(np.float32)
    nb = NeighborSampler(x, gaussian(1.0), mode="blocked",
                         exact_blocks=True, seed=0)
    e0, r0 = nb.evals, nb.device_counters["evals"]
    d0 = nb.device_counters["draws"]
    nb.walk(np.zeros(8, np.int64), 5)
    assert nb.device_counters["evals"] - r0 == nb.evals - e0
    assert nb.device_counters["draws"] - d0 == 5 * 8   # one draw/step/walker
    assert nb.device_counters.status == 0


# ------------------------------------------------------------- registry
def test_disabled_mode_is_noop():
    """Disabled registry: span() hands back the shared null span, and
    counter/gauge/observe/event leave NO state behind -- the enabled()
    branch is the entire cost."""
    assert not M.enabled()
    assert M.span("a") is M.span("b")               # singleton null span
    with M.span("a"):
        pass
    M.counter_inc("c", 5)
    M.gauge_set("g", 1.0)
    M.observe("h", 3.0)
    M.event("e", detail=1)
    reg = M.get_registry()
    assert reg["counters"] == {} and reg["gauges"] == {}
    assert reg["histograms"] == {} and not M.events()


def test_enabled_registry_records():
    M.enable()
    M.counter_inc("c", 2)
    M.counter_inc("c", 3)
    M.gauge_set("g", 7.5)
    M.observe("h", 100.0)
    M.event("e", k="v")
    with M.span("sp"):
        pass
    reg = M.get_registry()
    assert reg["counters"]["c"] == 5 and reg["gauges"]["g"] == 7.5
    assert "h" in reg["histograms"]
    assert M.events("e")[0][1]["k"] == "v"
    assert "span.sp.us" in M.histograms()           # span recorded a timing


def test_histogram_determinism():
    """Identical sample streams -> identical fixed-bucket p50/p99 (the
    quantiles are bucket-edge lookups, not interpolation over floats)."""
    vals = np.random.default_rng(7).lognormal(4, 2, 5000)
    h1, h2 = M.Histogram(), M.Histogram()
    for v in vals:
        h1.record(float(v))
    for v in vals:
        h2.record(float(v))
    assert h1.p50 == h2.p50 and h1.p99 == h2.p99
    assert h1.as_dict() == h2.as_dict()
    # quantiles are monotone and live on the fixed edge grid
    qs = [h1.quantile(q) for q in (0.1, 0.5, 0.9, 0.99)]
    assert qs == sorted(qs)


def test_timer_fences_and_records():
    M.enable()
    t = M.Timer("t")
    out = t.time(lambda: jnp.ones((64, 64)) @ jnp.ones((64, 64)))
    assert out.shape == (64, 64)
    us = t.timeit(lambda: jnp.ones(8) + 1, repeats=3, warmup=1)
    assert us > 0
    assert "timer.t.us" in M.histograms()


# ------------------------------------------------------------- exporters
def test_metrics_line_schema_validation():
    good = dict(schema_version=export.SCHEMA_VERSION, mode="multi-tenant",
                tenants=2, ticks=4, served=10, failed=0, p50_ms=1.0,
                p99_ms=2.0, throughput_rps=100.0, evictions=0, stale=0,
                realized_evals=123, per_tenant={})
    export.validate_metrics_line(good)
    with pytest.raises((ValueError, KeyError)):
        export.validate_metrics_line({k: v for k, v in good.items()
                                      if k != "realized_evals"})
    with pytest.raises((ValueError, KeyError)):
        bad = dict(good)
        bad["schema_version"] = export.SCHEMA_VERSION + 1
        export.validate_metrics_line(bad)


def test_telemetry_block_schema_validation():
    blk = export.telemetry_block(wall_us=12.5, realized_evals=42)
    export.validate_telemetry_block(blk, path="unit")
    assert blk["schema_version"] == export.SCHEMA_VERSION
    assert blk["fenced"] is True and blk["realized_evals"] == 42
    with pytest.raises((ValueError, KeyError)):
        export.validate_telemetry_block({"schema_version": 1}, path="unit")


def test_prometheus_text_dump():
    M.enable()
    M.counter_inc("serve.requests", 3)
    M.gauge_set("resident", 2.0)
    M.observe("lat.us", 50.0)
    txt = export.prometheus_text()
    assert "repro_serve_requests 3" in txt
    assert "repro_resident 2" in txt
    assert "repro_lat_us" in txt                    # histogram summary lines


def test_check_metrics_schema_tool(tmp_path):
    """The CI gate script: accepts a valid serve log, rejects a log with
    no metrics line, and rejects a BENCH artifact with no telemetry."""
    line = export.METRICS_PREFIX + json.dumps(dict(
        schema_version=export.SCHEMA_VERSION, mode="graph-stream", n=8,
        ticks=1, epoch=1, live=8, flags=[]))
    good = tmp_path / "good.log"
    good.write_text("noise\n" + line + "\n")
    bad = tmp_path / "bad.log"
    bad.write_text("no metrics here\n")
    bench = tmp_path / "BENCH_x.json"
    bench.write_text(json.dumps(dict(telemetry=export.telemetry_block())))
    sys.path.insert(0, "tools")
    try:
        import check_metrics_schema as cms
    finally:
        sys.path.pop(0)
    assert cms.main([str(good), "--bench-glob",
                     str(tmp_path / "BENCH_*.json")]) == 0
    assert cms.main([str(bad), "--no-bench"]) == 1
    bench.write_text(json.dumps(dict(results={})))
    assert cms.main(["--bench-glob", str(tmp_path / "BENCH_*.json")]) == 1


# ------------------------------------------------------------- sharded
def test_counter_payload_adds_zero_collectives_8dev():
    """DESIGN.md §15.1 acceptance: on an 8-device mesh the counter word
    leaves the §9 schedule at exactly one psum / zero ppermute per draw
    batch, the word's PSUMS slot records that schedule, and the EVALS
    slot equals the engine's analytic count."""
    code = """
import jax, jax.numpy as jnp, numpy as np
from repro.core.kernels_fn import gaussian
from repro.kernels.kde_sampler.sharded import ShardedBlocks, collective_counts
from repro.obs import counters as C
rng = np.random.default_rng(0)
n, bsz = 200, 16
x = rng.normal(0, 0.6, (n, 5)).astype(np.float32)
mesh = make_mesh((8,), ("data",))
eng = ShardedBlocks(mesh, x, gaussian(1.0), block_size=bsz, exact=True)
src = jnp.asarray(rng.integers(0, n, 48), jnp.int32)
key = jax.random.PRNGKey(1)
cc = collective_counts(lambda s, k: eng.fused_sample(s, k), src, key)
assert cc["psum_total"] == 1 and cc["ppermute_total"] == 0, cc
nb, prob, sums, cw = eng.fused_sample(src, key)
t = C.totals(cw)
assert t["psums"] == cc["psum_total"], t
assert t["status"] == 0 and t["draws"] == 48 and t["l1_reads"] == 48
w = 48
want = eng._l1_evals(w) + w * eng.block_size * eng.num_shards
assert t["evals"] == want, (t["evals"], want)
print("OBS_SHARDED_OK")
"""
    assert "OBS_SHARDED_OK" in subproc.run_devices(code, 8, tail=1200)
