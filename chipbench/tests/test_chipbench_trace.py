"""The benchmark's trace reduction on hand-built events and on a small
recorded CPU trace: busy union, idle share, gaps and their attribution to
the benchmark's host spans, and time per operation name."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from chipbench import kernel_names  # noqa: E402
from chipbench import trace as T  # noqa: E402


def _trace():
    # window 0..100; ticks 0..40 and 50..100; device ops overlap
    ops = {"/device:TPU:0": [("fusion.1", 5, 15), ("fusion.2", 10, 20),
                             ("_sample_block_kernel", 30, 38),
                             ("all-reduce.3", 60, 70),
                             ("_masked_blocksum_kernel", 90, 110)]}
    spans = [("chipbench.window", 0, 100), ("chipbench.tick", 0, 40),
             ("chipbench.tick", 50, 100), ("chipbench.submit", 40, 50),
             ("other", 0, 100)]
    return T.Trace(ops=ops, spans=[s for s in spans
                                   if s[0].startswith(T.SPAN_PREFIX)])


def test_merge_unions_overlapping_and_drops_empty():
    assert T.merge([(10, 20), (5, 15), (30, 30), (20, 25), (40, 50)]) == [
        (5, 25), (40, 50)]
    assert T.merge([]) == []


def test_covered_clip_and_gaps():
    union = [(5, 20), (30, 38), (60, 70), (90, 110)]
    assert T.covered(union, 0, 100) == 15 + 8 + 10 + 10
    assert T.clip(union, 10, 35) == [(10, 20), (30, 35)]
    assert T.gaps(union, 0, 100) == [(0, 5), (20, 30), (38, 60), (70, 90)]
    assert T.gaps([], 3, 7) == [(3, 7)]
    assert T.gaps([(0, 10)], 2, 8) == []


def test_attribute_gaps_to_innermost_span():
    spans = [("chipbench.window", 0, 100), ("chipbench.tick", 0, 40),
             ("chipbench.submit", 40, 50)]
    got = T.attribute([(0, 5), (20, 30), (41, 49), (120, 130)], spans)
    assert got == {"chipbench.tick": 15, "chipbench.submit": 8,
                   T.OUTSIDE: 10}


def test_busy_idle_window_and_durations():
    tr = _trace()
    assert tr.window() == (0, 100)
    assert [s[1] for s in tr.spans_named("tick")] == [0, 50]
    assert T.busy(tr, 0, 100) == {"/device:TPU:0": 15 + 8 + 10 + 10}
    assert T.idle_share(tr) == pytest.approx(1 - 43 / 100)
    d = T.durations(tr.ops["/device:TPU:0"], 0, 100)
    assert d["_masked_blocksum_kernel"] == 10 and d["fusion.1"] == 10
    assert kernel_names.time_per_device(tr, kernel_names.SWEEP, 0, 100) \
        == {"/device:TPU:0": 18}
    assert kernel_names.time_per_device(tr, ("all-reduce",), 0, 100) == {
        "/device:TPU:0": 10}


def test_idle_share_needs_window_and_devices():
    assert T.idle_share(T.Trace(ops={}, spans=[("chipbench.window", 0, 9)])
                        ) is None
    assert T.idle_share(T.Trace(ops={"/device:TPU:0": [("a", 0, 1)]},
                                spans=[])) is None


def test_per_layer_reducers_on_hand_built_trace():
    from chipbench.harness import load_module
    tr = _trace()
    ctx = dict(trace=tr, record={"calls": 2, "attempted": 64, "failed": 0,
                                 "evals": 6400})
    host = load_module("metrics", "frontend.host_ms.serve").reduce(ctx)
    # tick 0..40 has 23 busy, tick 50..100 has 20 busy (in ns)
    assert host == pytest.approx(((40 - 23) + (50 - 20)) / 2 / 1e6)
    dev = load_module("metrics", "programs.device_ms.serve").reduce(ctx)
    assert dev == pytest.approx(43 / 2 / 1e6)
    assert load_module("metrics", "programs.evals_per_req.serve").reduce(
        ctx) == 100
    assert load_module("metrics", "sweep.device_ms.sparsify").reduce(ctx) \
        == pytest.approx(18 / 2 / 1e6)
    empty = dict(trace=T.Trace(ops={}, spans=[]), record={"calls": 2})
    for name in ("idle.serve", "sweep.device_ms.sparsify",
                 "frontend.host_ms.serve"):
        assert load_module("metrics", name).reduce(empty) is None


def test_load_reads_benchmark_spans_from_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda a: jnp.sin(a).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("chipbench.window"):
        with jax.profiler.TraceAnnotation("chipbench.tick"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    tr = T.load(str(tmp_path))
    assert [s[0] for s in sorted(tr.spans, key=lambda s: s[1])] == [
        "chipbench.window", "chipbench.tick"]
    lo, hi = tr.window()
    tick = tr.spans_named("tick")[0]
    assert lo <= tick[1] <= tick[2] <= hi
