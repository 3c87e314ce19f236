"""The program's own spans and layer scopes as the benchmark reads them:
recorded CPU traces of a serving window and of a sparsifier call, the
spans-off switch, and the seven readers of ``chipbench/layers.py`` on
hand-built traces (a nested operation counted once; the benchmark's
older readers unchanged beside them; every reader silent on a trace the
program wrote nothing into)."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from chipbench import harness, layers  # noqa: E402
from chipbench import trace as T  # noqa: E402
from chipbench.tests import tiny  # noqa: E402

MS = 1e6                    # nanoseconds in a millisecond

NEW = ("frontend.stage_ms.serve", "frontend.dispatch_ms.serve",
       "frontend.scatter_ms.serve", "level1.device_ms.serve",
       "level2.device_ms.serve", "level1.device_ms.sparsify",
       "pipeline.host_ms.sparsify")
OLD = ("frontend.host_ms.serve", "programs.device_ms.serve",
       "programs.evals_per_req.serve", "sweep_roofline",
       "sweep.device_ms.sparsify", "idle.serve", "idle.sparsify")
PHASES = ("serve.stage", "serve.dispatch", "serve.readback",
          "serve.scatter")


def _profile(jax, tmp_path, fn):
    """Run ``fn`` under the CPU profiler; the benchmark's and the
    program's readings of the trace."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    return T.load(str(tmp_path)), layers.load(str(tmp_path))


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


@pytest.fixture
def serve_loop():
    import jax
    spec = tiny.spec("sift1m.exact-mix")
    loop = harness.load_module("traffic", "closed_serve").Loop(
        jax, spec, tiny.SEED, jax.devices()[:1])
    loop.setup()
    yield jax, loop
    loop.release()


def test_tick_spans_nest_inside_the_benchmark_tick(serve_loop, tmp_path):
    jax, loop = serve_loop
    span = harness.span_factory(jax, True)
    rec = {}
    tr, lay = _profile(jax, tmp_path,
                       lambda: rec.update(loop.window(0.2, span)))
    ticks = tr.spans_named("tick")
    assert len(ticks) == rec["ticks"] > 0
    for tick in ticks:
        mine = [s for s in lay.program_spans if _inside(s, tick)]
        names = [s[0] for s in mine]
        assert names.count("serve.tick") == 1
        assert names.count("serve.admit") == names.count("serve.group") == 1
        # sample, query and prob_of: three groups, four phases each
        for ph in PHASES:
            assert names.count(ph) == 3
        for grp in range(3):
            seq = [sorted((s for s in mine if s[0] == ph),
                          key=lambda s: s[1])[grp] for ph in PHASES]
            assert all(a[2] <= b[1] for a, b in zip(seq, seq[1:]))
    # every program span of the window lies inside a benchmark tick
    assert all(any(_inside(s, t) for t in ticks) for s in lay.program_spans)
    assert not [s for s in tr.spans if s[0].startswith("serve.")]


def test_spans_off_leave_no_program_span(serve_loop, tmp_path):
    from repro.obs import metrics as M
    jax, loop = serve_loop
    M.set_spans(False)
    try:
        assert M.span("serve.tick") is M._NULL_SPAN
        tr, lay = _profile(jax, tmp_path, lambda: loop.window(
            0.1, harness.span_factory(jax, True)))
    finally:
        M.set_spans(None)
    assert tr.spans_named("tick") and lay.program_spans == []


def test_sparsify_call_writes_its_five_spans(tmp_path):
    import jax
    import numpy as np
    from repro.core.kernels_fn import gaussian
    from repro.core.sparsify import spectral_sparsify
    x = jax.numpy.asarray(np.random.default_rng(0).normal(size=(256, 2)),
                          jax.numpy.float32)
    _, lay = _profile(jax, tmp_path, lambda: spectral_sparsify(
        x, gaussian(1.0), num_edges=512, estimator="exact",
        exact_blocks=True, seed=3))
    call, = lay.spans_named("sparsify.call")
    seq = [lay.spans_named(f"sparsify.{p}")
           for p in ("sampler", "degrees", "edges", "graph")]
    assert [len(s) for s in seq] == [1, 1, 1, 1]
    seq = [s[0] for s in seq]
    assert all(_inside(s, call) for s in seq)
    assert all(a[2] <= b[1] for a, b in zip(seq, seq[1:]))


# --------------------------------------------------------------------- #
# hand-built traces
# --------------------------------------------------------------------- #
L1 = "jit(batched_kde_query)/vmap(level1)/jit(exact_block_sums)/level1/dot"
L2 = "jit(batched_fused_sample)/vmap(level2)/gather"


def _serve_trace():
    """Window 0..100 ms, ticks 0..50 and 50..100; a level-1 loop nested
    inside its ``while`` on TPU:0, a thinner TPU:1."""
    ops = {"/device:TPU:0": [
        ("jit(q)/while", 5 * MS, 20 * MS),
        (L1, 5 * MS, 15 * MS), (L1 + "/body", 8 * MS, 12 * MS),
        (L2, 15 * MS, 18 * MS),
        (L1, 60 * MS, 70 * MS), (L2, 70 * MS, 71 * MS)],
        "/device:TPU:1": [(L1, 10 * MS, 12 * MS)]}
    spans = [("chipbench.window", 0, 100 * MS),
             ("chipbench.tick", 0, 50 * MS), ("chipbench.tick", 50 * MS,
                                               100 * MS)]
    prog = [("serve.tick", 0.5 * MS, 49 * MS),
            ("serve.stage", 1 * MS, 3 * MS), ("serve.stage", 51 * MS,
                                              54 * MS),
            ("serve.stage", 99 * MS, 102 * MS),          # 1 ms inside
            ("serve.dispatch", 3 * MS, 4 * MS), ("serve.dispatch", 54 * MS,
                                                 56 * MS),
            ("serve.readback", 4 * MS, 19 * MS),
            ("serve.scatter", 20 * MS, 21 * MS), ("serve.scatter", 70 * MS,
                                                  72 * MS)]
    tr = T.Trace(ops={k: [(n[-12:], s, e) for n, s, e in v]
                      for k, v in ops.items()}, spans=spans)
    return tr, layers.Layers(program_spans=prog, ops=ops)


def _sparsify_trace():
    """Two calls (0..50, 50..100 ms), each: sampler 0-5 (one op at 1-2),
    degrees 5-20 (level-1 reads 6-10, 12-16), edges 20-45 (an
    ``edge_scan`` while 21-44 around level-1 bodies 22-30, 31-40), graph
    45-50 (no op)."""
    ops, prog, spans = [], [], [("chipbench.window", 0, 100 * MS)]
    blk = "jit(_blocksum)/level1/_blocksum_kernel/pallas_call"
    scan = "jit(edge_batch_scan)/edge_scan/while"
    for c in (0, 50):
        def at(a, b, c=c):
            return (a + c) * MS, (b + c) * MS
        ops += [("jit(x)/slice", *at(1, 2)), (blk, *at(6, 10)),
                (blk, *at(12, 16)), (scan, *at(21, 44)),
                (scan + "/body/level1/pallas_call", *at(22, 30)),
                (scan + "/body/level1/pallas_call", *at(31, 40))]
        spans.append(("chipbench.call", *at(0, 50)))
        prog += [("sparsify.call", *at(0, 50)),
                 ("sparsify.sampler", *at(0, 5)),
                 ("sparsify.degrees", *at(5, 20)),
                 ("sparsify.edges", *at(20, 45)),
                 ("sparsify.graph", *at(45, 50))]
    tr = T.Trace(ops={"/device:TPU:0": [(p[-12:], s, e)
                                        for p, s, e in ops]}, spans=spans)
    return tr, layers.Layers(program_spans=prog,
                             ops={"/device:TPU:0": ops})


class _Dev:
    device_kind = "TPU v5 lite"


def _ctx(cell, tr, lay=None):
    ctx = dict(trace=tr, spec=harness.cell_spec(cell), devices=[_Dev()],
               record=dict(calls=2, attempted=64, failed=0, evals=640))
    if lay is not None:
        ctx["layers"] = lay
    return ctx


def _read(name, ctx):
    return harness.load_module("metrics", name).reduce(ctx)


def test_has_scope_reads_path_components():
    assert layers.has_scope(L1, "level1")
    assert layers.has_scope("jit(f)/vmap(level2)", "level2")
    assert not layers.has_scope("jit(level1_like)/dot", "level1")
    assert not layers.has_scope("", "level1")


def test_scope_time_counts_a_nested_operation_once():
    _, lay = _serve_trace()
    got = layers.scope_time(lay, "level1", 0, 100 * MS)
    # 5-15 with 8-12 nested inside it, then 60-70: 20 ms, not 24
    assert got == {"/device:TPU:0": 20 * MS, "/device:TPU:1": 2 * MS}
    assert layers.scope_time(lay, "level1", 0, 10 * MS)["/device:TPU:0"] \
        == 5 * MS
    assert layers.scope_time(lay, "edge_scan", 0, 100 * MS) == {
        "/device:TPU:0": 0, "/device:TPU:1": 0}


@pytest.mark.parametrize("name,want", [
    ("frontend.stage_ms.serve", (2 + 3 + 1) / 2),
    ("frontend.dispatch_ms.serve", (1 + 2) / 2),
    ("frontend.scatter_ms.serve", (1 + 2) / 2),
    ("level1.device_ms.serve", 20 / 2),
    ("level2.device_ms.serve", (3 + 1) / 2)])
def test_serve_readers_on_a_hand_built_trace(name, want):
    tr, lay = _serve_trace()
    assert _read(name, _ctx("sift1m.exact-mix", tr, lay)) == \
        pytest.approx(want)


@pytest.mark.parametrize("name,want", [
    # per call: 8 ms of degree reads + 17 ms of edge-scan reads
    ("level1.device_ms.sparsify", 8 + 17),
    # per call: sampler 5 - 1, degrees 15 - 8, graph 5 - 0
    ("pipeline.host_ms.sparsify", 4 + 7 + 5)])
def test_sparsify_readers_on_a_hand_built_trace(name, want):
    tr, lay = _sparsify_trace()
    assert _read(name, _ctx("nested64k.exact", tr, lay)) == \
        pytest.approx(want)


@pytest.mark.parametrize("cell,build", [
    ("sift1m.exact-mix", _serve_trace), ("nested64k.exact",
                                         _sparsify_trace)])
def test_new_readers_silent_where_the_program_wrote_nothing(cell, build):
    # the parent's program: no program span, no scope in any path
    tr, lay = build()
    bare = layers.Layers(program_spans=[], ops={
        d: [("jit(f)/fusion", s, e) for _, s, e in evs]
        for d, evs in lay.ops.items()})
    assert {n: _read(n, _ctx(cell, tr, bare)) for n in NEW} == \
        dict.fromkeys(NEW)
    # and on a trace without a device plane (a CPU run)
    hostonly = layers.Layers(program_spans=lay.program_spans, ops={})
    assert {n: _read(n, _ctx(cell, T.Trace(ops={}, spans=tr.spans),
                             hostonly)) for n in NEW} == dict.fromkeys(NEW)


@pytest.mark.parametrize("cell,build", [
    ("sift1m.exact-mix", _serve_trace), ("nested64k.exact",
                                         _sparsify_trace)])
def test_older_readers_unchanged_beside_program_spans(cell, build):
    tr, lay = build()
    empty = layers.Layers(program_spans=[], ops={})
    before = {n: _read(n, _ctx(cell, tr, empty)) for n in OLD}
    after = {n: _read(n, _ctx(cell, tr, lay)) for n in OLD}
    assert after == before
    assert any(v is not None for v in after.values())


def test_reader_loads_the_cells_trace_once(monkeypatch):
    calls = []
    monkeypatch.setattr(layers, "load", lambda path: calls.append(path))
    ctx = _ctx("sift1m.exact-mix", _serve_trace()[0])
    for name in NEW[:3]:
        assert _read(name, ctx) is None
    assert calls == [str(harness.TRACE_DIR / "sift1m.exact-mix")]


def test_report_attributes_busy_time():
    tr, lay = _serve_trace()
    out = layers.report(tr, lay)
    assert out["window_s"] == pytest.approx(0.1)
    assert out["busy_by_scope_s"]["level1"] == pytest.approx(
        (20 + 2) / 2 / 1e3)
    # busy: TPU:0 5-20 and 60-71 (26 ms), TPU:1 10-12 (2 ms); of it only
    # the unscoped while's 18-20 lies under no layer scope
    assert out["busy_unscoped_share"] == pytest.approx(2 / 28)
    # the one dispatch with a readback after it: 3-19 ms
    assert out["busy_in_dispatch_to_readback_share"] == pytest.approx(
        (14 + 2) / 28)


# --------------------------------------------------------------------- #
# op-name paths from the programs' HLO (the TPU trace's events carry none)
# --------------------------------------------------------------------- #
def _pb(num, val):
    """One protobuf field: an int as a varint, bytes length-delimited."""
    def varint(n):
        out = b""
        while True:
            out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
            n >>= 7
            if not n:
                return out
    if isinstance(val, int):
        return varint(num << 3) + varint(val)
    return varint(num << 3 | 2) + varint(len(val)) + val


def _instr(name, op_name):
    return _pb(2, _pb(1, name.encode()) + _pb(7, _pb(2, op_name.encode())))


def _space_with_hlo():
    module = _pb(3, _pb(1, b"main") + _instr("fusion.6", "jit(f)/level1/dot")
                 + _instr("while.3", "jit(f)/while"))
    module += _pb(3, _pb(1, b"body") + _instr("gather.2",
                                                "jit(f)/while/body/level2/g"))
    stat = _pb(1, 1) + _pb(6, _pb(1, module))
    meta = _pb(1, 7) + _pb(2, b"jit_f(7)") + _pb(5, stat)
    plane = (_pb(2, b"/host:metadata") + _pb(4, _pb(1, 7) + _pb(2, meta))
             + _pb(5, _pb(1, 1) + _pb(2, _pb(1, 1) + _pb(2, b"Hlo Proto"))))
    return _pb(1, _pb(2, b"/host:CPU")) + _pb(1, plane)


class _Ev:
    def __init__(self, name, start, dur, stats=()):
        self.name, self.start_ns, self.duration_ns = name, start, dur
        self.stats = list(stats)


def test_op_paths_come_from_the_programs_hlo(tmp_path):
    f = tmp_path / "t.xplane.pb"
    f.write_bytes(_space_with_hlo())
    raw = layers.hlo_protos(str(f))
    assert list(raw) == ["jit_f(7)"]
    names = layers.hlo_op_names(raw["jit_f(7)"])
    assert names == {"fusion.6": "jit(f)/level1/dot",
                     "while.3": "jit(f)/while",
                     "gather.2": "jit(f)/while/body/level2/g"}
    evs = [_Ev("%while.3 = (s32[]) while(...)", 10, 50),
           _Ev("%fusion.6 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop",
               12, 5),
           _Ev("%gather.2 = f32[8]{0} gather(...)", 20, 5),
           _Ev("%fusion.6 = f32[8]{0} fusion(...)", 200, 5),    # no program
           _Ev("%fusion.9 = f32[8]{0} fusion(...)", 30, 5,
               [("tf_op", "jit(g)/degrees/add")])]
    got = layers._op_paths(evs, [("jit_f(7)", 0, 100)], raw, {}, {})
    assert [p for p, _, _ in got] == [
        "jit(f)/while", "jit(f)/level1/dot", "jit(f)/while/body/level2/g",
        "", "jit(g)/degrees/add"]
    assert got[1][1:] == (12, 17)
    # a program named by another id: the one program of that name
    got = layers._op_paths(evs[1:2], [("jit_f(99)", 0, 100)], raw, {}, {})
    assert got[0][0] == "jit(f)/level1/dot"
    assert layers._program({"jit_f(1)": b"", "jit_f(2)": b""},
                           "jit_f(3)") is None


def test_hlo_of_a_profiled_program_holds_its_scopes(tmp_path):
    import jax
    import jax.numpy as jnp
    from repro.obs import metrics as M

    @jax.jit
    @M.scope("level1")
    def f(x):
        return jnp.exp(x) @ x

    x = jnp.ones((8, 8))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        f(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path, = tmp_path.glob("**/*.xplane.pb")
    raw = layers.hlo_protos(str(path))
    prog = [k for k in raw if k.startswith("jit_f(")]
    assert prog
    names = layers.hlo_op_names(raw[prog[0]])
    assert any(layers.has_scope(p, "level1") for p in names.values())
