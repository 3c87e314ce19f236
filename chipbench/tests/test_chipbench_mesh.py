"""The mesh cell, ``nested128k.mesh4``, at sizes a CPU test run can hold.

The cell's traffic loop, the sharded degree ring and two planted faults
of the collective draw each run in a child process with four virtual
CPU devices (``tests/subproc.py``); the collective matcher and the four
mesh readers run here on hand-built traces."""
import json
import math
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(REPO / "tests"))

import subproc  # noqa: E402
from chipbench import collectives, harness, layers  # noqa: E402
from chipbench import trace as T  # noqa: E402

CELL = "nested128k.mesh4"
MS = 1e6                    # nanoseconds in a millisecond

# prepended to each child: the checkout on the path, the tiny cell
_CHILD = """
import json, sys
sys.path.insert(0, ".")
import jax
from chipbench.tests import tiny
"""

# the owner draw always lands in shard 0 (the in-shard offset is kept),
# or the owner's share is read as if it were the whole mass
_FAULTS = {
    "owner_shard0": "nb % self.shard_size, prob",
    "unnormalised": "nb, prob * self.num_shards",
}


def _cell_run(prelude: str = "", control: bool = False) -> dict:
    out = subproc.run_devices(_CHILD + prelude + f"""
out = tiny.run(jax, {CELL!r}, devices=jax.devices()[:4], control={control})
print(json.dumps(out))
""", devices=4)
    return json.loads(out.strip().splitlines()[-1])


def test_mesh_cell_rehearsal_on_four_cpu_devices():
    out = _cell_run()
    window = json.loads(out["log"][0])["window"]
    assert window["retraces"] == {} and window["compile_events"] == 0
    assert out["correct"] is True and out["failed"] == 0
    assert out["device"]["count"] == 4
    # t = 10 n = 20,480 edges at n = 2,048: one psum per batch of 1,024
    assert window["psums"] == 20 * window["calls"] > 0
    assert {"sparsify_s", "peak_hbm_gb", "setup_s"} <= set(out["metrics"])


@pytest.mark.parametrize("fault", sorted(_FAULTS))
def test_planted_mesh_fault_reads_incorrect(fault):
    out = _cell_run(f"""
from repro.kernels.kde_sampler import sharded as sh
orig = sh._EngineSpec._local_draw
def broken(self, *a, **kw):
    nb, prob, tot, st = orig(self, *a, **kw)
    return {_FAULTS[fault]}, tot, st
sh._EngineSpec._local_draw = broken
""")
    assert out["correct"] is False, out["compared"]


def test_mesh_control_reads_incorrect():
    # every shard's level-1 sweep in bf16: the realized q_uv, and so the
    # edge weights, move by bf16 rounding, far past weight_rel's limit
    out = _cell_run(control=True)
    assert json.loads(out["log"][0])["window"]["retraces"] == {}
    assert out["correct"] is False, out["compared"]
    wrel = out["compared"]["weight_rel"]
    assert wrel["value"] > 10 * wrel["limit"]
    assert out["compared"]["bad_edges"]["value"] == 0


@pytest.mark.parametrize("n", [509, 1000])
def test_ring_degrees_match_the_reference_at_a_ragged_n(n):
    # n rows over 4 shards of whole sqrt(n)-row blocks leave sentinel rows
    # at the tail: 509 -> 4 x 132 (19 sentinels), 1000 -> 4 x 279 (116)
    out = subproc.run_devices(_CHILD + f"""
from chipbench import data
from chipbench.refs import kde as ref
from repro.core.kde.distributed import ShardedKDE
from repro.core.kernels_fn import gaussian
x = data.nested({n}, 11)
bw = data.median_bandwidth(jax, x)
est = ShardedKDE(make_mesh((4,), ("data",)), x, gaussian(bw), exact=True)
assert est.engine.n_pad > {n}
got = est.degrees()
want = ref.degrees(jax, x, 1.0 / bw ** 2, chunk={n})
print(json.dumps(float(abs(got / want - 1.0).max())))
""", devices=4)
    assert json.loads(out.strip().splitlines()[-1]) <= 1e-5


# --------------------------------------------------------------------- #
# the collective matcher and the mesh readers on hand-built traces
# --------------------------------------------------------------------- #
PSUM = ("%psum.7 = f32[1024,4,3]{0,1,2:T(4,128)S(1)} all-reduce("
        "f32[1024,4,3]{0,1,2:T(4,128)S(1)} %fusion.4), channel_id=1")
PERM0 = ("%collective-permute-start = (f32[32942,2]{0,1:T(2,128)S(1)}, "
         "f32[32942,2]{0,1:T(2,128)S(1)}, u32[]{:S(2)}) "
         "collective-permute-start(f32[32942,2]{0,1:T(2,128)S(1)} %copy.7)")
PERM1 = ("%ppermute.2 = f32[32942,2]{0,1:T(2,128)S(1)} collective-permute-"
         "done((f32[32942,2]{0,1:T(2,128)S(1)}) %collective-permute-start)")
FUSION = ("%fusion.6 = (f32[16,8]{1,0:T(8,128)S(1)}, f32[16,8,1000000]"
          "{2,1,0:T(8,128)}) fusion(f32[16,8,128]{2,1,0:T(8,128)} %y.1), "
          "kind=kOutput, calls=%all-reduce-like")
KERNEL = ("%_sample_block_kernel.1 = (s32[128,128]{1,0:T(8,128)S(1)}) "
          "custom-call(f32[128,128]{1,0:T(8,128)S(1)} %fusion)")


def test_collectives_are_named_by_their_hlo_opcode():
    assert collectives.opcode(PSUM) == "all-reduce"
    assert collectives.opcode(PERM0) == "collective-permute-start"
    assert collectives.opcode(PERM1) == "collective-permute-done"
    assert collectives.opcode(FUSION) == "fusion"
    assert collectives.opcode(KERNEL) == "custom-call"
    assert collectives.opcode("psum.7") == ""
    assert [collectives.is_collective(n) for n in
            (PSUM, PERM0, PERM1, FUSION, KERNEL, "all-reduce.3")] == [
        True, True, True, False, False, False]


def _mesh_trace():
    """A 100 ms window of two calls on two devices; device 1 spends
    more time in collectives (a psum nested in a fusion's interval
    counts once)."""
    ops = {"/device:TPU:0": [(FUSION, 0, 40 * MS), (PSUM, 41 * MS, 42 * MS),
                             (PERM0, 50 * MS, 51 * MS)],
           "/device:TPU:1": [(FUSION, 0, 40 * MS), (PSUM, 10 * MS, 14 * MS),
                             (PSUM, 12 * MS, 16 * MS),
                             (PERM1, 98 * MS, 104 * MS)]}
    spans = [("chipbench.window", 0, 100 * MS)]
    return T.Trace(ops=ops, spans=spans)


def _read(name, tr, record):
    ctx = dict(trace=tr, spec=harness.cell_spec(CELL), devices=[],
               record=record, layers=layers.Layers(program_spans=[], ops={}))
    return harness.load_module("metrics", name).reduce(ctx)


def test_collective_reader_on_a_hand_built_trace():
    tr = _mesh_trace()
    rec = dict(calls=2, psums=2560)
    # device 1: 10-16 ms of psums, 98-100 ms of the permute in the window
    assert _read("collective.device_ms.mesh", tr, rec) == \
        pytest.approx((6 + 2) / 2)
    assert _read("psums_per_call.mesh", tr, rec) == 1280
    bare = T.Trace(ops={d: [(FUSION, s, e) for _, s, e in evs]
                        for d, evs in tr.ops.items()}, spans=tr.spans)
    assert _read("collective.device_ms.mesh", bare, rec) is None


def test_mesh_readers_silent_where_nothing_was_written():
    # the parent's record (no psum count), a CPU trace (no device plane)
    tr = T.Trace(ops={}, spans=_mesh_trace().spans)
    got = {m["name"]: _read(m["name"], tr, dict(calls=2))
           for m in harness.cell_spec(CELL)["per_layer"]}
    assert got == dict.fromkeys(got) and len(got) == 4


def test_mesh_cell_asks_for_the_chips_its_mesh_holds():
    # a mesh configuration's cell takes one chip per mesh device, every
    # other cell one; at most half the cells (or one) take four
    bench = harness.benchmark()
    for cell in bench["workloads"]:
        mesh = harness.cell_spec(cell["name"], bench)["config"].get("mesh")
        assert cell["chips"] == (math.prod(mesh["shape"]) if mesh else 1)
    four = [c["name"] for c in bench["workloads"] if c["chips"] == 4]
    assert four == [CELL]
    assert len(four) <= max(1, len(bench["workloads"]) // 2)
