"""The harness around the cells: the work model, the peaks table, the
shape of ``BENCHMARK.json`` and its files, a CPU rehearsal of each
traffic loop with no retrace after the warm-up, and the command's
refusal to run without a TPU."""
import json
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from chipbench import harness  # noqa: E402
from chipbench.tests import tiny  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_sweep_work_by_hand():
    sweep = harness.load_module("work", "sweep")
    # one pass over 1000 x 128 f32 points and their 1000 f32 norms
    assert sweep.pass_bytes(1000, 128) == 1000 * 128 * 4 + 1000 * 4
    # 8 query requests of 8 points: one pass, 64 rows
    q = sweep.tick_work(1000, 128, rows=64)
    assert q == {"bytes": 516_000, "flops": 2 * 128 * 64 * 1000}
    chip = harness.peaks("TPU v5 lite")
    t = sweep.least_seconds(q, chip)
    assert t["bound"] == "memory"
    assert t["seconds"] == pytest.approx(516_000 / 819e9)
    # 4096 rows a pass: 2*128*4096 flop per 516 B/point -> compute bound
    c = sweep.tick_work(1000, 128, rows=4096)
    assert sweep.least_seconds(c, chip)["bound"] == "compute"
    assert sweep.least_seconds(c, chip)["seconds"] == pytest.approx(
        2 * 128 * 4096 * 1000 / 197e12)


def test_peaks_reject_unknown_kind():
    assert harness.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(ValueError, match="no published peaks"):
        harness.peaks("TPU v99")
    with pytest.raises(ValueError):
        harness.peaks("cpu")


def test_benchmark_json_names_its_files():
    bench = harness.benchmark()
    assert bench["command"][1] == "chipbench/run.py"
    assert bench["paths"] == ["chipbench"]
    configs = {c["name"]: c for c in bench["configs"]}
    for c in bench["configs"]:
        assert NAME.match(c["name"]) and (REPO / c["file"]).is_file()
        assert harness.load_json(REPO / c["file"])["name"] == c["name"]
    metrics = bench["end_to_end"] + bench["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    assert "setup_s" in {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert (harness.ROOT / "metrics" / f"{m['name']}.py").is_file()
    pairs = set()
    for cell in bench["workloads"]:
        assert NAME.match(cell["name"]) and cell["config"] in configs
        assert (cell["config"], cell["traffic"]) not in pairs
        pairs.add((cell["config"], cell["traffic"]))
        spec = harness.cell_spec(cell["name"])
        loop = spec["traffic"]["loop"]
        assert (harness.ROOT / "traffic" / f"{loop}.py").is_file()
        e2e = {m["name"] for m in spec["end_to_end"]}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert spec["per_layer"]
        for m in spec["per_layer"]:
            assert m["moves"] in e2e


@pytest.mark.parametrize("name", ["sift1m.exact-mix", "nested64k.exact"])
def test_loop_rehearsal_on_cpu(name):
    import jax
    out = tiny.run(jax, name)
    window = json.loads(out["log"][0])["window"]
    assert window["retraces"] == {}
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    metrics = set(out["metrics"])
    assert {"setup_s", "peak_hbm_gb"} <= metrics and len(metrics) >= 3
    assert list(out)[-2:] == ["compared", "log"]
    for c in out["compared"].values():
        assert c["value"] <= c["limit"]


def test_traced_rehearsal_reads_the_window():
    import jax
    out = tiny.run(jax, "sift1m.exact-mix", trace=True)
    assert out["device"]["window_s"] > 0
    assert "breakdown" in out
    # the CPU trace has no device plane: the device metrics stay silent
    assert set(out["metrics"]) <= {"programs.evals_per_req.serve"}


def _cpu_child(args, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, *args], capture_output=True,
                          text=True, cwd=cwd, env=env, timeout=120)


def test_command_refuses_a_machine_without_tpu():
    p = _cpu_child(["chipbench/run.py", "--workload", "nested64k.exact",
                    "--seed", "1", "--seconds", "1", "--trace", "0"], REPO)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "needs 1 TPU" in p.stderr


def test_command_fails_without_the_program(tmp_path):
    import shutil
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(REPO / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("traces", "__pycache__"))
    p = _cpu_child(["chipbench/run.py", "--workload", "sift1m.exact-mix",
                    "--seed", "1", "--seconds", "1", "--trace", "0"],
                   tmp_path)
    assert p.returncode != 0 and "{" not in p.stdout
