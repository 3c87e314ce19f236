"""The benchmark's harness: finds a cell's files by name, runs its traffic
loop through set-up, the measured window and the correctness check, and
reduces what it measured to the cell's metrics.

A traffic loop (``traffic/<loop>.py``) defines ``Loop(jax, spec, seed,
devices, control=False)`` with:

* ``setup()``          -- data from the seed, the program, a warm-up of
  exactly the window's composition;
* ``window(seconds, span)`` -- the measured window; returns a record dict
  with at least ``attempted``, ``failed``, ``window_s``;
* ``release()``        -- drops the program's device state;
* ``end_to_end(rec)``  -- ``{metric: value}`` of the cell's end-to-end
  metrics other than ``setup_s`` and ``peak_hbm_gb``;
* ``check(rec)``       -- ``(compared, info)``: ``{name: (value, limit)}``
  of the numbers that decide ``correct``, and diagnostics.

A per-layer metric is ``metrics/<name>.py`` with ``reduce(ctx)`` returning
a number, or None when it finds nothing to read.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import shutil
import time
from pathlib import Path

from chipbench import trace as _trace

ROOT = Path(__file__).resolve().parent
REPO = ROOT.parent
TRACE_DIR = ROOT / "traces"


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    """``BENCHMARK.json`` at the root of the checkout."""
    return load_json(REPO / "BENCHMARK.json")


def cell_spec(name: str, bench: dict = None) -> dict:
    """The cell ``name`` with its configuration, traffic mix and the
    metrics that apply to it."""
    bench = bench or benchmark()
    cells = {c["name"]: c for c in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]

    def applies(m):
        return name in m.get("workloads", [name])

    return dict(
        cell=cell, config=load_json(REPO / conf["file"]),
        traffic=load_json(ROOT / "traffic" / f"{cell['traffic']}.json"),
        end_to_end=[m for m in bench["end_to_end"] if applies(m)],
        per_layer=[m for m in bench["per_layer"] if applies(m)])


def load_module(subdir: str, name: str):
    """Import ``chipbench/<subdir>/<name>.py`` (names may hold dots)."""
    path = ROOT / subdir / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{subdir}_{name.replace('.', '_').replace('-', '_')}",
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def peaks(device_kind: str) -> dict:
    """Published peaks of ``device_kind``; an unknown kind is an error."""
    table = load_json(ROOT / "peaks.json")
    if device_kind not in table:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r} in chipbench/peaks.json")
    return table[device_kind]


def use_compile_cache(jax) -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    if set, else ``<checkout>/.jax_cache`` (a fixed path, since the path
    is part of the cache key).  Every program is cached, however fast it
    compiled, so that a second run compiles nothing."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        REPO / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CompileCounter:
    """Counts JAX's tracing, compiling and cache-loading events."""

    def __init__(self, jax):
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._note)

    def _note(self, event, duration, **kw):
        if "compile" in event or "cache_retrieval" in event:
            self.count += 1


def span_factory(jax, on: bool):
    """``span(name)``: a host span ``chipbench.<name>`` in the profiler's
    trace when ``on``, else nothing."""
    def span(name):
        if not on:
            return contextlib.nullcontext()
        return jax.profiler.TraceAnnotation(_trace.SPAN_PREFIX + name)
    return span


def device_info(devices) -> dict:
    """Platform, kind, count and the peak bytes in use on the fullest."""
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": int(peak)}


def run_cell(jax, name: str, seed: int, seconds: float, trace: bool,
             devices, t_start: float, control: bool = False,
             spec: dict = None, log=print) -> dict:
    """One run of cell ``name``: set-up, window, check, metrics.  Returns
    the result line as a dict (``compared`` last)."""
    spec = spec or cell_spec(name)
    traffic = load_module("traffic", spec["traffic"]["loop"])
    loop = traffic.Loop(jax, spec, seed, devices, control=control)
    compiles = CompileCounter(jax)
    loop.setup()
    setup_s = time.perf_counter() - t_start
    in_use = max((d.memory_stats() or {}).get("bytes_in_use", 0)
                 for d in devices)
    counts0, compiles0 = loop.trace_counts(), compiles.count
    span = span_factory(jax, trace)
    tdir = TRACE_DIR / name
    if trace:
        shutil.rmtree(tdir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(tdir), profiler_options=opts)
    try:
        rec = loop.window(seconds, span)
    finally:
        if trace:
            jax.profiler.stop_trace()
    counts1 = loop.trace_counts()
    retraces = {k: counts1[k] - counts0.get(k, 0) for k in counts1
                if counts1[k] != counts0.get(k, 0)}
    log(json.dumps({"window": {
        "retraces": retraces, "compile_events": compiles.count - compiles0,
        "setup_s": setup_s, "bytes_in_use_after_setup": in_use,
        **{k: v for k, v in rec.items() if isinstance(v, (int, float))}}}))
    dev = device_info(devices)
    loop.release()
    compared, info = loop.check(rec)
    log(json.dumps({"check_info": info}))
    correct = all(v is not None and v == v and v <= lim
                  for v, lim in compared.values())
    metrics = {}
    if trace:
        tr = _trace.load(str(tdir))
        ctx = dict(trace=tr, record=rec, spec=spec, devices=devices)
        win = tr.window()
        if win is not None:
            busy = _trace.busy(tr, *win)
            dev["busy_s"] = sum(busy.values()) / max(len(busy), 1) / 1e9
            dev["window_s"] = (win[1] - win[0]) / 1e9
        for m in spec["per_layer"]:
            val = load_module("metrics", m["name"]).reduce(ctx)
            if val is not None:
                metrics[m["name"]] = {"value": val, "unit": m["unit"]}
        breakdown = _breakdown(tr, win) if win is not None else None
    else:
        e2e = loop.end_to_end(rec)
        e2e["setup_s"] = setup_s
        e2e["peak_hbm_gb"] = dev["memory_peak_bytes"] / 1e9
        for m in spec["end_to_end"]:
            if m["name"] in e2e:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
        breakdown = None
    out = {"correct": bool(correct), "attempted": int(rec["attempted"]),
           "failed": int(rec["failed"]), "metrics": metrics, "device": dev}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["compared"] = {k: {"value": v, "limit": lim}
                       for k, (v, lim) in compared.items()}
    return out


def _breakdown(tr, win) -> dict:
    """Top device operations by time and top idle gaps by host span,
    each averaged over the devices, in seconds, inside the window."""
    ndev = max(len(tr.ops), 1)
    ops, idle = {}, {}
    for evs in tr.ops.values():
        for k, v in _trace.durations(evs, *win).items():
            ops[k] = ops.get(k, 0.0) + v / ndev / 1e9
        union = _trace.merge([(s, e) for _, s, e in evs])
        for k, v in _trace.attribute(_trace.gaps(union, *win),
                                     tr.spans).items():
            idle[k] = idle.get(k, 0.0) + v / ndev / 1e9
    top = lambda d: [[k, d[k]] for k in sorted(d, key=d.get,
                                                  reverse=True)[:10]]
    return {"device_ops": top(ops), "idle_gaps": top(idle)}
