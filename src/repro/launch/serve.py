"""Batched serving driver: prefill a batch of prompts, then decode with the
KV cache -- optionally with the paper's KDE attention for long contexts.

Example (CPU, reduced config):
  python -m repro.launch.serve --arch yi_6b --reduced --batch 4 \
      --prompt-len 64 --gen 16
  python -m repro.launch.serve --arch yi_6b --reduced --attention kde

With ``--attention kde --robust`` every decode step's logits are screened
for NaN/Inf; a flagged step is recomputed with the dense xla attention
from the pre-step cache (per-request graceful degradation, DESIGN.md §11)
and counted in the final report.

``--graph-stream N`` serves the OTHER side of the repo instead: an online
kernel-graph service over a mutating point set (DESIGN.md §12).  Each tick
mutates a fraction of the rows (insert/delete/update), then answers vertex
/ neighbor / edge-batch queries at the new epoch -- the samplers patch
their level-1 / degree / hash state instead of rebuilding.  The final
``[serve] metrics {...}`` line is machine-parsable JSON (per-tick
latencies, epoch, flags); a guard trip under ``REPRO_CHECKS=1`` exits 3:

  python -m repro.launch.serve --graph-stream 4096 --ticks 8 \
      --mutate-frac 0.01 --level1 hash

``--serve-tenants S`` runs the multi-tenant batched servable instead
(DESIGN.md §13): S mixed tenants (blocked + hashed level-1), ``--requests
R`` concurrent mixed requests per tick batched into padded device
programs, with p50/p99 request latency and throughput in the metrics
line:

  python -m repro.launch.serve --serve-tenants 4 --requests 16 --ticks 4

The same loop drives ``chip_smoke.py`` at deployment size (``--points``,
``--dim``; ``build_servable`` / ``serve_mix`` are its entry points).
"""
from __future__ import annotations

import argparse
import dataclasses
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ShapeConfig, get_config, get_reduced
from repro.data.pipeline import make_batch, token_split
from repro.launch.cache import use_compile_cache
from repro.models import transformer as T
from repro.obs import export as _export
from repro.obs import metrics as _metrics
from repro.train.train_step import make_decode_step


def _emit_metrics(payload: dict) -> None:
    """One schema-stamped JSON-lines metrics record (``obs.export``;
    tests, dashboards and ``tools/check_metrics_schema.py`` grep the
    ``[serve] metrics `` prefix and validate the rest)."""
    _export.emit_jsonl(payload)


def run_graph_stream(args, trace=None) -> int:
    """Online kernel-graph serving loop (DESIGN.md §12): mutate, then
    answer at the new epoch.  Cost per tick: O(m) mutation bookkeeping +
    one coalesced patch (O(w·m) level-1, O(n·m) degrees, O(m) hash
    splices) folded into the first query, vs. the frozen engines' full
    rebuild -- the ratio BENCH_streaming.json tracks.

    ``trace`` optionally scripts the mutations: a list of per-tick dicts
    with any of ``insert`` ((m, d) rows), ``delete`` (slot ids, or the
    string ``"frontier"`` to delete rows of the PREVIOUS tick's query
    frontier -- with ``--reuse-frontier`` this forces an ``EPOCH_STALE``
    consumer-side detection), and ``update`` ((slots, rows)).  Exit codes:
    0 clean; 3 when ``REPRO_CHECKS=1`` promoted a status flag to an
    ``EstimationError``."""
    from repro.core.kernels_fn import gaussian
    from repro.core.streaming import StreamingKernelGraph
    from repro.ft.guards import EstimationError

    n, d = int(args.graph_stream), 16
    rng = np.random.default_rng(args.seed)
    x0 = rng.normal(size=(n, d)).astype(np.float32)
    g = StreamingKernelGraph(x0, gaussian(1.0), level1=args.level1,
                             seed=args.seed)
    m = max(int(n * args.mutate_frac), 1)
    ticks = len(trace) if trace is not None else args.ticks
    reuse = bool(getattr(args, "reuse_frontier", False))
    mut_t = qry_t = 0.0
    ticks_done = 0
    frontier = None
    err = None
    try:
        for tick in range(ticks):
            t0 = time.time()
            if trace is not None:
                step = trace[tick]
                if step.get("insert") is not None:
                    g.insert(np.asarray(step["insert"], np.float32))
                dele = step.get("delete")
                if dele is not None:
                    if isinstance(dele, str) and dele == "frontier":
                        dele = (frontier if frontier is not None else
                                g.dataset.live_slots()[:m])
                    g.delete(np.asarray(dele))
                if step.get("update") is not None:
                    slots, rows = step["update"]
                    g.update(np.asarray(slots),
                             np.asarray(rows, np.float32))
            else:
                live = g.dataset.live_slots()
                g.insert(rng.normal(size=(m, d)).astype(np.float32))
                g.delete(rng.choice(live, size=m, replace=False))
                upd = rng.choice(g.dataset.live_slots(), size=m,
                                 replace=False)
                g.update(upd, rng.normal(size=(m, d)).astype(np.float32))
            mut_t += time.time() - t0
            t0 = time.time()
            u = (frontier if reuse and frontier is not None else
                 g.sample_vertices(min(256, n)))
            v, _ = g.sample_neighbors(u)
            g.sample_edges(min(512, n))
            qry_t += time.time() - t0
            assert g.dataset.is_live(v), "sampled a dead neighbor"
            frontier = u
            ticks_done += 1
    except EstimationError as e:
        err = str(e)
        print(f"[serve] guard tripped at tick {ticks_done}: {e}")
    rep = g.status_report()
    per = max(ticks_done, 1)
    print(f"[serve] graph-stream n={n} ticks={ticks_done}/{ticks} "
          f"mutate_frac={args.mutate_frac} level1={args.level1}")
    print(f"[serve] mutation {1e3 * mut_t / per:.1f} ms/tick, "
          f"queries {1e3 * qry_t / per:.1f} ms/tick "
          f"(patch-on-read, no rebuilds in the hot path)")
    _emit_metrics(dict(
        mode="graph-stream", n=n, ticks=ticks_done, ticks_planned=ticks,
        mutation_ms_per_tick=round(1e3 * mut_t / per, 3),
        query_ms_per_tick=round(1e3 * qry_t / per, 3),
        epoch=int(rep["epoch"]), live=int(rep["num_live"]),
        flags=rep["flags"], degree_rebuilds=int(rep["degree_rebuilds"]),
        hash_rebuilds=int(rep["hash_rebuilds"]), error=err))
    return 3 if err is not None else 0


def build_servable(points, kernel, tenant_opts, max_resident: int = 4,
                   seed: int = 0):
    """A :class:`KernelGraphServable` with one tenant ``t{i}`` per point
    set, tenant i configured by ``tenant_opts[i]`` (``add_tenant``
    keywords: ``level1``, ``exact_blocks``, ``hash_opts``, ...).  Each
    tenant's capacity is its point count (no insert headroom: the serving
    mix does not mutate)."""
    from repro.core.serving import KernelGraphServable
    srv = KernelGraphServable(max_resident=int(max_resident))
    names = []
    for i, (x, opts) in enumerate(zip(points, tenant_opts)):
        names.append(f"t{i}")
        srv.add_tenant(names[-1], x, kernel, capacity=int(x.shape[0]),
                       seed=seed + i, **opts)
    return srv, names


def submit_mix(srv, names, rng, tick: int, requests: int, seed: int):
    """One tick's mixed load: ``requests`` requests rotating over the
    tenants and over the ops sample (16 sources), query (8 dataset rows),
    walk (8 starts, length 4) and prob_of (16 pairs).  Returns the
    :class:`Request` handles."""
    reqs = []
    for r in range(requests):
        tn = names[(r + tick) % len(names)]
        n = srv.dataset(tn).num_live
        op = ("sample", "query", "walk", "prob_of")[r % 4]
        rs = seed + 1000 * tick + r
        if op == "sample":
            reqs.append(srv.submit(tn, "sample", seed=rs,
                                   src=rng.integers(0, n, size=16)))
        elif op == "query":
            rows = jnp.asarray(rng.integers(0, n, size=8))
            reqs.append(srv.submit(
                tn, "query", seed=rs,
                y=np.asarray(srv.dataset(tn).x_pad[rows])))
        elif op == "walk":
            reqs.append(srv.submit(tn, "walk", seed=rs, length=4,
                                   starts=rng.integers(0, n, size=8)))
        else:
            reqs.append(srv.submit(tn, "prob_of", seed=rs,
                                   src=rng.integers(0, n, size=16),
                                   dst=rng.integers(0, n, size=16)))
    return reqs


def serve_mix(srv, names, *, requests: int, ticks: int, seed: int = 0):
    """Warm-up tick (compiles every (op, bucket) group program; timed
    separately as ``warm_s``), then ``ticks`` measured ticks of
    :func:`submit_mix` load.  Every tick ends with the results on the
    host, so ``wall_s`` is fenced.  Returns the run summary, including
    the measured :class:`Request` objects under ``reqs``."""
    rng = np.random.default_rng(seed)
    t0 = time.perf_counter()
    warm = submit_mix(srv, names, rng, 0, requests, seed)
    srv.tick()
    warm_s = time.perf_counter() - t0
    reqs, stale = [], 0
    evals0 = srv.device_counters["evals"]
    t0 = time.perf_counter()
    for tick in range(1, ticks + 1):
        batch = submit_mix(srv, names, rng, tick, requests, seed)
        stale += srv.tick()["stale"]
        reqs.extend(batch)
    wall_s = time.perf_counter() - t0
    failed = sum(r.error is not None for r in reqs)
    return dict(warm_s=warm_s, wall_s=wall_s, reqs=reqs, warm_reqs=warm,
                served=len(reqs) - failed, failed=failed, stale=stale,
                realized_evals=srv.device_counters["evals"] - evals0)


def run_multi_tenant(args) -> int:
    """Multi-tenant batched serving loop (DESIGN.md §13): S tenants of
    ``--points`` x ``--dim`` points with mixed estimator configs,
    ``--requests`` concurrent mixed requests per tick drained into padded
    batch groups.  Reports p50/p99 submit -> completion latency and
    served-requests/s (steady-state: the first tick warms every (op,
    bucket) program off-clock).  Exit codes: 0 clean; 3 when any request
    failed (under ``REPRO_CHECKS=1`` a request's status flags become a
    per-request error)."""
    from repro.core.kernels_fn import gaussian

    if args.telemetry:
        _metrics.enable()
    S, R = int(args.serve_tenants), int(args.requests)
    n, d = int(args.points), int(args.dim)
    rng = np.random.default_rng(args.seed)
    points = [rng.normal(size=(n, d)).astype(np.float32) + 0.1 * i
              for i in range(S)]
    # one shared kernel config: tenants with equal static signatures
    # stack into the same batch group (the cross-tenant win)
    opts = [dict(level1="hash" if (args.level1 == "hash" and i % 2 == 1)
                 else "blocked") for i in range(S)]
    srv, names = build_servable(points, gaussian(1.0), opts,
                                max_resident=args.max_resident,
                                seed=args.seed)
    run = serve_mix(srv, names, requests=R, ticks=args.ticks,
                    seed=args.seed)
    per_tenant: dict = {}
    for r in run["reqs"]:
        pt = per_tenant.setdefault(r.tenant,
                                   dict(served=0, failed=0, lat_ms=[]))
        pt["lat_ms"].append(1e3 * r.latency)
        pt["served" if r.error is None else "failed"] += 1
    lat_ms = 1e3 * np.asarray([r.latency for r in run["reqs"]])
    rep = srv.report()
    served, failed, wall = run["served"], run["failed"], run["wall_s"]
    print(f"[serve] multi-tenant S={S} n={n} d={d} R={R}/tick "
          f"ticks={args.ticks} max_resident={args.max_resident}")
    print(f"[serve] p50 {np.percentile(lat_ms, 50):.1f} ms, "
          f"p99 {np.percentile(lat_ms, 99):.1f} ms, "
          f"{served / max(wall, 1e-9):.1f} req/s "
          f"(admissions={rep['admissions']} evictions={rep['evictions']})")
    _emit_metrics(dict(
        mode="multi-tenant", tenants=S, requests_per_tick=R,
        ticks=args.ticks, served=served, failed=failed, stale=run["stale"],
        p50_ms=round(float(np.percentile(lat_ms, 50)), 3),
        p99_ms=round(float(np.percentile(lat_ms, 99)), 3),
        throughput_rps=round(served / max(wall, 1e-9), 2),
        admissions=rep["admissions"], evictions=rep["evictions"],
        realized_evals=rep["device_counters"]["evals"],
        device_counters=rep["device_counters"],
        per_tenant={
            k: dict(served=v["served"], failed=v["failed"],
                    p50_ms=round(float(np.percentile(v["lat_ms"], 50)), 3))
            for k, v in sorted(per_tenant.items())},
        flags=rep["flags"]))
    if args.metrics_format == "prometheus":
        print(_export.prometheus_text(), end="")
    return 3 if failed else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi_6b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=0)
    ap.add_argument("--attention", choices=["xla", "kde"], default="xla")
    ap.add_argument("--kde-top-p", type=int, default=4)
    ap.add_argument("--kde-bk", type=int, default=32)
    ap.add_argument("--kde-stride", type=int, default=4)
    ap.add_argument("--robust", action="store_true",
                    help="screen decode logits; recompute flagged steps "
                         "with dense xla attention from the pre-step cache")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--graph-stream", type=int, default=0,
                    help="serve an online kernel graph over N points "
                         "instead of the LLM path (DESIGN.md §12)")
    ap.add_argument("--ticks", type=int, default=8)
    ap.add_argument("--mutate-frac", type=float, default=0.01)
    ap.add_argument("--level1", choices=["blocked", "hash"],
                    default="blocked")
    ap.add_argument("--reuse-frontier", action="store_true",
                    help="graph-stream: query the PREVIOUS tick's vertex "
                         "frontier (a scripted delete of those rows then "
                         "trips the EPOCH_STALE consumer check)")
    ap.add_argument("--serve-tenants", type=int, default=0,
                    help="run the multi-tenant batched servable over S "
                         "tenants instead (DESIGN.md §13)")
    ap.add_argument("--requests", type=int, default=16,
                    help="concurrent requests per serving tick")
    ap.add_argument("--points", type=int, default=2048,
                    help="multi-tenant: points per tenant")
    ap.add_argument("--dim", type=int, default=8,
                    help="multi-tenant: point dimension")
    ap.add_argument("--max-resident", type=int, default=4,
                    help="LRU bound on tenants holding device state")
    ap.add_argument("--telemetry", action="store_true",
                    help="enable the obs metrics registry (latency "
                         "histograms, counters; off by default so the "
                         "serving hot path stays branch-only)")
    ap.add_argument("--metrics-format", choices=["jsonl", "prometheus"],
                    default="jsonl",
                    help="'prometheus' additionally dumps the registry "
                         "in Prometheus text format after the run")
    args = ap.parse_args(argv)
    use_compile_cache()

    if args.serve_tenants:
        return run_multi_tenant(args)
    if args.graph_stream:
        return run_graph_stream(args)

    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    cfg = dataclasses.replace(cfg, dtype="float32")
    max_len = args.max_len or (args.prompt_len + args.gen)
    if args.attention == "kde":   # cache length must tile into KDE blocks
        max_len = ((max_len + args.kde_bk - 1) // args.kde_bk) * args.kde_bk
    shape = ShapeConfig("serve", args.prompt_len, args.batch, "prefill")

    params = T.init_params(jax.random.PRNGKey(args.seed), cfg)
    batch = {k: jnp.asarray(v)
             for k, v in make_batch(cfg, shape, 0, args.seed).items()}
    split = token_split(cfg, shape)

    # ---- prefill: run the forward once, then replay tokens into the cache
    # (teacher-forced cache build keeps one code path; production would use a
    # fused prefill kernel writing the cache directly)
    enc_len = split["frontend"] or 1
    cache = T.init_cache(cfg, args.batch, max_len, jnp.float32,
                         enc_len=enc_len)
    if cfg.is_encdec:
        cache["memory"] = T._run_encoder(params, cfg, batch["frontend"], "xla")

    kde_cfg = {"top_p": args.kde_top_p, "bk": args.kde_bk,
               "stride": args.kde_stride} if args.attention == "kde" else None
    step = jax.jit(make_decode_step(cfg, impl=args.attention, kde_cfg=kde_cfg))
    # staged fallback (DESIGN.md §11): a dense twin of the decode step,
    # built lazily so the happy path never compiles it.  Cache pytrees are
    # immutable, so holding the pre-step reference is free.
    robust = bool(args.robust) and args.attention != "xla"
    dense_step = None
    fallbacks = 0

    def guarded(cache_in, cur, pos):
        nonlocal dense_step, fallbacks
        nxt, logits, cache_out = step(params, cache_in, cur, jnp.int32(pos))
        if robust and not bool(jnp.all(jnp.isfinite(logits))):
            if dense_step is None:
                dense_step = jax.jit(make_decode_step(cfg, impl="xla"))
            fallbacks += 1
            nxt, logits, cache_out = dense_step(params, cache_in, cur,
                                                jnp.int32(pos))
        return nxt, logits, cache_out

    tokens = batch["tokens"]
    t0 = time.time()
    for pos in range(split["tokens"]):
        nxt, logits, cache = guarded(cache, tokens[:, pos:pos + 1], pos)
    prefill_t = time.time() - t0

    # ---- decode
    out = [np.asarray(nxt)]
    t0 = time.time()
    cur = nxt[:, None]
    for i in range(args.gen - 1):
        pos = split["tokens"] + i
        nxt, logits, cache = guarded(cache, cur, pos)
        cur = nxt[:, None]
        out.append(np.asarray(nxt))
    decode_t = time.time() - t0
    gen = np.stack(out, 1)
    print(f"[serve] arch={cfg.name} attention={args.attention} "
          f"batch={args.batch} prompt={split['tokens']} gen={args.gen}")
    print(f"[serve] prefill {prefill_t:.2f}s, decode {decode_t:.2f}s "
          f"({args.gen * args.batch / max(decode_t, 1e-9):.1f} tok/s)")
    if robust:
        print(f"[serve] robust: {fallbacks} step(s) recomputed with dense "
              f"attention")
    print(f"[serve] sample generations: {gen[:2].tolist()}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
