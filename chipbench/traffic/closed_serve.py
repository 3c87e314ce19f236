"""Closed-loop serving traffic for ``KernelGraphServable``.

A fixed set of clients, each with one request outstanding: every tick
submits one request per client, calls ``tick()`` once, and reads the
answers.  The mix file lists the clients (``op`` one of ``sample``,
``query`` and ``prob_of``, ``count``, ``width``) and the tenant they
address; every tick has the same composition, so the warm-up tick builds
every program the window runs.  Rows are uniform over the tenant's live rows; ``prob_of``
destinations differ from their sources; query points come from the
configuration's query set, eight consecutive rows per request.  All
payloads are made from the seed before the window.

After the window, a sample of the answered requests drawn from the seed
is compared with ``refs/kde.py``: each drawn or read probability against
``k(src, dst) / sum_{j != src} k(src, j)``, each query answer against the
exact row sum.  Every draw must be a row of the set other than its source.
Walks are not served: their endpoints alone cannot show whether every step
was taken (on this graph one step already mixes), so no comparison could
fail a walk that skips steps.
"""
from __future__ import annotations

import numpy as np

from chipbench import data
from chipbench.refs import kde as ref


class Loop:
    """The ``harness`` loop interface over one closed serving loop;
    ``devices`` is unused (one chip serves every tenant)."""

    def __init__(self, jax, spec, seed, devices, control=False):
        self.jax = jax
        self.conf = spec["config"]
        self.mix = spec["traffic"]
        self.seed = int(seed)
        self.s31 = data.seed31(seed)
        self.control = control
        self.srv = None

    # ------------------------------------------------------------------ #
    def _keys(self):
        jax = self.jax
        nt = len(self.conf["tenants"])
        ck = [data.prng_key(jax, self.seed, 2 * i) for i in range(nt)]
        pk = [data.prng_key(jax, self.seed, 2 * i + 1) for i in range(nt)]
        # the query set shares the first tenant's centres
        qk = data.prng_key(jax, self.seed, 2 * nt + 1)
        shapes = [self.conf["points"]] * nt + [self.conf["queries"]]
        return ck + [ck[0]], pk + [qk], shapes

    def _points(self):
        ck, pk, shapes = self._keys()
        return data.sift_like(self.jax, ck, pk, shapes, self.conf["dim"],
                              self.conf["clusters"])

    def setup(self) -> None:
        from repro.core.kernels_fn import gaussian
        from repro.launch.serve import build_servable

        sets = self._points()
        points, qset = list(sets[:-1]), sets[-1]
        self.bw = data.median_bandwidth(self.jax, points[0])
        opts = [{k: v for k, v in t.items() if k != "name"}
                for t in self.conf["tenants"]]
        self.srv, self.names = build_servable(
            points, gaussian(self.bw), opts,
            max_resident=self.conf["max_resident"], seed=self.s31)
        if self.control:
            # the control: the program's own bf16 path switched on
            for nm in self.names:
                self.srv.tenant(nm).opts["precision"] = "bf16"
        self.tenant = self.mix["tenant"]
        self.n = int(self.srv.dataset(self.tenant).num_live)
        self.queries = np.asarray(qset)
        del points, sets, qset
        self._make_payloads()
        # warm-up: one tick of exactly the window's composition
        for i, (op, kw) in enumerate(self._payload(self.max_ticks)):
            self.srv.submit(self.tenant, op, seed=self.s31 + i, **kw)
        self.srv.tick()

    def _make_payloads(self) -> None:
        """Per client and tick, every payload of up to ``max_ticks`` window
        ticks plus the warm-up tick, as host arrays."""
        rng = np.random.default_rng([self.s31, 0])
        T = self.max_ticks = int(self.mix["max_ticks"])
        n, nq = self.n, len(self.queries)
        self.clients = []
        ci = qi = 0
        nqc = sum(int(c["count"]) for c in self.mix["clients"]
                  if c["op"] == "query")
        for c in self.mix["clients"]:
            for _ in range(int(c["count"])):
                w = int(c["width"])
                p = dict(op=c["op"], id=ci)
                if c["op"] == "query":
                    # consecutive blocks of w rows, cycled over the set
                    p["start"] = ((np.arange(T + 1) * nqc + qi) * w) % (
                        nq // w * w)
                    qi += 1
                elif c["op"] == "prob_of":
                    src = rng.integers(0, n, size=(T + 1, w))
                    p["src"] = src.astype(np.int32)
                    p["dst"] = ((src + rng.integers(1, n, size=(T + 1, w)))
                                % n).astype(np.int32)
                elif c["op"] == "sample":
                    p["rows"] = rng.integers(0, n, size=(T + 1, w),
                                             dtype=np.int32)
                else:
                    raise ValueError(f"closed_serve serves no {c['op']!r}")
                p["width"] = w
                self.clients.append(p)
                ci += 1

    def _payload(self, t: int):
        out = []
        for p in self.clients:
            if p["op"] == "query":
                s = int(p["start"][t])
                out.append(("query", dict(y=self.queries[s:s + p["width"]])))
            elif p["op"] == "prob_of":
                out.append(("prob_of", dict(src=p["src"][t],
                                            dst=p["dst"][t])))
            else:
                out.append(("sample", dict(src=p["rows"][t])))
        return out

    def trace_counts(self) -> dict:
        from repro.kernels.kde_sampler import ops
        return dict(ops.TRACE_COUNTS)

    # ------------------------------------------------------------------ #
    def window(self, seconds: float, span) -> dict:
        import time
        reqs, tick_ms = [], []
        evals0 = self.srv.device_counters["evals"]
        nc = len(self.clients)
        t = 0
        with span("window"):
            t0 = time.perf_counter()
            end = t0 + seconds
            while True:
                with span("submit"):
                    batch = self._payload(t)
                    rs = [self.srv.submit(
                        self.tenant, op,
                        seed=(self.s31 + 1 + t * nc + i) % data.SEED_MOD,
                        **kw) for i, (op, kw) in enumerate(batch)]
                with span("tick"):
                    stats = self.srv.tick()
                reqs.extend(rs)
                tick_ms.append(stats["tick_ms"])
                t += 1
                now = time.perf_counter()
                if now >= end or t >= self.max_ticks:
                    break
        failed = sum(r.error is not None for r in reqs)
        return dict(requests=reqs, ticks=t, window_s=now - t0,
                    attempted=len(reqs), failed=failed,
                    evals=self.srv.device_counters["evals"] - evals0,
                    tick_ms_mean=float(np.mean(tick_ms)),
                    hit_max_ticks=int(t >= self.max_ticks))

    def release(self) -> None:
        import gc
        self.srv = None
        gc.collect()

    def end_to_end(self, rec) -> dict:
        lat = np.sort(np.asarray(
            [r.latency if r.error is None else np.inf
             for r in rec["requests"]]))
        rank = int(np.ceil(0.95 * len(lat))) - 1
        return {"served_rps": (rec["attempted"] - rec["failed"])
                / rec["window_s"],
                "p95_ms": 1e3 * float(lat[rank])}

    # ------------------------------------------------------------------ #
    def check(self, rec):
        jax = self.jax
        n = self.n
        reqs = rec["requests"]
        lim = self.mix["check"]["limits"]
        k = int(self.mix["check"]["requests_per_op"])
        ok = [r for r in reqs if r.error is None and r.result is not None]
        missing = len(reqs) - len(ok)
        bad = 0
        for r in ok:
            if r.op == "sample":
                nb, p = (np.asarray(a) for a in r.result)
                src = np.asarray(r.payload["src"])
                bad += int(np.sum((nb < 0) | (nb >= n) | (nb == src)
                                  | ~(p > 0) | ~(p <= 1)))
            elif r.op == "prob_of":
                p = np.asarray(r.result)
                bad += int(np.sum(~(p > 0) | ~(p <= 1)))
        rng = np.random.default_rng([self.s31, 1])

        def pick(op):
            cand = [r for r in ok if r.op == op]
            idx = rng.choice(len(cand), size=min(k, len(cand)),
                             replace=False) if cand else []
            return [cand[i] for i in sorted(idx)]

        # (src, dst, reported probability) of sampled draws and reads
        src, dst, prob = [], [], []
        for r in pick("sample"):
            src.append(np.asarray(r.payload["src"]))
            dst.append(np.asarray(r.result[0]))
            prob.append(np.asarray(r.result[1]))
        for r in pick("prob_of"):
            src.append(np.asarray(r.payload["src"]))
            dst.append(np.asarray(r.payload["dst"]))
            prob.append(np.asarray(r.result))
        queries = pick("query")
        ti = self.names.index(self.tenant)
        x = self._points()[ti]
        inv = 1.0 / (self.bw * self.bw)
        prob_rel = query_rel = None
        if src:
            src, dst = np.concatenate(src), np.concatenate(dst)
            prob = np.concatenate(prob).astype(np.float64)
            valid = (dst >= 0) & (dst < n)
            src, dst, prob = src[valid], dst[valid], prob[valid]
            us, inv_idx = np.unique(src, return_inverse=True)
            xs = np.asarray(x[us])
            deg = ref.rowsums(jax, xs, x, inv) - 1.0
            kv = ref.pairs(jax, x[src], x[dst], inv)
            want = kv / deg[inv_idx]
            prob_rel = float(np.max(np.abs(prob / want - 1.0)))
        if queries:
            y = np.concatenate([np.asarray(r.payload["y"]) for r in queries])
            got = np.concatenate([np.asarray(r.result, np.float64)
                                  for r in queries])
            want = ref.rowsums(jax, y, x, inv)
            query_rel = float(np.max(np.abs(got / want - 1.0)))
        numbers = dict(missing=missing, bad_draws=bad, prob_rel=prob_rel,
                       query_rel=query_rel)
        compared = {k: (v, lim[k]) for k, v in numbers.items()}
        info = dict(bandwidth=self.bw, checked_probs=int(len(prob)),
                    checked_queries=int(sum(len(r.payload["y"])
                                            for r in queries)))
        return compared, info
