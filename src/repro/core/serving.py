"""Multi-tenant batched serving layer for the kernel-graph primitives
(DESIGN.md §13).

The paper's value proposition is answering many KDE / sampling queries
cheaply after one sub-quadratic preprocessing pass -- exactly the shape of
a serving workload.  :class:`KernelGraphServable` is the saxml-style
servable on top of the fused engines: callers :meth:`~KernelGraphServable.
submit` ``query`` / ``sample`` / ``walk`` / ``prob_of`` requests against
named tenants (each one ``DynamicDataset`` + estimator state), and every
:meth:`~KernelGraphServable.tick` drains the queue into as few padded
device batches as the static shapes allow:

* **continuous batching** -- concurrent requests are grouped by
  ``(op, tenant signature, shape bucket)`` and run as ONE program via the
  ``batched_*`` entry points of ``kernels/kde_sampler`` / ``kde_hash``
  (``jax.vmap`` over the request axis; a one-tenant group's exact Pallas
  draw, ``prob_of`` or query read packs all its rows into ONE level-1
  pass), with per-request PRNG keys and per-request uint32 status
  words.  The tick counts those passes (``level1_passes``,
  ``serve.level1_passes``).  Request widths are padded up to a
  static bucket (powers of two by default), so the number of compiled
  programs is bounded by ``len(buckets)`` per (tenant signature, op) --
  not by the workload's request shapes.
* **tenant lifecycle** -- tenants' level-1 block structures and hash
  states are admitted on first use and evicted least-recently-used when
  more than ``max_resident`` tenants hold device state; the backing
  ``DynamicDataset`` (source of truth) always stays, so a re-admitted
  tenant simply rebuilds its derived state.  Mutating a tenant's dataset
  between ticks is safe: admission syncs through the ``(dataset_id,
  epoch)`` contract, and requests whose frontier rows died get a
  per-request ``EPOCH_STALE`` error without poisoning the rest of the
  batch.
* **guard semantics** -- the per-request status words flow through
  ``guards.raise_per_request``: under ``REPRO_CHECKS=1`` a flagged
  request carries its own ``EstimationError`` in ``Request.error`` while
  the other lanes of the tick complete normally.
* **mesh tenants** -- a tenant built with ``mesh=`` serves draws through
  its sharded engine: same-op requests are concatenated into one draw
  batch, preserving the one-psum-per-draw-batch schedule of DESIGN.md §9
  (the batching layer adds zero extra collectives, asserted in
  ``tests/test_serving.py``).

Distributional contract (``tests/test_serving.py``): a served request is
the SAME computation as the sequential single-tenant call with the same
key -- bitwise for keyed walks and draws when the request width equals
its shape bucket, and distribution-identical (each padded lane still
consumes iid uniforms) otherwise.  Mesh ``sample``/``prob_of`` groups
fold every co-batched request's seed into one key stream (see
:meth:`KernelGraphServable.submit`).  :meth:`~KernelGraphServable.tick`
itself never raises: admission, grouping, and each group's program are
fault-isolated, attaching failures to exactly the affected requests.

>>> srv = KernelGraphServable(max_resident=2)
>>> srv.add_tenant("a", xa, gaussian(1.0))
>>> r = srv.submit("a", "sample", src=np.arange(8), seed=0)
>>> srv.tick(); nb, prob = r.result
"""
from __future__ import annotations

import dataclasses
import functools
import time
from collections import Counter, OrderedDict
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.dataset import DynamicDataset
from repro.core.kernels_fn import Kernel
from repro.core.sampling.edge import _BENIGN, NeighborSampler
from repro.ft import guards as _g
from repro.obs import counters as _c
from repro.obs import metrics as _m

#: ops a request may name, and the payload key(s) each one takes
REQUEST_OPS = ("query", "sample", "walk", "prob_of")

#: default request-width buckets (powers of two); a request of width w is
#: padded to the smallest bucket >= w, bounding compiles per group
DEFAULT_BUCKETS = (4, 8, 16, 32, 64, 128, 256)


def shape_bucket(w: int, buckets=DEFAULT_BUCKETS) -> int:
    """Smallest static bucket >= ``w`` (next power of two past the table).
    Padding to buckets is what bounds recompiles: every compiled program
    is keyed by its padded shapes, so the program count per (tenant
    signature, op) is at most ``len(buckets)`` plus the overflow tail."""
    for b in buckets:
        if w <= b:
            return b
    p = 1
    while p < w:
        p <<= 1
    return p


_HOST_KEYS: Optional[str] = None


def _host_key_layout() -> str:
    """Probed-once layout of ``jax.random.PRNGKey(s)`` for the default
    threefry2x32 impl: ``"x64"`` -> ``[s >> 32, s & 0xffffffff]``,
    ``"x32"`` -> ``[0, s & 0xffffffff]`` (seeds truncated to 32 bits when
    ``jax_enable_x64`` is off), ``"opaque"`` -> unknown (custom PRNG)."""
    global _HOST_KEYS
    if _HOST_KEYS is None:
        probe = np.asarray(jax.random.PRNGKey((11 << 32) | 13))
        if probe.dtype != np.uint32 or probe.shape != (2,):
            _HOST_KEYS = "opaque"
        elif probe[0] == 11 and probe[1] == 13:
            _HOST_KEYS = "x64"
        elif probe[0] == 0 and probe[1] == 13:
            _HOST_KEYS = "x32"
        else:                                          # pragma: no cover
            _HOST_KEYS = "opaque"
    return _HOST_KEYS


def _batch_keys(seeds):
    """Per-request PRNG keys, stacked into one ``(R, 2)`` uint32 array.

    Seeding is on the per-tick critical path: ``jax.random.PRNGKey`` is a
    jitted program per call, so R requests would pay R dispatches before
    the batch even runs.  With the default threefry layout the keys are
    assembled in numpy (the jitted batch entry point transfers them with
    the rest of its arguments) -- bitwise identical to stacking
    ``PRNGKey(seed)`` per request (asserted in ``tests/test_serving.py``
    parity tests, which compare served draws against sequential calls
    keyed by ``PRNGKey``)."""
    layout = _host_key_layout()
    if layout != "opaque" and all(0 <= s < (1 << 31) for s in seeds):
        a = np.asarray(seeds, np.uint64)
        hi = ((a >> np.uint64(32)) if layout == "x64"
              else np.zeros_like(a)).astype(np.uint32)
        return np.stack(
            [hi, (a & np.uint64(0xFFFFFFFF)).astype(np.uint32)], axis=-1)
    return jnp.stack([jax.random.PRNGKey(int(s)) for s in seeds])


@functools.partial(jax.jit, static_argnames=("num",))
def _split_batch(keys: jax.Array, num: int) -> jax.Array:
    """``jax.random.split`` every key in a ``(R, 2)`` stack into ``num``
    subkeys as ONE program -- same bits as R sequential split calls."""
    return jax.vmap(lambda k: jax.random.split(k, num))(keys)


@dataclasses.dataclass
class Request:
    """One submitted serving request and, after its tick, its outcome.

    ``result`` mirrors the sequential API: ``sample`` -> (neighbors,
    probs); ``walk`` -> (endpoints, path-or-None); ``prob_of`` -> probs;
    ``query`` -> estimates.  ``status`` is the request's own uint32 flag
    word; ``error`` is the per-request ``EstimationError`` under
    ``REPRO_CHECKS=1`` (the tick itself never raises)."""

    tenant: str
    op: str
    payload: dict
    seed: int
    rid: int
    submitted: float
    status: int = 0
    result: object = None
    error: Optional[Exception] = None
    finished: Optional[float] = None

    @property
    def done(self) -> bool:
        """True once a tick produced a result or an error."""
        return self.finished is not None

    @property
    def latency(self) -> float:
        """Submit -> completion wall time in seconds (nan until done)."""
        return (self.finished - self.submitted) if self.done else float("nan")


class ServedTenant:
    """One tenant: a mutable ``DynamicDataset`` plus lazily-admitted
    estimator state (``NeighborSampler`` level-1 cache / hash layout).

    ``admit()`` builds or syncs the device state; ``release()`` drops it
    (LRU eviction) -- the dataset is the source of truth, so eviction
    never loses data, it only trades the rebuild cost back in."""

    def __init__(self, name: str, dataset: DynamicDataset, kernel: Kernel,
                 seed: int, opts: dict):
        self.name = name
        self.dataset = dataset
        self.kernel = kernel
        self.seed = int(seed)
        self.opts = dict(opts)
        self.nbr: Optional[NeighborSampler] = None
        self.builds = 0

    @property
    def resident(self) -> bool:
        """True while the tenant's derived device state is admitted."""
        return self.nbr is not None

    @property
    def mesh(self):
        """The tenant's mesh (None for flat single-device tenants)."""
        return self.opts.get("mesh")

    def admit(self) -> NeighborSampler:
        """Build (first use / after eviction) or epoch-sync the sampler."""
        if self.nbr is None:
            self.nbr = NeighborSampler(
                self.dataset.x_pad, self.kernel, dataset=self.dataset,
                seed=self.seed, **self.opts)
            self.builds += 1
        else:
            self.nbr._sync()
        return self.nbr

    def release(self) -> None:
        """Drop the derived device state (level-1 cache, hash layout)."""
        self.nbr = None

    # ------------------------------------------------------------------ #
    def _state_sig(self):
        """Hashable shape signature of the hash state (None when absent);
        part of the group key so only stack-compatible tenants batch."""
        hs = self.nbr._hstate
        if hs is None:
            return None
        return tuple((tuple(a.shape), str(a.dtype))
                     for a in jax.tree_util.tree_leaves(hs))

    def draw_sig(self):
        """Static signature of the tenant's draw programs: equal
        signatures => the stacked arena traces ONE program for the
        whole group.  Includes the padded dataset shape (not just the
        ``n`` config key): tenants must agree on the feature dimension
        ``d`` too, or the arena's ``jnp.stack`` would reject them."""
        c = self.nbr._cfg
        return (tuple(sorted(c.items())) + (tuple(self.nbr.x.shape),)
                + (self._state_sig(),))

    def query_sig(self):
        """Static signature of the tenant's query program (the dense
        level-1 read, or the hashed estimator's config + layout shapes);
        both carry the padded dataset shape so only stack-compatible
        tenants (same ``n_pad`` AND ``d``) share a group."""
        nbr = self.nbr
        if nbr.level1 == "hash":
            hq = nbr.hash_estimator
            return ("hash-query", tuple(sorted(hq._cfg.items())),
                    tuple(nbr.x.shape), self._state_sig())
        keys = ("kind", "inv_bw", "beta", "pairwise", "block_size",
                "num_blocks", "n", "s", "exact")
        return ("dense-query", tuple((k, nbr._cfg[k]) for k in keys),
                tuple(nbr.x.shape))


def _pad_idx(a, wb: int) -> np.ndarray:
    """Pad a 1-d index payload to its bucket by repeating the first
    element -- padded lanes sample from a real live row (no spurious
    flags) and are sliced off before the result is returned."""
    a = np.ascontiguousarray(np.asarray(a).reshape(-1), np.int32)
    if len(a) == wb:
        return a
    fill = a[0] if len(a) else np.int32(0)
    return np.concatenate([a, np.full(wb - len(a), fill, np.int32)])


def _pad_pts(y, qb: int) -> np.ndarray:
    """Pad a (q, d) query-point payload to its bucket with row 0."""
    y = np.ascontiguousarray(np.asarray(y, np.float32))
    if y.ndim == 1:
        y = y[None, :]
    if len(y) == qb:
        return y
    fill = y[:1] if len(y) else np.zeros((1, y.shape[1]), np.float32)
    return np.concatenate([y, np.repeat(fill, qb - len(y), axis=0)])


class KernelGraphServable:
    """Batched multi-tenant front end over the kernel-graph engines.

    Lifecycle: :meth:`add_tenant` registers datasets; :meth:`submit`
    enqueues requests (non-blocking); :meth:`tick` drains the queue into
    padded batch groups, runs each group as one device program, and
    scatters per-request results / status words / errors back onto the
    :class:`Request` objects.  Cost per tick: one ``batched_*`` program
    per (tenant signature, op, bucket) group -- compiled once per group
    shape and cached by jit thereafter -- plus O(R) host bookkeeping.

    ``max_resident`` bounds how many tenants hold derived device state
    (level-1 blocks + hash layouts) at once; the LRU policy evicts idle
    tenants first and never evicts a tenant needed by the current tick
    (the resident set may transiently overshoot if one tick touches more
    than ``max_resident`` tenants).
    """

    def __init__(self, max_resident: int = 4, buckets=DEFAULT_BUCKETS,
                 arena_cache: int = 16):
        self.max_resident = int(max_resident)
        self.buckets = tuple(buckets)
        self._tenants: dict = {}
        self._lru: OrderedDict = OrderedDict()
        self._queue: list = []
        self._arenas: OrderedDict = OrderedDict()
        self._arena_cap = int(arena_cache)
        self._rid = 0
        self.ticks = 0
        self.admissions = 0
        self.evictions = 0
        self.served = 0
        self.failed = 0
        self.status = 0
        self.flag_counts: Counter = Counter()
        # realized device totals folded from every served group's counter
        # words (DESIGN.md §15.1) -- the serving-side eval budget ledger
        self.device_counters = _c.HostTotals()

    # ------------------------------------------------------------------ #
    # tenant lifecycle
    def add_tenant(self, name: str, x, kernel: Kernel, *,
                   capacity: Optional[int] = None, level1: str = "blocked",
                   block_size: Optional[int] = None,
                   samples_per_block: int = 16, exact_blocks: bool = False,
                   hash_opts: Optional[dict] = None, mesh=None,
                   data_axes=("data",), seed: int = 0) -> ServedTenant:
        """Register a tenant: wraps ``x`` in a ``DynamicDataset`` (so the
        caller can mutate it between ticks) and records the estimator
        configuration; device state is built lazily at first admission."""
        if name in self._tenants:
            raise ValueError(f"tenant {name!r} already registered")
        ds = DynamicDataset(x, capacity=capacity)
        opts = dict(level1=level1, block_size=block_size,
                    samples_per_block=samples_per_block,
                    exact_blocks=exact_blocks, hash_opts=hash_opts,
                    mesh=mesh, data_axes=data_axes)
        t = ServedTenant(name, ds, kernel, seed, opts)
        self._tenants[name] = t
        return t

    def dataset(self, name: str) -> DynamicDataset:
        """The tenant's mutable dataset (insert/delete/update between
        ticks; consumers re-sync through the epoch contract)."""
        return self._tenants[name].dataset

    def tenant(self, name: str) -> ServedTenant:
        """The registered :class:`ServedTenant` handle."""
        return self._tenants[name]

    def _admit(self, name: str, needed) -> None:
        """LRU-touch ``name`` (building its state if evicted) and evict
        the least-recently-used tenants beyond ``max_resident`` -- but
        never one the current tick needs."""
        t = self._tenants[name]
        was = t.resident
        t.admit()
        if not was:
            self.admissions += 1
        self._lru[name] = True
        self._lru.move_to_end(name)
        while len(self._lru) > self.max_resident:
            victim = next((c for c in self._lru if c not in needed), None)
            if victim is None:
                break
            self._lru.pop(victim)
            self._tenants[victim].release()
            self.evictions += 1

    # ------------------------------------------------------------------ #
    # request intake
    def submit(self, tenant: str, op: str, *, seed: Optional[int] = None,
               **payload) -> Request:
        """Enqueue one request; returns its :class:`Request` handle (the
        next :meth:`tick` fills ``result`` / ``status`` / ``error``).
        ``seed`` pins the request's PRNG key -- equal seeds on equal
        payloads reproduce draws bitwise; default is a running counter.
        One caveat: a MESH tenant's ``sample``/``prob_of`` requests that
        land in the same tick concatenate into one draw batch whose key
        stream folds in every co-batched request's seed, so bitwise
        reproducibility there additionally requires the same co-batch
        composition (a request served alone always reproduces)."""
        if tenant not in self._tenants:
            raise KeyError(f"unknown tenant {tenant!r}")
        if op not in REQUEST_OPS:
            raise ValueError(f"unknown op {op!r}; expected {REQUEST_OPS}")
        if op == "prob_of":
            ns = np.asarray(payload["src"]).reshape(-1).shape[0]
            nd = np.asarray(payload["dst"]).reshape(-1).shape[0]
            if ns != nd:
                raise ValueError(
                    f"prob_of src/dst widths differ ({ns} != {nd}): "
                    "q(dst | src) pairs one destination per source row")
        self._rid += 1
        r = Request(tenant=tenant, op=op, payload=dict(payload),
                    seed=int(self._rid * 7919 if seed is None else seed),
                    rid=self._rid, submitted=time.perf_counter())
        self._queue.append(r)
        return r

    def pending(self) -> int:
        """Requests waiting for the next tick."""
        return len(self._queue)

    # ------------------------------------------------------------------ #
    # the serving tick
    def tick(self) -> dict:
        """Drain the queue into padded batch groups and serve each group
        as one device program.  Returns tick stats (requests, groups,
        stale, admissions/evictions deltas, wall time)."""
        reqs, self._queue = self._queue, []
        t0 = time.perf_counter()
        adm0, ev0 = self.admissions, self.evictions
        evals0 = self.device_counters["evals"]
        stats = dict(requests=len(reqs), groups=0, served=0, failed=0,
                     stale=0, level1_passes=0)
        if not reqs:
            stats.update(admissions=0, evictions=0, tick_ms=0.0,
                         realized_evals=0)
            return stats
        with _m.span("serve.tick", requests=len(reqs)):
            self._tick(reqs, stats)
        self.served += stats["served"]
        self.failed += stats["failed"]
        self.ticks += 1
        stats.update(admissions=self.admissions - adm0,
                     evictions=self.evictions - ev0,
                     tick_ms=1e3 * (time.perf_counter() - t0),
                     realized_evals=self.device_counters["evals"] - evals0)
        if _m.enabled():
            self._record_metrics(reqs, stats)
        return stats

    def _tick(self, reqs, stats) -> None:
        """The tick's phases, each under its span: admission (``serve.
        admit``), the stale gate and group keys (``serve.group``), one
        program per group, then the per-request tally."""
        needed = {r.tenant for r in reqs}
        admit_errors: dict = {}
        with _m.span("serve.admit", tenants=len(needed)):
            for name in sorted(needed):
                try:
                    self._admit(name, needed)
                except Exception as e:  # noqa: BLE001 -- per-tenant isolation
                    admit_errors[name] = e
        groups: dict = {}
        with _m.span("serve.group", requests=len(reqs)):
            for r in reqs:
                if r.tenant in admit_errors:
                    self._fail(r, admit_errors[r.tenant])
                    continue
                t = self._tenants[r.tenant]
                try:
                    if not self._gate_stale(r, t, stats):
                        continue
                    gkey = self._group_key(r, t)
                except Exception as e:     # noqa: BLE001 -- bad payload
                    self._fail(r, e)
                    continue
                groups.setdefault(gkey, []).append(r)
        for key, grp in groups.items():
            # per-group fault isolation: one group blowing up (bad payload
            # dims, engine failure) fails ITS requests only -- the other
            # groups of the tick still serve ("never poisons a batch")
            try:
                if key[0] == "mesh":
                    self._serve_mesh_group(key, grp)
                else:
                    self._serve_flat_group(key, grp, stats)
            except Exception as e:     # noqa: BLE001 -- per-group isolation
                for r in grp:
                    if r.finished is None:
                        self._fail(r, e)
            stats["groups"] += 1
        for r in reqs:
            if r.finished is None:       # defensive: mark unserved as failed
                r.error = r.error or RuntimeError("request not served")
                r.finished = time.perf_counter()
            if r.error is None:
                stats["served"] += 1
            else:
                stats["failed"] += 1

    def _record_metrics(self, reqs, stats) -> None:
        """Per-tenant / per-op latency histograms plus tick counters into
        the obs registry (DESIGN.md §15.3); called only while the registry
        is enabled, so the disabled-mode tick cost is one branch."""
        for r in reqs:
            if r.finished is not None:
                _m.observe(f"serve.latency.{r.tenant}.{r.op}.us",
                           (r.finished - r.submitted) * 1e6)
        for k in ("served", "failed", "stale", "admissions", "evictions",
                  "realized_evals", "level1_passes"):
            _m.counter_inc(f"serve.{k}", stats[k])
        _m.observe("serve.tick.us", stats["tick_ms"] * 1e3)
        _m.gauge_set("serve.resident", float(len(self._lru)))

    # ------------------------------------------------------------------ #
    @staticmethod
    def _fail(r: Request, e: Exception) -> None:
        """Finish ``r`` with ``e`` -- the tick itself never raises."""
        r.error = e
        r.finished = time.perf_counter()

    def _frontier_rows(self, r: Request) -> Optional[np.ndarray]:
        """Dataset rows the request dereferences (None for point queries)."""
        if r.op == "sample":
            return np.asarray(r.payload["src"])
        if r.op == "walk":
            return np.asarray(r.payload["starts"])
        if r.op == "prob_of":
            return np.concatenate([np.asarray(r.payload["src"]),
                                   np.asarray(r.payload["dst"])])
        return None

    def _gate_stale(self, r: Request, t: ServedTenant, stats: dict) -> bool:
        """Per-request liveness gate (the serving twin of
        ``NeighborSampler._check_frontier``): a frontier referencing dead
        slots gets ``EPOCH_STALE`` on ITS status word only.  Under
        ``REPRO_CHECKS=1`` the request errors out and skips the batch;
        otherwise the flag is advisory and the request is still served
        (dead slots carry exactly zero kernel mass)."""
        rows = self._frontier_rows(r)
        if rows is None or bool(np.all(t.dataset.is_live(rows))):
            return True
        r.status |= _g.EPOCH_STALE
        stats["stale"] += 1
        self.status |= _g.EPOCH_STALE
        self.flag_counts["EPOCH_STALE"] += 1
        if _g.checks_enabled():
            r.error = _g.EstimationError(
                f"serve:{r.op}:{r.tenant}: status flags ['EPOCH_STALE'] "
                f"(frontier references dead slots at epoch "
                f"{int(t.dataset.epoch)})")
            r.finished = time.perf_counter()
            return False
        return True

    def _group_key(self, r: Request, t: ServedTenant):
        """The static batch-group key: requests sharing a key run as one
        padded program (tenant signature + op + shape bucket)."""
        if t.mesh is not None:
            extra = (int(r.payload["length"]),) if r.op == "walk" else ()
            return ("mesh", r.tenant, r.op) + extra
        if r.op == "query":
            qb = shape_bucket(len(np.atleast_2d(r.payload["y"])),
                              self.buckets)
            return ("flat", "query", qb, t.query_sig())
        wb = shape_bucket(len(self._frontier_rows(r)) // (2 if r.op ==
                          "prob_of" else 1), self.buckets)
        extra = (int(r.payload["length"]),) if r.op == "walk" else ()
        return ("flat", r.op, wb) + extra + (t.draw_sig(),)

    # ------------------------------------------------------------------ #
    def _arena(self, tenants):
        """Stacked device arena for a group's tenants, cached by
        ``(name, epoch)`` pairs -- the serving face of the
        ``(dataset_id, epoch)`` invalidation contract."""
        key = tuple((t.name, int(t.dataset.epoch)) for t in tenants)
        hit = self._arenas.get(key)
        if hit is not None:
            self._arenas.move_to_end(key)
            return hit
        xa = jnp.stack([t.nbr.x for t in tenants])
        xa_sq = jnp.stack([t.nbr.x_sq for t in tenants])
        hstate = None
        if tenants[0].nbr._hstate is not None:
            # one stack serves draws AND hashed queries: the sampler's
            # _hstate IS hash_estimator.state (one bucket layout per
            # tenant), so the arena entry is reused by both paths
            from repro.kernels.kde_hash.ops import stack_hash_states
            hstate = stack_hash_states([t.nbr._hstate for t in tenants])
        self._arenas[key] = (xa, xa_sq, hstate)
        while len(self._arenas) > self._arena_cap:
            self._arenas.popitem(last=False)
        return xa, xa_sq, hstate

    def _scatter(self, grp, results, statuses):
        """Slice each request's lanes out of the padded batch outputs and
        fan the per-request status words through the checks policy."""
        if _c.is_word(statuses):
            # batched (R, WIDTH) counter words, one row per request: fold
            # the realized device work into the serving ledger before the
            # status fan-out (DESIGN.md §15.1)
            self.device_counters.note(statuses)
        ctxs = [f"serve:{r.op}:{r.tenant}" for r in grp]
        words, errors = _g.raise_per_request(statuses, ctxs, allow=_BENIGN)
        now = time.perf_counter()
        for i, r in enumerate(grp):
            r.status |= words[i]
            self.status |= words[i]
            _g.count_flags(self.flag_counts, words[i])
            r.error = errors[i]
            r.result = results[i] if errors[i] is None else None
            r.finished = now

    def _serve_flat_group(self, key, grp, stats) -> None:
        """Serve one (tenant signature, op, bucket) group as ONE padded
        program over the stacked tenant arena, in four spans:
        ``serve.stage`` (arena, padded payloads, keys), ``serve.dispatch``
        (the ``batched_*`` call), ``serve.readback`` (the host blocks on
        the outputs) and ``serve.scatter``.  A draw, ``prob_of`` or dense
        query group adds the Pallas level-1 passes its program makes over
        the dataset (``ops.level1_passes``) to the tick's
        ``level1_passes`` and to its ``serve.dispatch`` span as
        ``passes``."""
        from repro.kernels.kde_sampler import ops as _ops
        op, wb = key[1], key[2]
        meta = dict(op=op, requests=len(grp))
        passes = None
        with _m.span("serve.stage", **meta):
            names = sorted({r.tenant for r in grp})
            tenants = [self._tenants[nm] for nm in names]
            tmap = {nm: i for i, nm in enumerate(names)}
            xa, xa_sq, hstate = self._arena(tenants)
            # numpy inputs go straight to the jitted batch entry points:
            # the C++ jit dispatch path stages them faster than per-array
            # device_put, and this is the per-tick hot path
            tidx = np.asarray([tmap[r.tenant] for r in grp], np.int32)
            keys = _batch_keys([r.seed for r in grp])
            cfg = tenants[0].nbr._cfg
            # ``call`` dispatches the group's program and returns (outputs
            # to read back, status words); ``lane`` cuts request i's
            # result of width w out of the host outputs
            if op == "sample":
                widths = [len(np.asarray(r.payload["src"]).reshape(-1))
                          for r in grp]
                src = np.stack([_pad_idx(r.payload["src"], wb)
                                for r in grp])

                def call():
                    nb, prob, _, st = _ops.batched_fused_sample(
                        xa, xa_sq, tidx, src, keys, hstate=hstate, **cfg)
                    return (nb, prob), st

                def lane(out, i, w):
                    return out[0][i, :w], out[1][i, :w]
                passes = _ops.level1_passes(len(tenants), len(grp), wb,
                                            **cfg)
            elif op == "walk":
                length = key[3]
                widths = [len(np.asarray(r.payload["starts"]).reshape(-1))
                          for r in grp]
                starts = np.stack([_pad_idx(r.payload["starts"], wb)
                                   for r in grp])

                def call():
                    wkeys = _split_batch(keys, length)
                    end, _, st, _ = _ops.batched_walk_scan(
                        xa, xa_sq, tidx, starts, wkeys, hstate=hstate,
                        rounds=0, slack=2.0, record_path=False, **cfg)
                    return (end,), st

                def lane(out, i, w):
                    return out[0][i, :w], None
            elif op == "prob_of":
                widths = [len(np.asarray(r.payload["src"]).reshape(-1))
                          for r in grp]
                src = np.stack([_pad_idx(r.payload["src"], wb)
                                for r in grp])
                dst = np.stack([_pad_idx(r.payload["dst"], wb)
                                for r in grp])

                def call():
                    prob, st = _ops.batched_prob_of(
                        xa, xa_sq, tidx, src, dst, keys, hstate=hstate,
                        **cfg)
                    return (prob,), st

                def lane(out, i, w):
                    return out[0][i, :w]
                passes = _ops.level1_passes(len(tenants), len(grp), wb,
                                            **cfg)
            elif op == "query":
                widths = [len(np.atleast_2d(r.payload["y"])) for r in grp]
                y = np.stack([_pad_pts(r.payload["y"], wb) for r in grp])
                if tenants[0].nbr.level1 == "hash":
                    from repro.kernels.kde_hash import ops as _hops
                    hq = tenants[0].nbr.hash_estimator

                    def call():
                        est, _, st = _hops.batched_hashed_query(
                            xa, tidx, y, hstate, keys, **hq._cfg)
                        return (est,), st
                else:
                    qkeys = ("kind", "inv_bw", "beta", "pairwise",
                             "block_size", "num_blocks", "n", "s", "exact",
                             "use_pallas", "interpret", "bm", "level1",
                             "precision")

                    def call():
                        est, st = _ops.batched_kde_query(
                            xa, xa_sq, tidx, y, keys,
                            **{k: cfg[k] for k in qkeys})
                        return (est,), st
                    # a query reads level 1 in Pallas only where it packs:
                    # a several-tenant group keeps the per-request jnp sweep
                    passes = (_ops.level1_passes(1, len(grp), wb, **cfg)
                              if len(tenants) == 1 else 0)

                def lane(out, i, w):
                    return out[0][i, :w]
            else:                                      # pragma: no cover
                raise ValueError(op)
        dmeta = meta if passes is None else dict(meta, passes=passes)
        with _m.span("serve.dispatch", **dmeta):
            out, st = call()
        stats["level1_passes"] += passes or 0
        with _m.span("serve.readback", **meta):
            out = [np.asarray(a) for a in out]
        with _m.span("serve.scatter", **meta):
            self._scatter(grp, [lane(out, i, w)
                                for i, w in enumerate(widths)], st)

    def _serve_mesh_group(self, key, grp) -> None:
        """Serve a mesh tenant's group through its sharded engine: draws
        and probability reads concatenate the group's frontiers into ONE
        draw batch (one psum -- the §9 schedule; batching adds zero extra
        collectives), walks run per request (each walk step is its own
        collective batch either way).  The group shares ONE key stream
        that folds in every request's seed (first seed -> ``PRNGKey``,
        the rest ``fold_in`` in queue order): distribution-identical,
        deterministic in all submitted seeds, and bitwise-reproducible
        given equal seeds AND equal co-batch composition (documented on
        :meth:`KernelGraphServable.submit`)."""
        _, name, op = key[0], key[1], key[2]
        t = self._tenants[name]
        nbr = t.nbr
        engine = nbr._engine
        meta = dict(op=op, requests=len(grp))
        if op == "walk":
            length = key[3]
            res, words = [], []
            for r in grp:
                with _m.span("serve.stage", **meta):
                    starts = jnp.asarray(np.asarray(r.payload["starts"]),
                                         jnp.int32)
                    wkeys = jax.random.split(jax.random.PRNGKey(r.seed),
                                             length)
                with _m.span("serve.dispatch", **meta):
                    end, _, st, _ = engine.walk_scan(
                        starts, wkeys, rounds=0, slack=2.0,
                        record_path=False)
                with _m.span("serve.readback", **meta):
                    res.append((np.asarray(end), None))
                    words.append(np.asarray(st, np.uint32))
            with _m.span("serve.scatter", **meta):
                self._scatter(grp, res, np.asarray(words))
            return
        if op == "query":
            with _m.span("serve.stage", **meta):
                widths = [len(np.atleast_2d(r.payload["y"])) for r in grp]
                y = jnp.asarray(np.concatenate(
                    [np.atleast_2d(np.asarray(r.payload["y"], np.float32))
                     for r in grp]))
            with _m.span("serve.dispatch", **meta):
                est = nbr.blocks.query(y)
            with _m.span("serve.readback", **meta):
                est = np.asarray(est)
            with _m.span("serve.scatter", **meta):
                offs = np.cumsum([0] + widths)
                res = [est[offs[i]:offs[i + 1]] for i in range(len(grp))]
                st = np.full(len(grp), np.uint32(
                    getattr(nbr.blocks, "last_status", 0)), np.uint32)
                self._scatter(grp, res, st)
            return
        with _m.span("serve.stage", **meta):
            key0 = jax.random.PRNGKey(grp[0].seed)
            for r in grp[1:]:
                key0 = jax.random.fold_in(key0, r.seed)
            widths = [len(np.asarray(r.payload["src"]).reshape(-1))
                      for r in grp]
            src = jnp.asarray(np.concatenate(
                [np.asarray(r.payload["src"]).reshape(-1) for r in grp]),
                jnp.int32)
            offs = np.cumsum([0] + widths)
            if op == "prob_of":
                dst = jnp.asarray(np.concatenate(
                    [np.asarray(r.payload["dst"]).reshape(-1)
                     for r in grp]), jnp.int32)
        with _m.span("serve.dispatch", **meta):
            if op == "sample":
                nb, prob, _, cw = engine.fused_sample(src, key0)
                out = (nb, prob)
            else:                                      # prob_of
                bs, cw = engine.masked_block_sums(src, key0)
                prob_dev, cw2 = engine.prob_of_from_block_sums(src, dst, bs)
                # fold the level-1 read word into the prob-of word and flag
                # the read itself -- NONFINITE_RESULT on NaN/Inf
                cw = _c.fold_status(_c.fold(cw, cw2),
                                    _g.result_status(prob_dev))
                out = (prob_dev,)
        with _m.span("serve.readback", **meta):
            out = [np.asarray(a) for a in out]
        with _m.span("serve.scatter", **meta):
            if op == "sample":
                res = [(out[0][offs[i]:offs[i + 1]],
                        out[1][offs[i]:offs[i + 1]])
                       for i in range(len(grp))]
            else:
                res = [out[0][offs[i]:offs[i + 1]] for i in range(len(grp))]
            # ONE counter word covers the whole concatenated draw batch:
            # note it once (replicating it per request would multiply-count
            # the realized work) and fan only its status bits out to the
            # group
            st = self.device_counters.note(cw)
            self._scatter(grp, res,
                          np.full(len(grp), np.uint32(st), np.uint32))

    # ------------------------------------------------------------------ #
    def report(self) -> dict:
        """Lifetime counters + or-folded flags for ops dashboards."""
        return dict(ticks=self.ticks, served=self.served,
                    failed=self.failed, admissions=self.admissions,
                    evictions=self.evictions,
                    resident=[n for n in self._lru],
                    tenants=len(self._tenants),
                    flags=_g.decode_status(self.status),
                    flag_counts=dict(self.flag_counts),
                    device_counters=self.device_counters.as_dict())
