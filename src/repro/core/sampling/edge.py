"""Weighted neighbor / edge sampling -- Algorithms 4.11 and 4.13.

Given a vertex u, sample a neighbor v with Pr[v] ~= k(u, v) / deg(u)
(Definition 4.10) using segment KDE estimates only.

Two interchangeable factorizations of the same telescoping product
(Theorem 4.12):

* ``mode="tree"``   -- the paper's dyadic descent: at every internal node,
  query the two child-segment KDE structures and branch proportionally;
  O(log n) KDE queries per sample, error (1 +- eps')^depth.
* ``mode="blocked"``-- TPU-adapted depth-2 tree (DESIGN.md §2), executed by
  the fused device engine (``repro.kernels.kde_sampler``): level-1 masked
  block sums + Gumbel-max block draw + exact level-2 row + in-block draw
  are ONE compiled program keyed on a ``jax.random.PRNGKey``.  No per-call
  Python loops over blocks, one host->device transfer per batch (the
  frontier indices), one device->host transfer for the results.

Both modes vectorize over a batch of source vertices (random-walk frontier).
``sample`` returns the *realized* sampling probability of each drawn
neighbor, and ``prob_of`` evaluates the probability the sampler would assign
to an arbitrary (u, v) -- both are required by the sparsifier (Alg 5.1 steps
(c)-(d)).

Level-1 caching contract (DESIGN.md §4): the masked block sums of the most
recent frontier are kept on device; ``sample`` / ``prob_of`` /
``sample_exact`` on the *same* frontier reuse them instead of re-sweeping
the dataset, which makes ``prob_of`` exactly consistent with the estimates
``sample`` realized and collapses the rejection rounds of Theorem 4.12 to
one level-1 read.

Theorem 4.12's exactness step (O(1/tau) rejection rounds) is implemented in
``sample_exact`` as a fixed-round vectorized accept/reject program.

Every fused program also returns a ``repro.ft.guards`` status bitmask; the
sampler or-folds them into ``self.status`` / ``self.flag_counts`` and, under
``REPRO_CHECKS=1``, raises ``EstimationError`` on fatal flags.  Rejection
fallbacks (Theorem 4.12's all-rounds-reject event) are counted in
``exact_fallbacks`` and compared against the (1 - 1/c)^rounds prediction.
"""
from __future__ import annotations

from collections import Counter
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.kde.base import ExactBlockKDE, StratifiedKDE
from repro.core.kde.multilevel import MultiLevelKDE
from repro.core.kernels_fn import Kernel
from repro.ft import guards as _g
from repro.kernels import platform as _platform
from repro.obs import counters as _c

# Flags a healthy pipeline may legitimately raise: truncated buckets and
# heavy HT samples are accuracy (not validity) signals, and rejection
# exhaustion has a documented fallback (Theorem 4.12).
_BENIGN = _g.BUCKET_OVERFLOW | _g.HT_HEAVY | _g.REJECT_EXHAUSTED


class NeighborSampler:
    """Algorithm 4.11 / Theorem 4.12: sample v ~ k(u, v)/deg(u) given u.

    ``mode="blocked"`` is the fused depth-2 device engine (DESIGN.md §2-§4,
    one compiled program per batch, one level-1 read per frontier);
    ``mode="tree"`` is the paper's literal dyadic descent over a
    ``MultiLevelKDE``.  Cost per blocked sample: one level-1 read (w*B*s
    stratified / w*n exact kernel evals for a w-frontier) plus w exact
    level-2 rows of ``block_size`` columns.

    With ``mesh=`` (blocked mode only) the level-1 block structure lives
    sharded over the mesh's ``data_axes`` and every draw is the two-stage
    collective program of DESIGN.md §9 (one psum per draw batch) --
    distribution-identical to the flat draw, same §4 caching contract,
    same eval counters.

    With ``level1="hash"`` the level-1 block masses are estimated by the
    ``kde_hash`` padded-bucket estimator (exact NEAR members + HT FAR
    samples scattered into their blocks, O(max_bucket + num_far) evals
    per frontier row, DESIGN.md §10); the block draw, the exact level-2
    read and the Theorem 4.12 rejection-exact mode are unchanged, so the
    §2 sampling contract and the §4 cache carry over verbatim.

    >>> nbr = NeighborSampler(x, gaussian(1.0), mode="blocked")
    >>> v, q = nbr.sample(np.array([0, 1, 2]))
    """

    def __init__(self, x: jnp.ndarray, kernel: Kernel, mode: str = "blocked",
                 block_size: Optional[int] = None, samples_per_block: int = 16,
                 exact_blocks: bool = False, tree: Optional[MultiLevelKDE] = None,
                 seed: int = 0, use_pallas: Optional[bool] = None,
                 interpret: Optional[bool] = None, mesh=None,
                 data_axes=("data",), level1: str = "blocked",
                 hash_opts: Optional[dict] = None, dataset=None,
                 precision: str = "f32"):
        from repro.kernels.kde_sampler import ops as _ops
        self._ops = _ops
        # streaming attach (DESIGN.md §12): engines build over the padded
        # capacity; every public entry epoch-checks and patches/rebuilds
        self._dataset = dataset
        self._ds_epoch = int(dataset.epoch) if dataset is not None else 0
        if dataset is not None:
            if mode != "blocked":
                raise ValueError("dataset= needs the blocked engine")
            x = dataset.x_pad
        self.x = jnp.asarray(x, jnp.float32)
        self.kernel = kernel
        self.n = int(x.shape[0])
        self.mode = mode
        self.level1 = level1
        # level-1 sweep dtype policy (DESIGN.md §14); validated against the
        # kernel kind up front so bad configs fail at construction.
        self.precision = precision
        if precision != "f32":
            from repro.kernels.kde_sampler.ref import (check_precision,
                                                       static_pairwise)
            check_precision(precision, kernel.name, static_pairwise(kernel))
            if mesh is not None:
                raise ValueError(
                    "precision='bf16' is single-device for now: the "
                    "sharded one-psum schedule is pinned f32 (its jaxpr "
                    "is contract-asserted; see DESIGN.md §14)")
        self._rng = np.random.default_rng(seed)
        self._key = jax.random.PRNGKey(seed)
        # or-fold of every program's status word + per-flag event counts
        # (DESIGN.md §11); rejection-fallback accounting for Theorem 4.12.
        self.status = 0
        self.flag_counts: Counter = Counter()
        # realized device totals (DESIGN.md §15.1): every fused program's
        # counter word folds in through ``_note``; ``device_counters
        # ["evals"]`` reconciles against the analytic ``.evals`` on the
        # flat blocked/exact pipelines (asserted in tests)
        self.device_counters = _c.HostTotals()
        self.exact_draws = 0
        self.exact_fallbacks = 0
        self._engine = None
        self._hash = None
        self._hstate = None
        if level1 not in ("blocked", "hash"):
            raise ValueError(f"unknown level1 {level1!r}")
        if level1 == "hash" and exact_blocks:
            raise ValueError("level1='hash' replaces the level-1 read with "
                             "hashed estimates; exact_blocks=True (the "
                             "reproducible exact read) cannot be honored "
                             "-- pick one")
        if mesh is not None:
            assert mode == "blocked", "mesh= needs the blocked engine"
            if level1 == "hash":
                raise ValueError("level1='hash' is single-device for now; "
                                 "the sharded hash table covers queries "
                                 "(kde_hash.sharded), not draws")
        if mode == "blocked":
            bs = block_size or max(int(np.sqrt(self.n)), 16)
            # kept for the streaming rebuild path (journal gap / capacity
            # growth re-runs this construction over the new padded array)
            self._mesh0 = mesh
            self._axes0 = data_axes
            self._spb0 = samples_per_block
            self._seed0 = seed
            if mesh is not None:
                # Mesh construction path (DESIGN.md §9): the level-1 block
                # structure lives sharded inside a ShardedKDE; draws are
                # two-stage collective programs.  The §4 caching contract
                # and every eval-counter formula below are unchanged.
                from repro.core.kde.distributed import ShardedKDE
                self._blocks = ShardedKDE(
                    mesh, self.x, kernel, block_size=bs,
                    samples_per_block=samples_per_block, exact=exact_blocks,
                    data_axes=data_axes, seed=seed)
                self._engine = self._blocks.engine
            elif exact_blocks:
                self._blocks = ExactBlockKDE(self.x, kernel, block_size=bs,
                                             precision=precision)
            else:
                self._blocks = StratifiedKDE(self.x, kernel, block_size=bs,
                                             samples_per_block=samples_per_block,
                                             seed=seed, precision=precision)
            # ONE device dataset + one precomputed-norms sweep, shared with
            # the block KDE structure (and, through ``blocks``, with any
            # degree sampler built on top of it -- DESIGN.md §6).
            self.x = self._blocks.x
            self.x_sq = self._blocks.x_sq
            self.block_size = self._blocks.block_size
            self.num_blocks = self._blocks.num_blocks
            self.exact_blocks = exact_blocks
            if self._engine is not None:
                # the mesh engine's shard-local reads are jnp programs
                use_pallas, interpret = False, False
            else:
                use_pallas, interpret = _platform.resolve(
                    use_pallas, interpret, kernel.name)
            self._far_per_block = 1
            if level1 == "hash":
                # Hashed level-1 (DESIGN.md §10): block masses estimated
                # from the kde_hash padded-bucket layout (exact NEAR
                # scatter + ``far_per_block`` stratified FAR slots per
                # block) at O(max_bucket + B far_per_block) evals per
                # frontier row; level-2 stays the exact in-block read, so
                # the §2 contract and every consumer of cached block sums
                # are unchanged.
                from repro.core.kde.hashed import HashedKDE
                hopts = dict(hash_opts or {})
                # Defaults tuned so the full degrees->sparsify pipeline at
                # n=16k spends ~20% of the stratified eval budget while
                # keeping spectral error within 1.25x (BENCH_kde.json).
                self._far_per_block = int(hopts.pop("far_per_block", 2))
                hopts.setdefault("max_bucket", 128)
                self._hash = HashedKDE(self.x, kernel,
                                       seed=seed + 7919,
                                       use_pallas=bool(use_pallas),
                                       interpret=bool(interpret),
                                       dataset=dataset,
                                       precision=precision,
                                       **hopts)
                self._hstate = self._hash.state
            from repro.kernels.kde_sampler.ref import static_pairwise
            # Static engine configuration shared by every jitted entry point.
            self._cfg = dict(
                kind=kernel.name, inv_bw=1.0 / kernel.bandwidth,
                beta=getattr(kernel, "beta", 1.0),
                pairwise=static_pairwise(kernel),
                block_size=self.block_size, num_blocks=self.num_blocks,
                n=self.n, s=self._blocks.samples_per_block,
                exact=exact_blocks, use_pallas=bool(use_pallas),
                interpret=bool(interpret),
                bm=32 if level1 == "hash" else 128,
                level1=level1, num_far=self._far_per_block,
                precision=precision)
            self._l2_cfg = {k: self._cfg[k] for k in
                            ("kind", "inv_bw", "beta", "pairwise",
                             "block_size", "n")}
            # (digest, block sums, frontier indices) -- the indices let the
            # streaming sync decide patch-vs-drop when the dataset mutates
            self._l1_cache: Optional[
                Tuple[bytes, jnp.ndarray, np.ndarray]] = None
        elif mode == "tree":
            assert tree is not None, "tree mode needs a MultiLevelKDE"
            self.x_sq = jnp.sum(self.x * self.x, axis=-1)
            self._tree = tree
        else:
            raise ValueError(mode)

    # ------------------------------------------------------------------ #
    @property
    def blocks(self):
        """The level-1 KDE structure (blocked mode) -- exposed so consumers
        (the sparsifier's degree preprocessing) can share it instead of
        building a second structure over the same dataset."""
        assert self.mode == "blocked"
        return self._blocks

    @property
    def evals(self) -> int:
        """Total kernel evaluations across the level-1 structure and every
        sampling call -- the paper's Section 7 cost metric."""
        if self.mode == "blocked":
            return self._blocks.evals + getattr(self, "_extra_evals", 0)
        return self._tree.evals + getattr(self, "_extra_evals", 0)

    def _count(self, k: int):
        self._extra_evals = getattr(self, "_extra_evals", 0) + k

    def _next_key(self) -> jnp.ndarray:
        self._key, k = jax.random.split(self._key)
        return k

    def _note(self, st, context: str) -> int:
        """Fold one program's counter word (or a legacy scalar status)
        into the counters, then apply the ``REPRO_CHECKS`` policy (fatal
        flags raise, benign ones pass)."""
        if _c.is_word(st):
            s = self.device_counters.note(jax.device_get(st))
        else:
            s = int(np.uint32(jax.device_get(st)))
        self.status |= s
        _g.count_flags(self.flag_counts, s)
        _g.raise_on_status(s, context=context, allow=_BENIGN)
        return s

    # ------------------------------------------------------------------ #
    # streaming contract (DESIGN.md §12)
    def _rebuild(self) -> None:
        """Full level-1 rebuild over the dataset's current padded array --
        the journal-gap / capacity-growth path of the streaming contract.
        Block size is kept; the block count follows the new capacity."""
        ds = self._dataset
        self.x = jnp.asarray(ds.x_pad, jnp.float32)
        self.n = int(self.x.shape[0])
        bs = self.block_size
        if self._engine is not None:
            from repro.core.kde.distributed import ShardedKDE
            self._blocks = ShardedKDE(
                self._mesh0, self.x, self.kernel, block_size=bs,
                samples_per_block=self._spb0, exact=self.exact_blocks,
                data_axes=self._axes0, seed=self._seed0)
            self._engine = self._blocks.engine
        elif self.exact_blocks:
            self._blocks = ExactBlockKDE(self.x, self.kernel, block_size=bs)
        else:
            self._blocks = StratifiedKDE(
                self.x, self.kernel, block_size=bs,
                samples_per_block=self._spb0, seed=self._seed0)
        self.x = self._blocks.x
        self.x_sq = self._blocks.x_sq
        self.num_blocks = self._blocks.num_blocks
        self._cfg.update(n=self.n, num_blocks=self.num_blocks)
        self._l2_cfg["n"] = self.n
        self._l1_cache = None

    def _sync(self) -> None:
        """Epoch check at every public entry: refresh the dataset views,
        patch the cached level-1 read by the coalesced mutation delta
        (O(w m) evals; dropped instead when a cached frontier row itself
        mutated), patch the sharded engine's device copies (zero
        collectives), and let a hashed level-1 run its own patch-or-
        rebuild.  A journal gap falls back to ``_rebuild``."""
        ds = self._dataset
        if ds is None or self._ds_epoch == int(ds.epoch):
            return
        from repro.core.dataset import coalesce_mutations
        batches = ds.mutations_since(self._ds_epoch)
        if batches is None:
            self._rebuild()
            if self._hash is not None:
                self._hash._sync()
                self._hstate = self._hash.state
            self._ds_epoch = int(ds.epoch)
            return
        slots, old_x, new_x, _, _ = coalesce_mutations(batches)
        if self._engine is not None:
            # mesh path: one zero-collective scatter program patches the
            # sharded + replicated dataset copies; the cached level-1 sums
            # live in flat layout only, so the sharded cache is dropped
            self._blocks.patch_rows(jnp.asarray(slots),
                                    jnp.asarray(new_x, jnp.float32))
            self.x = self._blocks.x
            self.x_sq = self._blocks.x_sq
            self._l1_cache = None
        else:
            # jnp arrays rebind on mutation -- refresh every shared view
            self.x = ds.x_pad
            self.x_sq = ds.x_sq_pad
            self._blocks.x = self.x
            self._blocks.x_sq = self.x_sq
            if self._l1_cache is not None:
                dig, bs, src32 = self._l1_cache
                if np.intersect1d(src32,
                                  np.asarray(slots, np.int64)).size:
                    self._l1_cache = None   # frontier row itself mutated
                else:
                    bs, cw = self._ops.patch_block_sums(
                        bs, self.x, jnp.asarray(src32),
                        jnp.asarray(slots), jnp.asarray(old_x, jnp.float32),
                        jnp.asarray(new_x, jnp.float32),
                        kind=self._cfg["kind"], inv_bw=self._cfg["inv_bw"],
                        beta=self._cfg["beta"],
                        pairwise=self._cfg["pairwise"],
                        block_size=self.block_size)
                    self._count(2 * len(src32) * len(slots))
                    self._note(cw, "NeighborSampler.sync")
                    self._l1_cache = (dig, bs, src32)
        if self._hash is not None:
            self._hash._sync()
            self._hstate = self._hash.state
        self._ds_epoch = int(ds.epoch)

    def _check_frontier(self, src32: np.ndarray, context: str) -> None:
        """Liveness gate for caller-supplied frontiers: referencing a
        deleted slot folds ``EPOCH_STALE`` into the status word (an
        ``EstimationError`` under ``REPRO_CHECKS=1`` -- the flag is not in
        ``_BENIGN``)."""
        ds = self._dataset
        if ds is None:
            return
        if not bool(np.all(ds.is_live(np.asarray(src32)))):
            self._note(_g.EPOCH_STALE, context)

    @property
    def hash_estimator(self):
        """The shared hashed-KDE estimator behind ``level1="hash"`` --
        exposed so consumers (Algorithm 4.3 degree preprocessing) reuse
        the one bucket layout instead of hashing the dataset twice."""
        assert self._hash is not None, "level1='hash' sampler required"
        return self._hash

    # ------------------------------------------------------------------ #
    # blocked mode: fused device engine
    def _level1_evals(self, w: int) -> int:
        if self.level1 == "hash":
            # the frontier gather sweeps the realized bucket-member width,
            # the streaming overflow region (previously omitted -- the
            # host counter drifted below the device word on streaming
            # hash pipelines), and far_per_block FAR slots per block --
            # the same static shapes the device counter word is built from
            mb = (int(self._hstate.members.shape[1])
                  if self._hstate is not None else self._hash.max_bucket)
            ov = (int(self._hstate.overflow.shape[0])
                  if self._hstate is not None
                  and self._hstate.overflow is not None else 0)
            return w * (mb + ov + self.num_blocks * self._cfg["num_far"])
        if self.exact_blocks:
            return w * self.n
        return w * self.num_blocks * self._cfg["s"]

    @staticmethod
    def _digest(src32: np.ndarray) -> bytes:
        """Cache key for a frontier: dtype-normalized indices + length (raw
        tobytes of caller-supplied arrays would collide across dtypes)."""
        return src32.shape[0].to_bytes(8, "little") + src32.tobytes()

    def _level1(self, src32: np.ndarray, src_dev: jnp.ndarray) -> jnp.ndarray:
        """Masked level-1 block sums for a frontier, cached per frontier."""
        dig = self._digest(src32)
        if self._l1_cache is not None and self._l1_cache[0] == dig:
            return self._l1_cache[1]
        if self._engine is not None:
            bs, cw = self._engine.masked_block_sums(src_dev,
                                                    self._next_key())
        else:
            bs, cw = self._ops.masked_block_sums(self.x, self.x_sq, src_dev,
                                                 self._next_key(),
                                                 hstate=self._hstate,
                                                 **self._cfg)
        self._count(self._level1_evals(len(src32)))
        self._note(cw, "NeighborSampler.level1")
        self._l1_cache = (dig, bs, src32)
        return bs

    def sample(self, src: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """Sample one neighbor per source.  Returns (neighbors, probs)."""
        src = np.asarray(src)
        if self.mode == "tree":
            return self._sample_tree(src)
        self._sync()
        self._check_frontier(src, "NeighborSampler.sample")
        src32 = np.ascontiguousarray(src, np.int32)
        src_dev = jnp.asarray(src32)
        dig = self._digest(src32)
        if self._l1_cache is not None and self._l1_cache[0] == dig:
            if self._engine is not None:
                nb, prob, st = self._engine.sample_from_block_sums(
                    src_dev, self._l1_cache[1], self._next_key())
            else:
                nb, prob, st = self._ops.sample_from_block_sums(
                    self.x, self.x_sq, src_dev, self._l1_cache[1],
                    self._next_key(), **self._l2_cfg)
        else:
            if self._engine is not None:
                nb, prob, bs, st = self._engine.fused_sample(
                    src_dev, self._next_key())
            else:
                nb, prob, bs, st = self._ops.fused_sample(
                    self.x, self.x_sq, src_dev, self._next_key(),
                    hstate=self._hstate, **self._cfg)
            self._count(self._level1_evals(len(src)))
            self._l1_cache = (dig, bs, src32)
        self._count(len(src) * self.block_size)
        self._note(st, "NeighborSampler.sample")
        return np.asarray(nb), np.asarray(prob)

    def prob_of(self, src: np.ndarray, dst: np.ndarray) -> np.ndarray:
        """Probability the sampler assigns to edge (src -> dst)."""
        src, dst = np.asarray(src), np.asarray(dst)
        if self.mode == "tree":
            return self._prob_of_tree(src, dst)
        self._sync()
        self._check_frontier(np.concatenate([src, dst]),
                             "NeighborSampler.prob_of")
        src32 = np.ascontiguousarray(src, np.int32)
        src_dev = jnp.asarray(src32)
        bs = self._level1(src32, src_dev)
        if self._engine is not None:
            out, cw = self._engine.prob_of_from_block_sums(
                src_dev, jnp.asarray(dst, jnp.int32), bs)
        else:
            out, cw = self._ops.prob_of_from_block_sums(
                self.x, self.x_sq, src_dev, jnp.asarray(dst, jnp.int32), bs,
                **self._l2_cfg)
        self._count(len(src) * self.block_size)
        self._note(cw, "NeighborSampler.prob_of")
        return np.asarray(out)

    # ------------------------------------------------------------------ #
    # tree mode (faithful Algorithm 4.11)
    def _sample_tree(self, src: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        out = np.zeros(len(src), np.int64)
        probs = np.ones(len(src), np.float64)
        for i, s in enumerate(src):
            lo, hi = 0, self._tree.n
            p = 1.0
            q = self.x[int(s)][None, :]
            while not self._tree.is_leaf(lo, hi):
                (l0, l1), (r0, r1) = self._tree.children(lo, hi)
                a = float(self._tree.segment_query(q, l0, l1)[0])
                b = float(self._tree.segment_query(q, r0, r1)[0])
                if l0 <= s < l1:
                    a = max(a - 1.0, 1e-12)
                if r0 <= s < r1:
                    b = max(b - 1.0, 1e-12)
                pa = a / max(a + b, 1e-30)
                if self._rng.uniform() <= pa:
                    lo, hi, p = l0, l1, p * pa
                else:
                    lo, hi, p = r0, r1, p * (1.0 - pa)
            kv = np.array(self.kernel.pairwise(q, self.x[lo:hi]))[0]
            self._count(hi - lo)
            idx = np.arange(lo, hi)
            kv[idx == s] = 0.0
            pin = kv / max(kv.sum(), 1e-30)
            j = self._rng.choice(len(pin), p=pin / pin.sum())
            out[i] = lo + j
            probs[i] = p * pin[j]
        return out, probs

    def _prob_of_tree(self, src: np.ndarray, dst: np.ndarray) -> np.ndarray:
        out = np.zeros(len(src), np.float64)
        for i, (s, t) in enumerate(zip(src, dst)):
            lo, hi = 0, self._tree.n
            p = 1.0
            q = self.x[int(s)][None, :]
            while not self._tree.is_leaf(lo, hi):
                (l0, l1), (r0, r1) = self._tree.children(lo, hi)
                a = float(self._tree.segment_query(q, l0, l1)[0])
                b = float(self._tree.segment_query(q, r0, r1)[0])
                if l0 <= s < l1:
                    a = max(a - 1.0, 1e-12)
                if r0 <= s < r1:
                    b = max(b - 1.0, 1e-12)
                pa = a / max(a + b, 1e-30)
                if l0 <= t < l1:
                    lo, hi, p = l0, l1, p * pa
                else:
                    lo, hi, p = r0, r1, p * (1.0 - pa)
            kv = np.array(self.kernel.pairwise(q, self.x[lo:hi]))[0]
            self._count(hi - lo)
            idx = np.arange(lo, hi)
            kv[idx == s] = 0.0
            out[i] = p * kv[t - lo] / max(kv.sum(), 1e-30)
        return out

    # ------------------------------------------------------------------ #
    def sample_exact(self, src: np.ndarray, rounds: int = 8,
                     slack: float = 2.0) -> np.ndarray:
        """Theorem 4.12 exactness: rejection-sample against exact weights.

        Proposal = this sampler; target ~ k(u, v).  Accept v with probability
        k(u,v) / (c * q(v) * Z_hat) where Z_hat estimates deg(u) and c covers
        the estimator distortion.  Vectorized fixed-round accept/reject; falls
        back to the last proposal if all rounds reject (prob (1-1/c)^rounds).

        The level-1 read happens ONCE; all proposal rounds and the degree
        estimate Z_hat share it (blocked mode).  The k(u, v) accept weights
        are evaluated as w aligned pairs, not a (w, w) matrix diagonal.
        """
        src = np.asarray(src)
        if self.mode == "tree":
            return self._sample_exact_host(src, rounds, slack)
        self._sync()
        self._check_frontier(src, "NeighborSampler.sample_exact")
        src32 = np.ascontiguousarray(src, np.int32)
        src_dev = jnp.asarray(src32)
        bs = self._level1(src32, src_dev)
        if self._engine is not None:
            cur, st, fb = self._engine.sample_exact(
                src_dev, bs, self._next_key(), rounds=rounds, slack=slack)
        else:
            cur, st, fb = self._ops.fused_sample_exact(
                self.x, self.x_sq, src_dev, bs, self._next_key(),
                rounds=rounds, slack=slack, **self._l2_cfg)
        self._count((rounds + 1) * len(src) * self.block_size
                    + rounds * len(src))
        self._note(st, "NeighborSampler.sample_exact")
        self.exact_draws += len(src)
        self.exact_fallbacks += int(jax.device_get(fb))
        _g.warn_fallback_rate(self.exact_fallbacks, self.exact_draws,
                              rounds, slack,
                              context="NeighborSampler.sample_exact")
        return np.asarray(cur)

    def _sample_exact_host(self, src: np.ndarray, rounds: int,
                           slack: float) -> np.ndarray:
        cur, _ = self.sample(src)
        zs = np.maximum(np.asarray(
            self._tree.segment_query(self.x[jnp.asarray(src)], 0,
                                     self._tree.n)) - 1.0, 1e-12)
        accepted = np.zeros(len(src), bool)
        for _ in range(rounds):
            cand, q = self.sample(src)
            kuv = np.asarray(self.kernel.pairs(self.x[jnp.asarray(src)],
                                               self.x[jnp.asarray(cand)]))
            self._count(len(src))
            ratio = kuv / np.maximum(slack * q * zs, 1e-30)
            acc = (~accepted) & (self._rng.uniform(size=len(src))
                                 < np.minimum(ratio, 1.0))
            cur = np.where(acc, cand, cur)
            accepted |= acc
        return cur

    # ------------------------------------------------------------------ #
    def edge_batches(self, cdf_device: jnp.ndarray, degs_device: jnp.ndarray,
                     total_degree: float, t: int, batch: int = 1024,
                     key: Optional[jnp.ndarray] = None):
        """Algorithm 5.1 edge sampling, fully fused (blocked mode): draws
        ``ceil(t / batch)`` iid edge batches in ONE ``lax.scan`` device
        program -- u ~ degrees via the device prefix CDF, v | u via the
        depth-2 engine, the (algebraically collapsed) reverse probability
        q_vu = k(u,v)/deg(v), and the importance weight ``k(u,v) / (t q_e)``
        -- and returns the first t edges as (u, v, weight, q_uv, q_vu)
        numpy arrays.

        ``cdf_device`` / ``degs_device`` come from a ``PrefixCDF``
        (float64-accumulated, rounded to f32); extra draws from the final
        partial batch are discarded, which leaves the estimator unbiased
        (edges are iid)."""
        assert self.mode == "blocked", "fused edge batches need blocked mode"
        self._sync()
        t = int(t)
        num_batches = max((t + batch - 1) // batch, 1)
        keys = jax.random.split(self._next_key() if key is None else key,
                                num_batches)
        if self._engine is not None:
            out = self._engine.edge_batch_scan(
                jnp.asarray(cdf_device), jnp.asarray(degs_device),
                1.0 / float(total_degree), 1.0 / t, keys, batch=int(batch))
        else:
            out = self._ops.edge_batch_scan(
                self.x, self.x_sq, jnp.asarray(cdf_device),
                jnp.asarray(degs_device), 1.0 / float(total_degree), 1.0 / t,
                keys, hstate=self._hstate, batch=int(batch), **self._cfg)
        drawn = num_batches * batch
        # per edge: one level-1 read of the u frontier, one exact level-2
        # row, and one aligned k(u, v) pair (the reverse probability
        # reuses the pair and the preprocessed degrees -- no extra reads).
        self._count(self._level1_evals(drawn)
                    + drawn * self.block_size + drawn)
        self._l1_cache = None  # frontier moved; cached sums are stale
        *data, st = out
        self._note(st, "NeighborSampler.edge_batches")
        return tuple(np.asarray(a).reshape(-1)[:t] for a in data)

    # ------------------------------------------------------------------ #
    def triangle_batches(self, u: np.ndarray, v: np.ndarray,
                         degs_device: jnp.ndarray, num_draws: int,
                         key: Optional[jnp.ndarray] = None):
        """Theorem 6.17's inner loop, fully fused (blocked mode): orient
        the (u, v) vertex pairs by the degree-then-index order, read the
        oriented v frontier's level-1 sums ONCE, draw ``num_draws``
        neighbors w ~ k(v, .)/deg(v) under ``lax.scan``, and reweight --
        one program, one device->host transfer of (u', v', W_e).

        Cost: one level-1 read of the m-edge frontier plus, per draw, m
        exact level-2 rows and m aligned k(u, w) pairs -- ``m*(B*s + 1) +
        num_draws*m*(bs + 1)`` kernel evals for stratified reads
        (``m*(n + 1) + ...`` exact)."""
        assert self.mode == "blocked", "fused triangle batches need blocked mode"
        self._sync()
        self._check_frontier(np.concatenate([np.asarray(u), np.asarray(v)]),
                             "NeighborSampler.triangle_batches")
        m = len(np.asarray(u))
        keys = jax.random.split(self._next_key() if key is None else key,
                                int(num_draws) + 1)
        if self._engine is not None:
            uu, vv, w_hat, st = self._engine.triangle_edge_scan(
                jnp.asarray(u, jnp.int32), jnp.asarray(v, jnp.int32),
                jnp.asarray(degs_device), keys)
        else:
            uu, vv, w_hat, st = self._ops.triangle_edge_scan(
                self.x, self.x_sq, jnp.asarray(u, jnp.int32),
                jnp.asarray(v, jnp.int32), jnp.asarray(degs_device), keys,
                hstate=self._hstate, **self._cfg)
        self._count(self._level1_evals(m) + m
                    + int(num_draws) * (m * self.block_size + m))
        self._l1_cache = None  # frontier moved; cached sums are stale
        self._note(st, "NeighborSampler.triangle_batches")
        return np.asarray(uu), np.asarray(vv), np.asarray(w_hat)

    # ------------------------------------------------------------------ #
    def walk(self, starts: np.ndarray, length: int, exact: bool = False,
             rounds: int = 8, slack: float = 2.0,
             key: Optional[jnp.ndarray] = None, record_path: bool = False):
        """Run |starts| walks of ``length`` steps entirely on device
        (blocked mode): the frontier is ``lax.scan`` carry and every step is
        one fused depth-2 sample.  Returns (endpoints, (length, w) path) as
        numpy arrays; with ``record_path=False`` (default) the path is
        never stacked on device and None is returned in its place --
        endpoints are bitwise identical either way (same key stream)."""
        assert self.mode == "blocked", "device walks need blocked mode"
        self._sync()
        self._check_frontier(np.asarray(starts), "NeighborSampler.walk")
        starts_dev = jnp.asarray(starts, jnp.int32)
        keys = jax.random.split(self._next_key() if key is None else key,
                                length)
        if self._engine is not None:
            end, path, st, fb = self._engine.walk_scan(
                starts_dev, keys, rounds=rounds if exact else 0,
                slack=slack, record_path=bool(record_path))
        else:
            end, path, st, fb = self._ops.walk_scan(
                self.x, self.x_sq, starts_dev, keys,
                hstate=self._hstate, rounds=rounds if exact else 0,
                slack=slack, record_path=bool(record_path), **self._cfg)
        w = len(np.asarray(starts))
        # the walk-resident level-1 cache (kernels.tuning) caps the per-step
        # level-1 read at B * s_eff cached columns on the jnp blocked path;
        # mirror walk_scan's gate so the eval counter reports true cost
        if (self.level1 == "blocked" and not self.exact_blocks
                and not self._cfg["use_pallas"] and self._engine is None):
            wbs, w_blocks, s_eff = self._ops.walk_layout(
                self.n, self.block_size, self.num_blocks, self._cfg["s"])
            per_step = w * w_blocks * s_eff + w * wbs
            if exact:
                # rejection rounds run on the walk-resident layout too:
                # level-2 rows are wbs wide, not block_size (the old
                # block_size term drifted above the device word whenever
                # tuning picked a different walk block size)
                per_step += rounds * (w * wbs + w)
        else:
            per_step = self._level1_evals(w) + w * self.block_size
            if exact:
                per_step += rounds * (w * self.block_size + w)
        self._count(length * per_step)
        self._l1_cache = None  # frontier moved; cached sums are stale
        self._note(st, "NeighborSampler.walk")
        if exact:
            self.exact_draws += w * length
            self.exact_fallbacks += int(jax.device_get(fb))
            _g.warn_fallback_rate(self.exact_fallbacks, self.exact_draws,
                                  rounds, slack,
                                  context="NeighborSampler.walk")
        return np.asarray(end), (np.asarray(path) if record_path else None)


def shared_level1_estimator(nbr: NeighborSampler, estimator: str,
                            seed: int = 0):
    """Reuse ``nbr``'s level-1 KDE structure as the degree estimator
    whenever it implements the requested one (DESIGN.md §6/§7): one device
    dataset, one ``x_sq`` sweep, one eval counter for the whole pipeline.
    A ``level1="hash"`` sampler shares its hashed bucket layout the same
    way (``estimator="hash"`` -> the sampler's own ``HashedKDE``).
    ``rs`` / ``grid_hbe`` (and exact/stratified mismatches) fall back to a
    standalone ``make_estimator`` over the sampler's device dataset."""
    from repro.core.kde.base import make_estimator

    if estimator == "robust":
        # the staged-fallback wrapper builds its own hash->stratified->
        # exact chain; sharing nbr's level-1 would tie its degradation
        # policy to the sampler's cache, so it gets a standalone build
        return make_estimator("robust", nbr.x, nbr.kernel, seed=seed)
    if estimator == "hash":
        if nbr.level1 == "hash":
            return nbr.hash_estimator
        return make_estimator("hash", nbr.x, nbr.kernel, seed=seed)
    wants_exact = estimator in ("exact", "exact_block")
    if wants_exact == nbr.exact_blocks and estimator not in ("rs",
                                                             "grid_hbe"):
        return nbr.blocks
    return make_estimator(estimator if estimator != "exact_block" else
                          "exact", nbr.x, nbr.kernel, seed=seed)


class EdgeSampler:
    """Algorithm 4.13: vertex by degree, then neighbor by weight."""

    def __init__(self, degree_sampler, neighbor_sampler: NeighborSampler):
        self.deg = degree_sampler
        self.nbr = neighbor_sampler

    def sample(self, size: int) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Returns (u, v, p) with p the realized directional probability
        p_hat(u) * q_hat(v | u)."""
        u = self.deg.sample(size)
        v, q = self.nbr.sample(u)
        return u, v, self.deg.prob(u) * q


def _categorical_rows(p: np.ndarray, rng) -> np.ndarray:
    """Sample one index per row of a nonnegative matrix (rows need not be
    normalized).  All-zero rows fall back to a uniform draw instead of
    propagating NaN through the division by the row total."""
    c = np.cumsum(p, axis=1)
    tot = c[:, -1:]
    dead = tot <= 0.0
    uniform = np.broadcast_to(
        np.arange(1, p.shape[1] + 1, dtype=np.float64)[None, :] / p.shape[1],
        c.shape)
    c = np.where(dead, uniform, c / np.where(dead, 1.0, tot))
    u = rng.uniform(size=(p.shape[0], 1))
    return (u > c).sum(axis=1).clip(0, p.shape[1] - 1)
