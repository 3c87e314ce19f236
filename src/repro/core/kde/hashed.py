"""Device-resident hashed KDE estimator -- the Section 3.1 black-box slot.

``HashedKDE`` adapts the ``repro.kernels.kde_hash`` engine to the
Definition 1.1 estimator interface: the KAP22/DEANN near/far decomposition
(exact NEAR term over the query's random-shifted grid bucket + a
Horvitz-Thompson FAR term over uniform complement samples) as ONE jitted
device program per query batch -- the sub-linear per-query cost the
paper's framework assumes (O(max_bucket + num_far_samples) kernel evals
per query instead of the dense backends' O(n)).

``GridHBE`` (``hbe.py``) remains the host oracle of the same estimator
family; ``HashedKDE`` is what the fused pipelines consume
(``estimator="hash"``), and with ``mesh=`` the bucket tables live sharded
(each shard hashes its own rows) with exactly one psum per query batch
(DESIGN.md §10).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.kde.base import KDEBase
from repro.core.kernels_fn import Kernel
from repro.ft import guards as _g
from repro.kernels import platform as _platform


class HashedKDE(KDEBase):
    """Definition 1.1 estimator over the static padded-bucket layout.

    Per query: <= ``max_bucket`` exact NEAR evals + ``num_far_samples``
    HT-weighted FAR evals, all inside one compiled program (Pallas bucket
    kernel on TPU).  ``evals`` counts the *realized* NEAR reads plus the
    FAR budget -- the paper's Section 7 cost metric.

    >>> est = HashedKDE(x, gaussian(1.0)); est.query(x[:32])
    """

    def __init__(self, x, kernel: Kernel, cell_width: float | None = None,
                 num_hash_dims: int = 8, num_far_samples: int = 64,
                 max_bucket: int = 256, seed: int = 0,
                 use_pallas: bool | None = None,
                 interpret: bool | None = None, mesh=None,
                 data_axes=("data",), dataset=None,
                 overflow_cap: int | None = None, precision: str = "f32"):
        if dataset is not None:
            x = dataset.x_pad      # engines build over the padded capacity
        super().__init__(x, kernel, precision=precision)
        from repro.kernels.kde_hash import ops as _ops
        self._ops = _ops
        self.num_far_samples = int(num_far_samples)
        self.max_bucket = int(max_bucket)
        self._key = jax.random.PRNGKey(seed)
        self.engine = None
        # guards (DESIGN.md §11): last_status is the most recent batch's
        # word, status the or-fold over the estimator's lifetime
        self.last_status = 0
        self.status = 0
        self.flag_counts: dict = {}
        # streaming attach (DESIGN.md §12): derived state is keyed on the
        # dataset's (id, epoch); queries transparently patch-or-rebuild
        self._dataset = dataset
        self._ds_epoch = int(dataset.epoch) if dataset is not None else 0
        self._patcher = None
        self.rebuilds = 0
        if overflow_cap is None:
            overflow_cap = max(64, self.n // 64) if dataset is not None \
                else 0
        self._build_kw = dict(cell_width=cell_width,
                              num_hash_dims=int(num_hash_dims),
                              max_bucket=int(max_bucket), seed=int(seed),
                              overflow_cap=int(overflow_cap))
        self._mesh = mesh
        self._data_axes = data_axes
        self._use_pallas = use_pallas
        self._interpret = interpret
        self._build()

    def _build(self) -> None:
        """(Re)build the bucket layout at the current dataset epoch; also
        the ``needs_rebuild`` compaction path of the streaming contract."""
        from repro.kernels.kde_sampler.ref import static_pairwise
        _ops = self._ops
        kernel = self.kernel
        live = (self._dataset.live_host if self._dataset is not None
                else None)
        if self._dataset is not None:
            self.x = self._dataset.x_pad
            self.x_sq = self._dataset.x_sq_pad
            self.n = int(self.x.shape[0])
        if self._mesh is not None:
            from repro.kernels.kde_hash.sharded import ShardedHashTable
            self.engine = ShardedHashTable(
                self._mesh, self.x, kernel,
                cell_width=self._build_kw["cell_width"],
                num_hash_dims=self._build_kw["num_hash_dims"],
                max_bucket=self._build_kw["max_bucket"],
                num_far_samples=self.num_far_samples,
                data_axes=self._data_axes, seed=self._build_kw["seed"],
                live=live, overflow_cap=self._build_kw["overflow_cap"])
            self.state = None
            self.cell_width = self.engine.spec.cell_width
            return
        self.state, self.cell_width = _ops.build_hash_state(
            self.x, kernel, cell_width=self._build_kw["cell_width"],
            num_hash_dims=self._build_kw["num_hash_dims"],
            max_bucket=self._build_kw["max_bucket"],
            seed=self._build_kw["seed"], live=live,
            overflow_cap=self._build_kw["overflow_cap"])
        self._patcher = (_ops.HashPatcher(self.state, self.cell_width)
                         if self._dataset is not None else None)
        use_pallas, interpret = _platform.resolve(
            self._use_pallas, self._interpret, kernel.name)
        self._cfg = dict(kind=kernel.name, inv_bw=1.0 / kernel.bandwidth,
                         beta=getattr(kernel, "beta", 1.0),
                         pairwise=static_pairwise(kernel),
                         cell_width=self.cell_width,
                         num_far=min(self.num_far_samples, self.n),
                         n=self.n, use_pallas=bool(use_pallas),
                         interpret=bool(interpret),
                         precision=self.precision)

    def compact(self) -> None:
        """Fold the overflow region back into a fresh bucket layout at the
        current epoch (the lazy compaction of DESIGN.md §12)."""
        self._build()
        self.rebuilds += 1
        if self._dataset is not None:
            self._ds_epoch = int(self._dataset.epoch)

    def _sync(self) -> None:
        """Epoch check at query entry: patch the bucket layout by the
        coalesced mutation delta, or rebuild when the journal cannot
        bridge the gap / the overflow region saturated.  Saturation sets
        ``guards.OVERFLOW_SATURATED`` (an ``EstimationError`` under
        ``REPRO_CHECKS=1``; otherwise an automatic compaction)."""
        ds = self._dataset
        if ds is None or self._ds_epoch == int(ds.epoch):
            return
        from repro.core.dataset import coalesce_mutations
        batches = ds.mutations_since(self._ds_epoch)
        if batches is None:        # journal overflow / compact / grow
            self.compact()
            return
        self.x = ds.x_pad
        self.x_sq = ds.x_sq_pad
        slots, old_x, new_x, old_live, new_live = \
            coalesce_mutations(batches)
        if self.engine is not None:
            ok = self.engine.patch_rows(slots, old_x, new_x, old_live,
                                        new_live)
            saturated = not ok
        else:
            new_state = self._patcher.apply(self.state, slots, old_x,
                                            new_x, old_live, new_live)
            saturated = self._patcher.needs_rebuild
            if not saturated:
                self.state = new_state
        if saturated:
            s = _g.OVERFLOW_SATURATED
            self.last_status = s
            self.status |= s
            _g.count_flags(self.flag_counts, s)
            _g.raise_on_status(s, context="HashedKDE.sync",
                               allow=_g.BUCKET_OVERFLOW | _g.HT_HEAVY)
            self.compact()
            return
        self._ds_epoch = int(ds.epoch)

    def _split(self) -> jnp.ndarray:
        self._key, k = jax.random.split(self._key)
        return k

    def _note(self, st) -> int:
        """Fold one program return -- a counter word or a legacy scalar
        status -- into the guard state and ``device_counters``."""
        from repro.obs import counters as _c
        if _c.is_word(st):
            s = self.device_counters.note(jax.device_get(st))
        else:
            s = int(np.uint32(jax.device_get(st)))
        self.last_status = s
        self.status |= s
        _g.count_flags(self.flag_counts, s)
        _g.raise_on_status(s, context="HashedKDE.query",
                           allow=_g.BUCKET_OVERFLOW | _g.HT_HEAVY)
        return s

    def query(self, y: jnp.ndarray) -> jnp.ndarray:
        """NEAR-exact + FAR-sampled row-sum estimates (Section 3.1): one
        device program (one psum on the mesh path) per batch.  The batch's
        status word lands in ``last_status`` (or-folded into ``status``);
        fatal flags raise under ``REPRO_CHECKS=1``."""
        y = jnp.asarray(y, jnp.float32)
        self._sync()
        if self.engine is not None:
            est, cnt, st = self.engine.query(y, self._split())
            self.evals += int(np.asarray(cnt).sum()) \
                + y.shape[0] * self.engine.num_far * self.engine.num_shards
            self._note(st)
            return est
        est, cnt, st = self._ops.hashed_query(self.x, y, self.state,
                                              self._split(), **self._cfg)
        self.evals += int(np.asarray(cnt).sum()) \
            + y.shape[0] * self._cfg["num_far"]
        self._note(st)
        return est

    def degrees(self, batch: int = 1024) -> np.ndarray:
        """Algorithm 4.3 over the hashed structure: n queries of the
        dataset against itself minus the kernel's actual diagonal --
        O(n (max_bucket + num_far_samples)) kernel evals total.  (Defined
        so ``DegreeSampler(mesh=...)`` accepts the mesh adapter; the body
        is the shared host loop.)  With a streaming dataset attached only
        the LIVE rows are queried (sentinel queries against sentinel FAR
        samples would evaluate ``inf - inf``); dead slots report degree
        exactly 0."""
        from repro.core.sampling.vertex import host_degree_loop
        if self._dataset is None:
            return host_degree_loop(self, batch)
        self._sync()
        ls = self._dataset.live_slots()
        out = np.zeros(self.n, np.float64)
        for lo in range(0, len(ls), batch):
            sel = ls[lo:lo + batch]
            out[sel] = np.asarray(self.query(self.x[jnp.asarray(sel)]))
        out[ls] -= 1.0           # k(x, x) = 1 for the Table-1 kernels
        return out
