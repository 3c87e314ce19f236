"""The comparison that decides ``correct`` has to fail what is wrong.

At sizes a CPU test run can hold, each cell is driven through the harness
(set-up, window, check) three ways: as it is (``correct`` true), with the
control -- the program's own bf16 level-1 path switched on -- in its
place, and with the timed path broken underneath in each way the cell
can break: a step that returns its state unchanged, half of the batch
left out, an answer altered where it is produced, and for the
sparsifier, edge batches that repeat one another.  Every broken run must
read ``correct`` false.
"""
import contextlib
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from chipbench.tests import tiny  # noqa: E402

SERVE = "sift1m.exact-mix"
SPARSIFY = "nested64k.exact"


@contextlib.contextmanager
def patched(obj, name, make):
    """Replace ``obj.name`` by ``make(original)`` and drop JAX's caches,
    so that nothing compiled before the patch is reused."""
    import jax
    orig = getattr(obj, name)
    setattr(obj, name, make(orig))
    jax.clear_caches()
    try:
        yield
    finally:
        setattr(obj, name, orig)
        jax.clear_caches()


def _draw_unchanged(orig):
    def fn(xa, xa_sq, tidx, src, *a, **kw):
        _, prob, extra, st = orig(xa, xa_sq, tidx, src, *a, **kw)
        return src, prob, extra, st
    return fn


def _half_served(orig):
    def fn(self, grp, results, statuses):
        h = len(grp) // 2
        orig(self, grp[:h], results[:h], statuses[:h])
    return fn


def _prob_altered(orig):
    def fn(*a, **kw):
        prob, st = orig(*a, **kw)
        return prob * 1.01, st
    return fn


def _query_altered(orig):
    def fn(*a, **kw):
        est, st = orig(*a, **kw)
        return est * 1.0001, st
    return fn


def _serve_faults():
    from repro.core.serving import KernelGraphServable
    from repro.kernels.kde_sampler import ops
    return {"state_unchanged": (ops, "batched_fused_sample", _draw_unchanged),
            "half_left_out": (KernelGraphServable, "_scatter", _half_served),
            "answer_altered": (ops, "batched_prob_of", _prob_altered),
            "query_altered": (ops, "batched_kde_query", _query_altered)}


def _edges(change):
    def make(orig):
        def fn(*a, **kw):
            u, v, w, q_uv, q_vu, word = orig(*a, **kw)
            return change(u, v, w) + (q_uv, q_vu, word)
        return fn
    return make


def _repeat(a):
    """The (batches, batch) edge array with its first half repeated in
    place of the second."""
    t = a.shape[0]
    return a[np.arange(t) % max(t // 2, 1)]


def _one_key(orig):
    def fn(x, x_sq, cdf, degs, inv_total, inv_t, keys, *a, **kw):
        import jax.numpy as jnp
        same = jnp.broadcast_to(keys[:1], keys.shape)
        return orig(x, x_sq, cdf, degs, inv_total, inv_t, same, *a, **kw)
    return fn


def _sparsify_faults():
    from repro.kernels.kde_sampler import ops
    half = lambda u, v, w: (u[:, ::2], v[:, ::2], 2.0 * w[:, ::2])
    rep = lambda u, v, w: (_repeat(u), _repeat(v), _repeat(w))
    return {"state_unchanged": (ops, "edge_batch_scan",
                                _edges(lambda u, v, w: (u, u, w))),
            "half_left_out": (ops, "edge_batch_scan", _edges(half)),
            "answer_altered": (ops, "edge_batch_scan",
                               _edges(lambda u, v, w: (u, v, w * 1.001))),
            "batches_repeated": (ops, "edge_batch_scan", _edges(rep)),
            "one_key": (ops, "edge_batch_scan", _one_key)}


FAULTS = [(SERVE, f) for f in ("state_unchanged", "half_left_out",
                               "answer_altered", "query_altered")] + [
    (SPARSIFY, f) for f in ("state_unchanged", "half_left_out",
                            "answer_altered", "batches_repeated",
                            "one_key")]


@pytest.mark.parametrize("name", [SERVE, SPARSIFY])
def test_control_fails_the_comparison(name):
    import jax
    out = tiny.run(jax, name, control=True)
    assert out["correct"] is False
    failed = [k for k, c in out["compared"].items()
              if not c["value"] <= c["limit"]]
    assert failed and set(failed) <= {"prob_rel", "query_rel",
                                      "weight_rel"}


@pytest.mark.parametrize("name,fault", FAULTS)
def test_broken_timed_path_reads_incorrect(name, fault):
    import jax
    obj, attr, make = (_serve_faults() if name == SERVE
                       else _sparsify_faults())[fault]
    with patched(obj, attr, make):
        out = tiny.run(jax, name)
    assert out["correct"] is False, out["compared"]


def test_half_left_out_is_seen_by_the_missing_count():
    import jax
    obj, attr, make = _serve_faults()["half_left_out"]
    with patched(obj, attr, make):
        out = tiny.run(jax, SERVE)
    assert out["compared"]["missing"]["value"] > 0
    assert out["failed"] == out["compared"]["missing"]["value"]
    assert np.isfinite(out["compared"]["prob_rel"]["value"])
