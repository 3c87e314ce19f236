"""Spans and layer scopes (DESIGN.md §15.2): the spans-only switch of
``obs.metrics`` and its default of following the profiler, the keyword
metadata of a span, and the ``level1`` / ``level2`` / ``edge_scan`` names
the device layers leave in the op-name metadata of the served programs
and the sparsifier's edge scan -- metadata only, the compiled programs
are the same."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.kde_sampler import ops
from repro.obs import metrics as M

N, D, BS = 512, 4, 64


@pytest.fixture(autouse=True)
def _default_switch():
    M.disable()
    yield
    M.reset()
    M.disable()


def test_spans_follow_the_profiler_by_default(tmp_path):
    assert M.span("serve.tick") is M._NULL_SPAN
    jax.profiler.start_trace(str(tmp_path))
    try:
        with M.span("serve.stage", op="sample", requests=8) as s:
            assert s is not M._NULL_SPAN
    finally:
        jax.profiler.stop_trace()
    assert M.span("serve.tick") is M._NULL_SPAN


def test_switch_pins_spans_and_enable_turns_them_on():
    M.set_spans(True)
    assert M.span("a") is not M._NULL_SPAN
    with M.span("a", op="query"):
        pass
    assert M.histograms() == {}          # spans alone record no histogram
    M.set_spans(False)
    assert M.span("a") is M._NULL_SPAN
    M.enable()
    with M.span("b"):
        pass
    assert "span.b.us" in M.histograms()
    M.disable()
    assert M.span("b") is M._NULL_SPAN


def test_scope_decorator_keeps_the_signature():
    @M.scope("level1")
    def f(x, *, k=2):
        """doc"""
        return x * k

    assert f.__name__ == "f" and f.__doc__ == "doc"
    assert f(3, k=4) == 12
    g = jax.jit(f, static_argnames=("k",))
    assert "level1" in _op_names(g, jnp.ones(3))


def _op_names(fn, *args, **kw):
    """Every op-name metadata string of ``fn``'s compiled HLO."""
    hlo = fn.lower(*args, **kw).compile().as_text()
    return " ".join(set(re.findall(r'op_name="([^"]*)"', hlo)))


def _strip(hlo: str) -> str:
    """The instructions alone: no metadata, no stack-frame tables."""
    hlo = hlo.split("\nFileNames", 1)[0]
    return re.sub(r",? ?metadata=\{[^}]*\}", "", hlo)


def _arena():
    x = jax.random.normal(jax.random.PRNGKey(0), (N, D))
    xa = x[None]
    return x, xa, jnp.sum(xa * xa, -1)


def _cfg(**kw):
    return dict(dict(kind="gaussian", inv_bw=1.0, beta=1.0, pairwise=None,
                     block_size=BS, num_blocks=N // BS, n=N, s=16,
                     exact=True, use_pallas=False, interpret=False, bm=128),
                **kw)


@pytest.mark.parametrize("pallas", [False, True])
def test_served_sample_carries_level1_and_level2(pallas):
    _, xa, xa_sq = _arena()
    cfg = _cfg(use_pallas=pallas, interpret=pallas, bm=8)
    names = _op_names(ops.batched_fused_sample, xa, xa_sq,
                      np.zeros(2, np.int32), np.zeros((2, 8), np.int32),
                      np.zeros((2, 2), np.uint32), **cfg)
    assert "level1" in names and "level2" in names


def test_served_query_and_prob_of_carry_their_layers():
    _, xa, xa_sq = _arena()
    cfg = _cfg()
    q = {k: cfg[k] for k in ("kind", "inv_bw", "beta", "pairwise",
                             "block_size", "num_blocks", "n", "s",
                             "exact")}
    names = _op_names(ops.batched_kde_query, xa, xa_sq,
                      np.zeros(2, np.int32), np.zeros((2, 8, D), np.float32),
                      np.zeros((2, 2), np.uint32), **q)
    assert "level1" in names
    names = _op_names(ops.batched_prob_of, xa, xa_sq, np.zeros(2, np.int32),
                      np.zeros((2, 8), np.int32), np.ones((2, 8), np.int32),
                      np.zeros((2, 2), np.uint32), **cfg)
    assert "level1" in names and "level2" in names


def test_edge_scan_carries_edge_scan_and_level1():
    x, _, _ = _arena()
    cdf = jnp.linspace(1.0 / N, 1.0, N)
    names = _op_names(ops.edge_batch_scan, x, jnp.sum(x * x, -1), cdf,
                      jnp.ones(N), 1.0 / N, 1.0 / 64,
                      jax.random.split(jax.random.PRNGKey(1), 4),
                      batch=16, **_cfg())
    for scope in ("edge_scan", "level1", "level2"):
        assert scope in names


def test_scopes_change_metadata_only():
    """The scoped program and the same function traced without its scope
    compile to the same HLO once the metadata is stripped."""
    x, _, _ = _arena()
    q = x[:8]
    cfg = {k: v for k, v in _cfg().items()
           if k in ("kind", "inv_bw", "beta", "pairwise", "block_size",
                    "num_blocks", "n")}
    scoped = ops.exact_block_sums
    plain = jax.jit(scoped.__wrapped__.__wrapped__,
                    static_argnames=tuple(cfg))
    a = scoped.lower(q, x, jnp.sum(x * x, -1), **cfg).compile().as_text()
    b = plain.lower(q, x, jnp.sum(x * x, -1), **cfg).compile().as_text()
    assert "level1" in a and "level1" not in b
    assert _strip(a).replace("exact_block_sums", "") == \
        _strip(b).replace("exact_block_sums", "")
