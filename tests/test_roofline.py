"""Roofline machinery: HLO parsing, trip-count correction, analytic FLOPs
validated against XLA cost_analysis on small UNROLLED models."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import SHAPES, ShapeConfig, get_config, get_reduced
from repro.roofline.analysis import (collective_bytes, roofline_terms,
                                     shape_bytes)
from repro.roofline.flops import _head_flops, _layer_fwd_flops, cell_cost


def test_shape_bytes_parser():
    assert shape_bytes("f32[2,3,4]{2,1,0}") == 96
    assert shape_bytes("bf16[128]") == 256
    assert shape_bytes("(f32[2,2]{1,0}, s32[4])") == 32
    assert shape_bytes("pred[]") == 1
    assert shape_bytes("token[]") == 0


def test_while_trip_count_correction():
    """A collective inside a scan body must be multiplied by the trip count."""
    def f(x):
        def body(c, _):
            return c + jax.lax.psum(c, "i") * 0.001, None
        y, _ = jax.lax.scan(body, x, None, length=7)
        return y

    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1,), ("i",))
    sh = NamedSharding(mesh, P())
    g = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("i"), out_specs=P("i")))
    comp = g.lower(jax.ShapeDtypeStruct((8, 4), jnp.float32)).compile()
    cs = collective_bytes(comp.as_text())
    # one 8x4 f32 all-reduce (on a 1-device mesh it may be optimized away --
    # accept either 0 or trip-scaled bytes)
    if cs.total_bytes > 0:
        assert cs.total_bytes % 7 == 0 or cs.total_bytes >= 7 * 16


def test_analytic_flops_match_hlo_on_unrolled_tiny_model():
    """The roofline compute term comes from the analytic model; validate it
    against cost_analysis on a 2-layer reduced config with UNROLLED layers
    (no scan -> XLA counts everything)."""
    from repro.models import layers as L
    cfg = dataclasses.replace(get_reduced("yi_6b"), dtype="float32",
                              num_layers=2)
    b, s = 2, 128

    def fwd_unrolled(params, tokens):
        x = params["embed"][tokens]
        positions = jnp.arange(s)
        for i in range(cfg.num_layers):
            lp = jax.tree.map(lambda a: a[i], params["layers"])
            from repro.models.transformer import _dense_block
            x, _ = _dense_block(lp, cfg, x, positions, "xla")
        head = params.get("lm_head", params["embed"].T)
        return x @ head

    from repro.models import transformer as T
    params = jax.eval_shape(
        lambda: T.init_params(jax.random.PRNGKey(0), cfg))
    toks = jax.ShapeDtypeStruct((b, s), jnp.int32)
    comp = jax.jit(fwd_unrolled).lower(params, toks).compile()
    ca = comp.cost_analysis()
    if isinstance(ca, list):  # older jax returns one entry per device
        ca = ca[0]
    hlo_flops = ca["flops"]
    analytic = cfg.num_layers * _layer_fwd_flops(cfg, b, s) \
        + 2.0 * b * s * cfg.d_model * cfg.padded_vocab
    ratio = hlo_flops / analytic
    assert 0.7 < ratio < 1.3, (hlo_flops, analytic)


def test_cell_cost_sanity_all_cells():
    """Every (arch x shape) cell yields positive, ordered cost terms."""
    from repro.configs.base import ARCH_IDS
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        for shape in SHAPES.values():
            cost = cell_cost(cfg, shape, kde_decode=(shape.name == "long_500k"))
            assert cost.flops > 0 and cost.hbm_bytes > 0, (arch, shape.name)
            assert cost.model_flops <= cost.flops * 1.01, (arch, shape.name)
            if shape.kind == "train":
                # train FLOPs within 3x of 6ND (attention + dispatch overhead)
                assert cost.flops < 6 * cost.model_flops, (arch, shape.name)


def test_kde_decode_reduces_flops():
    cfg = get_config("yi_6b")
    shape = SHAPES["long_500k"]
    exact = cell_cost(cfg, shape, kde_decode=False)
    kde = cell_cost(cfg, shape, kde_decode=True)
    assert kde.flops < 0.35 * exact.flops  # sub-quadratic attention win


def test_roofline_terms():
    rl = roofline_terms(1e15, 9e14, 1e12, 5e9, 256)
    assert rl.dominant in ("compute", "memory", "collective")
    assert 0 < rl.useful_ratio <= 1.0
    assert rl.compute_s == pytest.approx(1e15 / (256 * 197e12))


def test_chip_peaks_keyed_by_device_kind():
    """Peaks come from the published table by device_kind; an unknown kind
    is an error, and a CPU timing has no roofline ("not measured")."""
    from repro.roofline import analysis as A
    v5e = A.chip_spec("TPU v5 lite")
    assert (v5e.peak_flops, v5e.hbm_bw) == (197e12, 819e9)
    with pytest.raises(ValueError):
        A.chip_spec("TPU v99")
    assert A.device_chip_spec(jax.devices("cpu")[0]) is None
    assert A.roofline_summary(None, 1.0, 1e9, 1e9) == {
        "fraction": A.NOT_MEASURED}
    rl = A.roofline_summary(v5e, 1.0, 197e12, 1.0)
    assert rl["dominant"] == "compute" and abs(rl["fraction"] - 1.0) < 1e-12
