"""Regression tests for the §Perf optimizations (EXPERIMENTS.md):
chunked attention, context-parallel prefill, shard_map MoE, shard_map KDE
decode.  Multi-device checks run in subprocesses with their own XLA_FLAGS."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import subproc
from repro.models import layers as L


RNG = np.random.default_rng(7)


def _run(code: str, devices: int = 8) -> str:
    return subproc.run_devices(code, devices, tail=1500)


# ------------------------------------------------------- chunked attention
@pytest.mark.parametrize("b,hq,hkv,sq,skv,chunk", [
    (2, 4, 2, 120, 120, 32),      # GQA, ragged chunking
    (1, 2, 2, 64, 64, 64),        # single chunk
    (2, 8, 4, 33, 97, 16),        # decode-ish offset shapes
])
def test_chunked_attention_equals_dense(b, hq, hkv, sq, skv, chunk):
    hd = 16
    q = jnp.asarray(RNG.normal(0, 1, (b, hq, sq, hd)).astype(np.float32))
    k = jnp.asarray(RNG.normal(0, 1, (b, hkv, skv, hd)).astype(np.float32))
    v = jnp.asarray(RNG.normal(0, 1, (b, hkv, skv, hd)).astype(np.float32))
    off = skv - sq
    o1 = L.xla_attention(q, k, v, causal=True, q_offset=off, kv_valid=skv - 3)
    o2 = L.xla_attention_chunked(q, k, v, causal=True, q_offset=off,
                                 kv_valid=skv - 3, chunk=chunk)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=2e-5)


def test_chunked_attention_bf16():
    q = jnp.asarray(RNG.normal(0, 1, (1, 2, 64, 16))).astype(jnp.bfloat16)
    k = jnp.asarray(RNG.normal(0, 1, (1, 2, 64, 16))).astype(jnp.bfloat16)
    v = jnp.asarray(RNG.normal(0, 1, (1, 2, 64, 16))).astype(jnp.bfloat16)
    o1 = L.xla_attention(q, k, v, causal=True)
    o2 = L.xla_attention_chunked(q, k, v, causal=True, chunk=16)
    np.testing.assert_allclose(np.asarray(o1, np.float32),
                               np.asarray(o2, np.float32), atol=3e-2)


# ------------------------------------------------- context-parallel prefill
def test_seq_mode_prefill_lowers_and_cuts_collectives():
    out = _run("""
import dataclasses, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs.base import get_reduced, ShapeConfig
from repro.data.pipeline import input_specs
from repro.distributed import sharding as shard
from repro.models import transformer as T
from repro.models.layers import activation_sharding
from repro.train.train_step import make_prefill_step
from repro.roofline.analysis import collective_bytes

cfg = get_reduced("yi_6b")
shape = ShapeConfig("p", 256, 4, "prefill")
mesh = make_mesh((2, 4), ("data", "model"))
params_s = jax.eval_shape(lambda: T.cast_params(
    T.init_params(jax.random.PRNGKey(0), cfg), jnp.bfloat16))
p_sh = shard.param_shardings(params_s, mesh)
specs = input_specs(cfg, shape)
b_sh = {k: NamedSharding(mesh, shard.batch_spec(mesh, v.ndim, v.shape[0]))
        for k, v in specs.items()}
res = {}
for mode in (False, True):
    with activation_sharding(mesh, ("data",), seq_mode=mode):
        comp = jax.jit(make_prefill_step(cfg),
                       in_shardings=(p_sh, b_sh)).lower(params_s, specs).compile()
    res[mode] = collective_bytes(comp.as_text(),
                                 default_trip=cfg.num_layers).total_bytes
print("TP:", res[False], "CP:", res[True])
assert res[True] > 0
print("SEQ_MODE_OK")
""")
    assert "SEQ_MODE_OK" in out


def test_seq_mode_numerics_match():
    """CP-sharded prefill produces the same logits as unsharded."""
    out = _run("""
import dataclasses, jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs.base import get_reduced, ShapeConfig
from repro.data.pipeline import make_batch
from repro.models import transformer as T
from repro.models.layers import activation_sharding
cfg = dataclasses.replace(get_reduced("yi_6b"), dtype="float32")
params = T.init_params(jax.random.PRNGKey(0), cfg)
shape = ShapeConfig("p", 64, 2, "train")
batch = {k: jnp.asarray(v) for k, v in make_batch(cfg, shape, 0).items()}
ref, _ = T.forward(params, cfg, batch, remat=False)
mesh = make_mesh((2, 4), ("data", "model"))
with activation_sharding(mesh, ("data",), seq_mode=True):
    got, _ = jax.jit(lambda p, b: T.forward(p, cfg, b, remat=False))(params, batch)
np.testing.assert_allclose(np.asarray(ref), np.asarray(got), atol=2e-3)
print("CP_NUMERICS_OK")
""")
    assert "CP_NUMERICS_OK" in out


# --------------------------------------------------------- shard_map MoE
def test_shardmap_moe_matches_dense_reference():
    out = _run("""
import dataclasses, jax, jax.numpy as jnp, numpy as np
from repro.configs.base import get_reduced
from repro.models import transformer as T
from repro.models import layers as L
cfg = dataclasses.replace(get_reduced("granite_moe_1b_a400m"), dtype="float32")
params = T.init_params(jax.random.PRNGKey(0), cfg)
lp = jax.tree.map(lambda a: a[0], params["layers"])
x = jnp.asarray(np.random.default_rng(0).normal(
    0, 0.5, (4, 16, cfg.d_model)).astype(np.float32))
mesh = make_mesh((2, 4), ("data", "model"))  # 4 experts over model=4
y_ref, aux_ref = L.moe_block_dense(lp["mlp"], cfg, x)
with L.activation_sharding(mesh, ("data",)):
    y_sm, aux_sm = jax.jit(lambda p, x: L.moe_block(p, cfg, x,
                                                    capacity_factor=8.0))(
        lp["mlp"], x)
np.testing.assert_allclose(np.asarray(y_sm), np.asarray(y_ref), atol=1e-4)
assert abs(float(aux_sm) - float(aux_ref)) < 1e-4
print("MOE_SHARDMAP_OK")
""")
    assert "MOE_SHARDMAP_OK" in out


def test_shardmap_moe_grads_match():
    out = _run("""
import dataclasses, jax, jax.numpy as jnp, numpy as np
from repro.configs.base import get_reduced
from repro.models import layers as L
from repro.models import transformer as T
cfg = dataclasses.replace(get_reduced("granite_moe_1b_a400m"), dtype="float32")
params = T.init_params(jax.random.PRNGKey(0), cfg)
lp = jax.tree.map(lambda a: a[0], params["layers"])
x = jnp.asarray(np.random.default_rng(1).normal(
    0, 0.5, (4, 8, cfg.d_model)).astype(np.float32))
mesh = make_mesh((2, 4), ("data", "model"))

def loss_ref(p, x):
    y, aux = L.moe_block_dense(p, cfg, x)
    return jnp.sum(y ** 2) + 0.01 * aux

def loss_sm(p, x):
    y, aux = L.moe_block(p, cfg, x, capacity_factor=8.0)
    return jnp.sum(y ** 2) + 0.01 * aux

g_ref = jax.grad(loss_ref)(lp["mlp"], x)
with L.activation_sharding(mesh, ("data",)):
    g_sm = jax.jit(jax.grad(loss_sm))(lp["mlp"], x)
for k in ("w1", "w2", "w3", "router"):
    np.testing.assert_allclose(np.asarray(g_sm[k]), np.asarray(g_ref[k]),
                               atol=2e-3)
print("MOE_GRADS_OK")
""")
    assert "MOE_GRADS_OK" in out


# --------------------------------------------------- shard_map KDE decode
@pytest.mark.parametrize("hkv", [2, 4])  # seq-sharded vs heads-sharded layout
def test_shardmap_kde_decode_matches_mirror(hkv):
    out = _run(f"""
import jax, jax.numpy as jnp, numpy as np
from repro.models import layers as L
from repro.kernels.kde_attention.ref import kde_attention_ref
rng = np.random.default_rng(0)
b, hq, hkv, S, hd = 1, 8, {hkv}, 1024, 32
q = jnp.asarray(rng.normal(0, 1, (b, hq, 1, hd)).astype(np.float32))
k = jnp.asarray(rng.normal(0, 0.3, (b, hkv, S, hd)).astype(np.float32))
v = jnp.asarray(rng.normal(0, 1, (b, hkv, S, hd)).astype(np.float32))
mesh = make_mesh((2, 4), ("data", "model"))
kw = dict(top_p=4, bk=64, stride=4)
with L.activation_sharding(mesh, ("data",)):
    out = L.kde_decode_attention_shardmap(q, k, v, 900, mesh=mesh,
                                          baxes=("data",), **kw)
ref = kde_attention_ref(q[:, :, 0, :], k, v, kv_valid=900, **kw)
np.testing.assert_allclose(np.asarray(out[:, :, 0, :]), np.asarray(ref),
                           atol=1e-5)
print("KDE_SHARDMAP_OK")
""")
    assert "KDE_SHARDMAP_OK" in out


def test_shardmap_kde_falls_back_on_indivisible():
    """S not a multiple of bk*shards -> returns None (mirror fallback)."""
    out = _run("""
import jax, jax.numpy as jnp, numpy as np
from repro.models import layers as L
rng = np.random.default_rng(0)
q = jnp.asarray(rng.normal(0, 1, (1, 4, 1, 16)).astype(np.float32))
k = jnp.asarray(rng.normal(0, 1, (1, 2, 96, 16)).astype(np.float32))
v = jnp.asarray(rng.normal(0, 1, (1, 2, 96, 16)).astype(np.float32))
mesh = make_mesh((2, 4), ("data", "model"))
r = L.kde_decode_attention_shardmap(q, k, v, 90, top_p=2, bk=64, stride=4,
                                    mesh=mesh, baxes=("data",))
assert r is None
print("FALLBACK_OK")
""")
    assert "FALLBACK_OK" in out
