"""Compile-only rehearsal of the main-path Pallas kernels for a TPU v5e.

Interpret mode accepts layouts the chip's compiler refuses (rank-1 blocks,
(bm, 1) tiles, in-kernel gathers), so every kernel the served path and the
Table-1 pipelines run is compiled here for a described -- not attached --
v5e chip at real widths, in f32 and bf16.  Nothing runs; a refusal raises
at ``compile()``.  The topology is described inside a fixture (never at
import time): only the worker that runs this file loads the TPU compiler.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro.kernels import tuning
from repro.kernels.kde_hash import kernel as hk
from repro.kernels.kde_rowsum import kernel as rk
from repro.kernels.kde_sampler import kernel as sk

# real widths: a 256-row query tile against a 65,536-row dataset in
# 256-row blocks (the dense kernels), and the hashed level-1 gather of a
# 10^6-row tenant (128 NEAR slots + 1000 blocks x 2 FAR slots)
M, N, BN = 256, 65536, 256
HASH_T = 128 + 1000 * 2
INV_BW = 0.25


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- any failure: no TPU compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's executables cannot be read back from the
    # persistent cache, so keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shape(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _call(name, d, precision):
    """(kernel fn, operand shapes) of one main-path kernel call."""
    args = dict(kind="gaussian", inv_bw=INV_BW, beta=1.0,
                precision=precision)
    nb = N // BN
    if name == "rowsum":
        # the tuner's tiles: the compile checks its VMEM budget at this d
        bm, bn = tuning.pallas_tiles(M, N, d, precision)
        return (lambda q, x: rk.rowsum_pallas(q, x, bm=bm, bn=bn, **args),
                [(M, d), (N, d)])
    if name == "blocksum":
        return (lambda q, x: rk.blocksum_pallas(q, x, bm=128, bn=BN, **args),
                [(M, d), (N, d)])
    if name == "masked_blocksum":
        return (lambda q, x, own: sk.masked_blocksum_pallas(
            q, x, own, bm=128, bn=BN, **args),
            [(M, d), (N, d), ((M, 1), jnp.int32)])
    if name == "sample_block":
        return (lambda q, x, own, g: sk.sample_block_pallas(
            q, x, own, g, bm=128, bn=BN, **args),
            [(M, d), (N, d), ((M, 1), jnp.int32), (M, nb)])
    assert name == "weighted_kv"
    return (lambda q, w, xr: hk.weighted_kv_pallas(q, w, xr, bm=32, **args),
            [(M, d), (M, HASH_T), (M, HASH_T, d)])


@pytest.mark.parametrize("precision", ["f32", "bf16"])
@pytest.mark.parametrize("d", [16, 128])
@pytest.mark.parametrize("name", ["rowsum", "blocksum", "masked_blocksum",
                                  "sample_block", "weighted_kv"])
def test_kernel_compiles_for_v5e(one_chip, name, d, precision):
    fn, specs = _call(name, d, precision)
    shapes = [_shape(one_chip, *s) if isinstance(s[0], tuple)
              else _shape(one_chip, s) for s in specs]
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("op", ["batched_fused_sample", "batched_prob_of"])
def test_packed_served_programs_compile_for_v5e(one_chip, op):
    """The served draw and ``prob_of`` programs on a one-tenant arena at
    the SIFT1M widths (10^6 x 128 in 1000-row blocks, 8 requests of 16
    rows): the rows of every request share ONE Pallas level-1 call, and
    the program keeps no per-request copy of the tenant."""
    from repro.kernels.kde_sampler import ops
    n, d, bs, r, w = 1_000_000, 128, 1000, 8, 16
    cfg = dict(kind="gaussian", inv_bw=INV_BW, beta=1.0, pairwise=None,
               block_size=bs, num_blocks=n // bs, n=n, s=16, exact=True,
               use_pallas=True, interpret=False, bm=128, level1="blocked",
               num_far=64, precision="f32")
    rows = _shape(one_chip, (r, w), jnp.int32)
    args = [_shape(one_chip, (1, n, d)), _shape(one_chip, (1, n)),
            _shape(one_chip, (r,), jnp.int32), rows]
    if op == "batched_prob_of":
        args.append(rows)
    args.append(_shape(one_chip, (r, 2), jnp.uint32))
    compiled = getattr(ops, op).lower(*args, **cfg).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    assert compiled.memory_analysis().temp_size_in_bytes < n * d * 4


def test_packed_served_query_compiles_for_v5e(one_chip):
    """The served query program on a one-tenant arena at the SIFT1M
    widths (10^6 x 128 in 1000-row blocks, 16 requests of 8 points): the
    points of every request share ONE Pallas blocksum call, and no (R w,
    n) kernel tile is materialised (temporaries under the tenant's own
    bytes)."""
    from repro.kernels.kde_sampler import ops
    n, d, bs, r, w = 1_000_000, 128, 1000, 16, 8
    cfg = dict(kind="gaussian", inv_bw=INV_BW, beta=1.0, pairwise=None,
               block_size=bs, num_blocks=n // bs, n=n, s=16, exact=True,
               use_pallas=True, interpret=False, bm=128, level1="blocked",
               precision="f32")
    args = [_shape(one_chip, (1, n, d)), _shape(one_chip, (1, n)),
            _shape(one_chip, (r,), jnp.int32), _shape(one_chip, (r, w, d)),
            _shape(one_chip, (r, 2), jnp.uint32)]
    compiled = ops.batched_kde_query.lower(*args, **cfg).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    assert compiled.memory_analysis().temp_size_in_bytes < n * d * 4
