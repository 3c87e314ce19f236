"""Problem data made from the seed: the benchmark's own generators and its
own bandwidth rule, so that no change to the program can change the
problem it is measured on.

``sift_like`` makes SIFT-shaped descriptors on the device (integer
coordinates in [0, 255] around random centres); ``nested`` is the paper's
Figure-2a point set (half at the origin, half on the unit circle);
``median_bandwidth`` is the median rule of Section 3.1 over a seeded
sample of 2,048 points.
"""
from __future__ import annotations

import functools

import numpy as np

#: seeds up to a little over 2**31 arrive on the command line; keys and
#: the program's own 31-bit seeds are derived from them here
SEED_MOD = (1 << 31) - (1 << 20)


def seed31(seed: int) -> int:
    """A non-negative seed derived from any whole ``seed``, with room
    below 2**31 for the offsets the traffic loops add to it."""
    return int(seed) % SEED_MOD


def prng_key(jax, seed: int, stream: int = 0):
    """A PRNG key for ``(seed, stream)``; defined for seeds above 32 bits."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(jax.random.fold_in(key, seed >> 31), stream)


@functools.lru_cache(maxsize=None)
def _sift_program(jax, shapes, d: int, clusters: int):
    jnp = jax.numpy

    def one(kc, kp, n):
        kl, kn = jax.random.split(kp)
        centers = 48.0 * jnp.abs(jax.random.normal(kc, (clusters, d)))
        lab = jax.random.randint(kl, (n,), 0, clusters)
        x = centers[lab] + 12.0 * jax.random.normal(kn, (n, d))
        return jnp.round(jnp.clip(x, 0.0, 255.0))

    def run(center_keys, point_keys):
        return tuple(one(center_keys[i], point_keys[i], n)
                     for i, n in enumerate(shapes))

    return jax.jit(run)


def sift_like(jax, center_keys, point_keys, shapes, d: int = 128,
              clusters: int = 1024):
    """SIFT-shaped f32 point sets on the device, set i with ``shapes[i]``
    rows around the ``clusters`` centres that ``center_keys[i]`` draws,
    all made by ONE jitted program: non-negative integer-valued
    coordinates in [0, 255], as SIFT descriptors are.  Two sets with the
    same centre key come from one distribution (a base set and its query
    set).  The same keys and shapes give the same bits."""
    jnp = jax.numpy
    return _sift_program(jax, tuple(int(n) for n in shapes), int(d),
                         int(clusters))(jnp.stack(list(center_keys)),
                                        jnp.stack(list(point_keys)))


def nested(n: int, seed: int) -> np.ndarray:
    """Half the points at the origin, half on the unit circle (paper
    Figure 2a), with small jitter; (n, 2) float32, rows shuffled."""
    rng = np.random.default_rng(seed)
    half = n // 2
    inner = rng.normal(0.0, 0.05, size=(half, 2))
    theta = rng.uniform(0, 2 * np.pi, size=n - half)
    outer = np.stack([np.cos(theta), np.sin(theta)], 1)
    outer += rng.normal(0.0, 0.02, size=outer.shape)
    x = np.concatenate([inner, outer]).astype(np.float32)
    return x[rng.permutation(n)]


def median_bandwidth(jax, x, sample: int = 2048, seed: int = 0) -> float:
    """Median pairwise Euclidean distance over a seeded sample of rows
    (the median rule of Section 3.1), with squared distances from
    ||a||^2 + ||b||^2 - 2 a.b at full f32 contract precision."""
    jnp = jax.numpy
    x = jnp.asarray(x, jnp.float32)
    n = x.shape[0]
    if n > sample:
        idx = jax.random.choice(jax.random.PRNGKey(seed), n, (sample,),
                                replace=False)
        x = x[idx]

    @jax.jit
    def med(x):
        sq = jnp.sum(x * x, axis=-1)
        d2 = sq[:, None] + sq[None, :] - 2.0 * jnp.matmul(
            x, x.T, precision=jax.lax.Precision.HIGHEST)
        iu = jnp.triu_indices(x.shape[0], k=1)
        return jnp.median(jnp.sqrt(jnp.maximum(d2[iu], 0.0)))

    return float(med(x))
