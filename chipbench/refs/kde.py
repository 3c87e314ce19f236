"""Plain references for the Gaussian kernel graph: no Pallas, no code of
the program, squared distances from coordinate differences.

``k(a, b) = exp(-||a - b||^2 * inv_bw2)`` with ``inv_bw2 = 1 / bw^2``, the
program's Gaussian convention.  Every reference works in blocks of rows
and of columns, so that it never holds the n x n kernel matrix.
"""
from __future__ import annotations

import functools

import numpy as np


@functools.lru_cache(maxsize=None)
def _rowsum_program(jax, chunk: int):
    jnp = jax.numpy

    def run(y, xs, inv_bw2):
        def body(_, xc):
            d2 = jnp.sum(jnp.square(y[:, None, :] - xc[None]), axis=-1)
            return None, jnp.sum(jnp.exp(-d2 * inv_bw2), axis=1)
        return jax.lax.scan(body, None, xs)[1]

    return jax.jit(run)


def rowsums(jax, y, x, inv_bw2: float, rows: int = 64,
            chunk: int = 2048) -> np.ndarray:
    """``sum_j k(y_i, x_j)`` for every row of ``y`` (float64 on the host).
    Rows go in blocks of ``rows``, columns in chunks of ``chunk``; each
    chunk's f32 sum is added in float64 on the host, so that the
    reference's own rounding stays far below the program's.  Padded
    columns sit at 1e30 and add exactly 0."""
    jnp = jax.numpy
    x = jnp.asarray(x, jnp.float32)
    y = np.asarray(y, np.float32)
    pad = -x.shape[0] % chunk
    xs = jnp.pad(x, ((0, pad), (0, 0)),
                 constant_values=1e30).reshape(-1, chunk, x.shape[1])
    run = _rowsum_program(jax, int(chunk))
    m = y.shape[0]
    ypad = np.concatenate([y, np.zeros((-m % rows, y.shape[1]), np.float32)])
    out = [np.asarray(run(jnp.asarray(ypad[lo:lo + rows]), xs,
                          np.float32(inv_bw2)), np.float64).sum(axis=0)
           for lo in range(0, len(ypad), rows)]
    return np.concatenate(out)[:m]


def pairs(jax, a, b, inv_bw2: float) -> np.ndarray:
    """``k(a_i, b_i)`` for aligned rows (float64 on the host)."""
    jnp = jax.numpy
    a = jnp.asarray(a, jnp.float32)
    b = jnp.asarray(b, jnp.float32)
    d2 = jnp.sum(jnp.square(a - b), axis=-1)
    return np.asarray(jnp.exp(-d2 * np.float32(inv_bw2)), np.float64)


@functools.lru_cache(maxsize=None)
def _degree_program(jax, chunk: int):
    jnp = jax.numpy

    def run(rows, x, inv_bw2):
        def body(_, xr):
            d2 = jnp.sum(jnp.square(xr[:, None, :] - x[None]), axis=-1)
            return None, jnp.sum(jnp.exp(-d2 * inv_bw2), axis=1)
        return jax.lax.scan(body, None, rows)[1].reshape(-1)

    return jax.jit(run)


def degrees(jax, x, inv_bw2: float, chunk: int = 512) -> np.ndarray:
    """Each point's degree ``sum_{j != i} k(x_i, x_j)`` as float64 on the
    host, for a small ``d``, row chunk by row chunk in one program.
    ``len(x)`` must be a multiple of ``chunk`` (or smaller than it)."""
    jnp = jax.numpy
    chunk = min(int(chunk), int(x.shape[0]))
    x = jnp.asarray(x, jnp.float32)
    rows = x.reshape(-1, chunk, x.shape[1])
    out = _degree_program(jax, int(chunk))(rows, x, np.float32(inv_bw2))
    # k(x, x) = exp(0) = 1 exactly on the diagonal
    return np.asarray(out, np.float64) - 1.0
