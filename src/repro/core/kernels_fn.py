"""Kernel functions (Table 1 of the paper) and their algebraic properties.

Every kernel maps to [0, 1] with k(x, x) = 1.  The paper parameterizes all
algorithms by ``tau = min_ij k(x_i, x_j)``.

The low-rank reduction (Section 5.2) needs the *squaring constant* ``c`` with
``k(x, y)^2 == k(c*x, c*y)``:

  - Laplacian  exp(-||x-y||_1 / sigma):  k^2 = exp(-2||x-y||_1/sigma)  -> c = 2
  - Exponential exp(-||x-y||_2 / sigma): same argument                  -> c = 2
  - Gaussian   exp(-||x-y||_2^2 / sigma^2): k^2 = exp(-2||.||^2/s^2)    -> c = sqrt(2)

(The paper's prose says "c = 2, 2, and 4 respectively"; for the Gaussian the
correct constant under k(x,y)=exp(-||x-y||^2) is sqrt(2) -- exp(-||cx-cy||^2)
= exp(-c^2 ||x-y||^2) so c^2 = 2.  We implement the mathematically correct
value and verify it by property test.)
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Kernel:
    """A kernel function with the metadata the paper's reductions need."""

    name: str
    # pairwise(x: (m, d), y: (n, d)) -> (m, n) kernel matrix block
    pairwise: Callable[[jnp.ndarray, jnp.ndarray], jnp.ndarray]
    # Constant c with k(x,y)^2 = k(cx, cy); None if no such constant exists.
    squaring_constant: Optional[float]
    # Exponent p of tau in the state-of-the-art KDE query time (Table 1).
    kde_exponent: float
    bandwidth: float = 1.0
    # Shape parameter (rational quadratic only); 1.0 elsewhere.
    beta: float = 1.0

    def matrix(self, x: jnp.ndarray) -> jnp.ndarray:
        """Full kernel matrix K (for oracles / evaluation only)."""
        return self.pairwise(x, x)

    def pairs(self, x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
        """Elementwise k(x_i, y_i) for aligned (w, d) batches -- O(w d), not
        the (w, w) matrix whose diagonal would be thrown away."""
        return jax.vmap(lambda a, b: self.pairwise(a[None, :], b[None, :])[0, 0])(
            jnp.asarray(x, jnp.float32), jnp.asarray(y, jnp.float32))

    def __call__(self, x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
        return self.pairwise(x, y)


def _sq_dists(x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    # ||x - y||^2 = ||x||^2 + ||y||^2 - 2 x.y ; clamp for numerical safety.
    # Full f32 contract precision: a TPU's default f32 dot is one bf16
    # pass, whose cancellation error swamps d2 for uncentered data.
    xx = jnp.sum(x * x, axis=-1)[:, None]
    yy = jnp.sum(y * y, axis=-1)[None, :]
    d2 = xx + yy - 2.0 * jnp.matmul(x, y.T,
                                    precision=jax.lax.Precision.HIGHEST)
    return jnp.maximum(d2, 0.0)


def gaussian(bandwidth: float = 1.0) -> Kernel:
    """exp(-||x-y||_2^2 / sigma^2) (Table 1; squaring constant sqrt(2)).

    >>> ker = gaussian(bandwidth=1.0)
    """
    inv = 1.0 / (bandwidth * bandwidth)

    def pw(x, y):
        return jnp.exp(-_sq_dists(x, y) * inv)

    return Kernel("gaussian", pw, squaring_constant=float(jnp.sqrt(2.0)),
                  kde_exponent=0.173, bandwidth=bandwidth)


def exponential(bandwidth: float = 1.0) -> Kernel:
    """exp(-||x-y||_2 / sigma) (Table 1; squaring constant 2)."""
    inv = 1.0 / bandwidth

    def pw(x, y):
        return jnp.exp(-jnp.sqrt(_sq_dists(x, y)) * inv)

    return Kernel("exponential", pw, squaring_constant=2.0,
                  kde_exponent=0.1, bandwidth=bandwidth)


def laplacian(bandwidth: float = 1.0) -> Kernel:
    """exp(-||x-y||_1 / sigma): the kernel used in the paper's experiments."""
    inv = 1.0 / bandwidth
    budget = 1 << 28  # cap the (m, n, d) broadcast at ~1 GiB of f32

    def pw(x, y):
        m, d = x.shape[0], x.shape[-1]
        n = y.shape[0]
        chunk = max(int(budget // max(n * d, 1)), 1)
        if m <= chunk:
            d1 = jnp.sum(jnp.abs(x[:, None, :] - y[None, :, :]), axis=-1)
            return jnp.exp(-d1 * inv)
        outs = [pw(x[lo:lo + chunk], y) for lo in range(0, m, chunk)]
        return jnp.concatenate(outs, axis=0)

    return Kernel("laplacian", pw, squaring_constant=2.0,
                  kde_exponent=0.5, bandwidth=bandwidth)


def rational_quadratic(beta: float = 1.0, bandwidth: float = 1.0) -> Kernel:
    """(1 + ||x-y||_2^2/sigma^2)^(-beta) (Table 1; no squaring constant,
    so the Section 5.2 low-rank reduction does not apply to it)."""
    inv = 1.0 / (bandwidth * bandwidth)

    def pw(x, y):
        return (1.0 + _sq_dists(x, y) * inv) ** (-beta)

    # k^2 = (1+z)^{-2beta}: no squaring constant in general.
    return Kernel("rational_quadratic", pw, squaring_constant=None,
                  kde_exponent=0.0, bandwidth=bandwidth, beta=beta)


_REGISTRY = {
    "gaussian": gaussian,
    "exponential": exponential,
    "laplacian": laplacian,
    "rational_quadratic": rational_quadratic,
}


def make_kernel(name: str, bandwidth: float = 1.0, **kw) -> Kernel:
    """Factory over the Table-1 kernels by name.

    >>> ker = make_kernel("laplacian", bandwidth=2.0)
    """
    return _REGISTRY[name](bandwidth=bandwidth, **kw)


def squared_kernel_dataset(kernel: Kernel, x: jnp.ndarray) -> jnp.ndarray:
    """Transform dataset X -> cX so that row sums of K' give ||K_i,*||_2^2.

    Section 5.2: k(x,y)^2 = k(cx, cy), so KDE queries against cX with query
    c*y return sum_j k(x_j, y)^2, i.e. squared row norms of K.
    """
    c = kernel.squaring_constant
    if c is None:
        raise ValueError(f"kernel {kernel.name} admits no squaring constant")
    return x * c


def median_bandwidth(x: jnp.ndarray, ord: int = 2, sample: int = 2048,
                     seed: int = 0) -> float:
    """The 'median rule' (Section 3.1): bandwidth = median pairwise distance."""
    n = x.shape[0]
    if n > sample:
        idx = jax.random.choice(jax.random.PRNGKey(seed), n, (sample,),
                                replace=False)
        x = x[idx]
    if ord == 2:
        d = jnp.sqrt(_sq_dists(x, x))
    else:
        d = jnp.sum(jnp.abs(x[:, None, :] - x[None, :, :]), axis=-1)
    off = d[jnp.triu_indices(x.shape[0], k=1)]
    return float(jnp.median(off))
