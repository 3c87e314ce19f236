"""Where the Pallas kernels run, decided from the platform in one place.

On a TPU backend the kernels are compiled by Mosaic and the engines use
them by default.  Elsewhere the engines run their jnp paths, and a kernel
that a caller asks for explicitly (the interpret-mode oracle tests) runs
under the Pallas interpreter.  Nothing on a TPU runs interpreted.
"""
from __future__ import annotations

import jax

# the kinds the Pallas bodies implement: a custom ``pairwise`` kernel has
# no kernel body and always takes the jnp path
from repro.kernels.kde_sampler.ref import BUILTIN_KINDS


def on_tpu() -> bool:
    """True when the default backend compiles Pallas TPU kernels."""
    return jax.default_backend() == "tpu"


def resolve(use_pallas: bool | None = None, interpret: bool | None = None,
            kind: str | None = None) -> tuple[bool, bool]:
    """``(use_pallas, interpret)`` with unset values taken from the
    platform: Pallas on a TPU (for the kinds it implements), interpreted
    only off-TPU.  An explicit ``interpret=True`` on a TPU is an error."""
    tpu = on_tpu()
    if use_pallas is None:
        use_pallas = tpu and (kind is None or kind in BUILTIN_KINDS)
    if interpret is None:
        interpret = not tpu
    elif interpret and tpu:
        raise ValueError("interpret=True on a TPU backend: the kernels run "
                         "compiled on the chip")
    return bool(use_pallas), bool(interpret)


def interpret_mode(interpret: bool | None = None) -> bool:
    """The ``interpret`` flag for a direct kernel call (see ``resolve``)."""
    return resolve(True, interpret)[1]
