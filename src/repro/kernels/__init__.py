"""Pallas kernels for the paper's compute hot-spots (the CCKP DP and the
batched simplex pivots) plus the language-model kernels.

Every kernel runs compiled on a TPU and in interpret mode elsewhere; the
``ops.py`` wrappers pick the mode with `interpret_mode`."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def interpret_mode(kernel: str, *operands) -> bool:
    """True off-TPU (run the kernel in interpret mode); False on a TPU.

    On a TPU, float64 operands are refused here: Mosaic has no 64-bit
    floats, so the kernel could not lower.  The float64 LP parity path
    is the jnp one (``impl="jnp"``, the default)."""
    if jax.default_backend() != "tpu":
        return True
    wide = sorted({str(x.dtype) for x in operands
                   if jnp.issubdtype(x.dtype, jnp.floating)
                   and jnp.dtype(x.dtype).itemsize > 4})
    if wide:
        raise ValueError(
            f"{kernel}: {'/'.join(wide)} operands on a TPU; Mosaic has no "
            f"64-bit floats.  Use the jnp path (impl='jnp') for float64, "
            f"or pass float32 operands")
    return False
