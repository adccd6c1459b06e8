"""jit'd wrapper exposing the kernel with core/amdp._model_dp's signature
(so `amdp(..., impl="pallas")` drops in)."""
from __future__ import annotations

import jax.numpy as jnp

from .. import interpret_mode
from .cckp_dp import cckp_model_dp


def model_dp(y: jnp.ndarray, p_i: int, a_i: float, n_steps: int):
    interpret = interpret_mode("cckp_dp", y)
    a = jnp.asarray(a_i, jnp.float32)
    return cckp_model_dp(y, a, p=int(p_i), n_steps=int(n_steps),
                         interpret=interpret)
