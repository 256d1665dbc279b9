"""Pure-jnp oracle for the fused LP round: ``out = c·base + A @ F``."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.common import F32_MATMUL


def lp_round_ref(
    A: jnp.ndarray,      # (N, N) fused operator (αβ·scale·H + α·M)
    F: jnp.ndarray,      # (N, S) current labels
    base: jnp.ndarray,   # (N, S) Y (fixed) or F (drift)
    c: float,            # β²
) -> jnp.ndarray:
    return c * base + jnp.matmul(
        A, F, preferred_element_type=jnp.float32, precision=F32_MATMUL
    ).astype(F.dtype)
