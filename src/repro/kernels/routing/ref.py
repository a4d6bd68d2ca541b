"""Pure-jnp oracle for the fused routing kernel (identical math, no tiling)."""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core import approx_math


def fused_routing_ref(u_hat: jax.Array, n_iters: int = 3,
                      softmax_mode: str = "exact"
                      ) -> Tuple[jax.Array, jax.Array]:
    """u_hat (B, J, D, I) -> (v (B, J, D), c (B, J, I)); fp32 internally."""
    u = u_hat.astype(jnp.float32)
    bsz, j_, d_, i_ = u.shape
    b = jnp.zeros((bsz, j_, i_), jnp.float32)
    c = v = None
    for it in range(n_iters):
        if softmax_mode == "taylor":
            c = approx_math.taylor_softmax(b, axis=1, range_reduce=True)
        else:
            c = jax.nn.softmax(b, axis=1)
        s = jnp.einsum("bji,bjdi->bjd", c, u)
        v = approx_math.squash_fast(s, axis=-1)
        if it < n_iters - 1:
            b = b + jnp.einsum("bjdi,bjd->bji", u, v)
    return v.astype(u_hat.dtype), c
