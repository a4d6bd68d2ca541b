"""Taylor-softmax Pallas kernel — paper Eq. 2 as a row-tiled TPU kernel.

The Eq. 2 polynomial is pure MAC work (5 mul + 5 add, Horner), so the whole
softmax is VPU element-wise ops + a row reduction: no transcendental path.
Row blocks are tiled to (row_block, N); N (the softmax axis) stays whole in
VMEM because softmax is a full-row reduction.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.approx_math import E_A, TAYLOR_COEFFS


def _taylor_exp_inline(x, reduce_k: int = 5):
    c0, c1, c2, c3, c4, c5 = TAYLOR_COEFFS
    scale = float(2 ** reduce_k)
    x = jnp.clip(x, -scale, scale) / scale
    p = c4 + c5 * x
    p = c3 + x * p
    p = c2 + x * p
    p = c1 + x * p
    p = c0 + x * p
    y = E_A * p
    for _ in range(reduce_k):
        y = y * y
    return y


def _softmax_kernel(x_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)                # (Rb, N)
    m = jnp.max(x, axis=-1, keepdims=True)
    e = _taylor_exp_inline(x - m)
    o = e / jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)
    o_ref[...] = o.astype(o_ref.dtype)


def taylor_softmax_pallas(x: jax.Array, row_block: int = 256, *,
                          interpret: bool) -> jax.Array:
    """Softmax over the last axis of x (any leading shape) using Eq. 2."""
    shape = x.shape
    n = shape[-1]
    x2 = x.reshape(-1, n)
    rows = x2.shape[0]
    rb = min(row_block, rows)
    while rows % rb:
        rb -= 1
    if rb % 8:                # sublane rule: 8-row blocks or all the rows
        rb = rows
    out = pl.pallas_call(
        _softmax_kernel,
        grid=(rows // rb,),
        in_specs=[pl.BlockSpec((rb, n), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rb, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, n), x.dtype),
        interpret=interpret,
    )(x2)
    return out.reshape(shape)
