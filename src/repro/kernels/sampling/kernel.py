"""Fused device sampling (Pallas): temperature / top-k / top-p masking
and the categorical draw happen in one launch over a block of rows, so a
serving tick's sampled tokens leave the device as a single (B,) int32
transfer instead of a per-row host numpy loop over full logit rows.

The body is :func:`repro.kernels.sampling.ref.sample_tokens` applied to
the VMEM-resident row block — the kernel adds the blocking/fusion, the
math lives in one place (which is what makes oracle parity exact).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.sampling.ref import sample_tokens


def _fused_sampling_kernel(logits_ref, t_ref, seed_ref, pos_ref, tk_ref,
                           tp_ref, o_ref):
    tok = sample_tokens(logits_ref[...],
                        t_ref[...][:, 0], seed_ref[...][:, 0],
                        pos_ref[...][:, 0], tk_ref[...][:, 0],
                        tp_ref[...][:, 0])
    o_ref[...] = tok[:, None]


def _vmem_limit(rows: int, v: int) -> int:
    """Scoped-VMEM request for one (rows, V) block: the double-buffered
    logits plus the ~6 full-row f32 temporaries of :func:`sample_tokens`
    (rows pad to 8 sublanes).  A full vocabulary (V = 151936) needs
    ~23 MB at 8 rows, over the 16 MiB default."""
    padded = -(-rows // 8) * 8
    return min(max(8 * padded * v * 4, 16 << 20), 100 << 20)


def fused_sampling_pallas(
    logits: jax.Array, temperature: jax.Array, seeds: jax.Array,
    pos: jax.Array, top_k: jax.Array, top_p: jax.Array, *,
    row_block: int = 8, interpret: bool,
) -> jax.Array:
    """logits (B, V); temperature/seeds/pos/top_k/top_p (B,) -> (B,) i32."""
    b, v = logits.shape
    rb = max(min(int(row_block), b), 1)
    while b % rb:
        rb -= 1
    if rb % 8:                # sublane rule: 8-row blocks or the whole batch
        rb = b
    col = pl.BlockSpec((rb, 1), lambda i: (i, 0))
    out = pl.pallas_call(
        _fused_sampling_kernel,
        grid=(b // rb,),
        in_specs=[pl.BlockSpec((rb, v), lambda i: (i, 0)),
                  col, col, col, col, col],
        out_specs=pl.BlockSpec((rb, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, 1), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_vmem_limit(rb, v)),
        interpret=interpret,
    )(logits,
      temperature.astype(jnp.float32).reshape(b, 1),
      seeds.astype(jnp.int32).reshape(b, 1),
      pos.astype(jnp.int32).reshape(b, 1),
      top_k.astype(jnp.int32).reshape(b, 1),
      top_p.astype(jnp.float32).reshape(b, 1))
    return out[:, 0]
