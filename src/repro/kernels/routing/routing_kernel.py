"""Fused dynamic-routing Pallas TPU kernel.

Design (DESIGN.md §2 — the FPGA->TPU adaptation of "everything in BRAM"):

* One ``pallas_call`` runs ALL routing iterations for a block of batch rows.
  ``u_hat``, the logits ``b``, couplings ``c`` and votes ``v`` never leave
  VMEM between iterations — zero HBM round-trips inside the loop, vs.
  3 x 4 tensor round-trips for the unfused jnp version.

* Layout: ``u_hat`` is (B, J, D, I) and ``c`` is (B, J, I), so the input
  capsule axis I — the long one, 1152 = 9 x 128 unpruned — is the lane
  axis and D = 16 fills whole sublane tiles.  Every per-parent slice is
  then a leading-axis index (``u_ref[:, j]``) or a sublane row
  (``c[:, j:j+1]``); nothing slices along the lanes.

* The paper's loop reordering (Code 1 -> Code 2: make j, k the outer loops
  so the PE array vectorizes over input capsules with no write conflict)
  becomes: the FC and Agreement contractions are expressed per parent
  capsule j (static Python loop — J is 10) as batched matmuls over I:

      FC:        s_j  = c[:, j] . u[:, j]^T       (B, 1, I) x (B, D, I)
      Agreement: b_j += v_j . u[:, j]             (B, 1, D) x (B, D, I)

  Both land on the MXU; row j of ``b`` is updated in place — no scatter.

* ``softmax_mode="taylor"`` uses the paper's Eq. 2 polynomial (pure MAC
  work — no transcendental path) for the coupling softmax.

Grid: 1-D over batch blocks.  VMEM per step for the unpruned MNIST CapsNet
(B_blk=8, I=1152, J=10, D=16, fp32): the u_hat block is exactly
8*10*16*1152*4 B = 5.9 MB (D fills the 8-row sublane tiles, I = 9 lane
tiles), double-buffered 11.8 MB; c and the logit scratch pad J=10 to 16
rows, 8*16*1152*4 B = 1.2 MB each (c double-buffered).  ~14.7 MB in all,
under the 16 MiB scoped-VMEM default.  Pruned (I=252, padded to 256
lanes) the same block is ~3.3 MB.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import approx_math


def _bmm(a: jax.Array, b: jax.Array, contract_a: int, contract_b: int
         ) -> jax.Array:
    """Batched (leading-axis) matmul at full f32 precision."""
    return jax.lax.dot_general(
        a, b, dimension_numbers=(((contract_a,), (contract_b,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST)


def _routing_kernel(u_ref, v_ref, c_ref, b_scr, *, n_iters: int,
                    softmax_mode: str):
    n_out = u_ref.shape[1]
    b_scr[...] = jnp.zeros_like(b_scr)                 # (Bb, J, I)
    for it in range(n_iters):
        # softmax over parents j: the sublane axis of (Bb, J, I)
        logits = b_scr[...]
        z = logits - jnp.max(logits, axis=1, keepdims=True)
        e = (approx_math.taylor_exp(z, range_reduce=True)
             if softmax_mode == "taylor" else jnp.exp(z))
        c_ref[...] = e / jnp.maximum(jnp.sum(e, axis=1, keepdims=True), 1e-30)
        for j in range(n_out):                         # paper Code 2: j outer
            uj = u_ref[:, j].astype(jnp.float32)       # (Bb, D, I)
            s = _bmm(c_ref[:, j:j + 1, :], uj, 2, 2)   # FC       (Bb, 1, D)
            # Squash (paper Fig. 11a: one ||s||^2, one rsqrt)
            sq = jnp.sum(jnp.square(s), axis=-1, keepdims=True)
            v = s * (sq * jax.lax.rsqrt(sq + 1e-9) / (1.0 + sq))
            if it < n_iters - 1:
                b_scr[:, j:j + 1, :] += _bmm(v, uj, 2, 1)  # Agreement (Bb, 1, I)
            else:
                v_ref[:, j:j + 1, :] = v.astype(v_ref.dtype)


def fused_routing_pallas(
    u_hat: jax.Array,
    n_iters: int = 3,
    softmax_mode: str = "exact",
    batch_block: int = 8,
    *,
    interpret: bool,
) -> Tuple[jax.Array, jax.Array]:
    """u_hat (B, J, D, I) -> (v (B, J, D), c (B, J, I))."""
    bsz, n_out, d, n_in = u_hat.shape
    bb = min(batch_block, bsz)
    assert bsz % bb == 0, (bsz, bb)
    kernel = functools.partial(
        _routing_kernel, n_iters=n_iters, softmax_mode=softmax_mode)
    v, c = pl.pallas_call(
        kernel,
        grid=(bsz // bb,),
        in_specs=[
            pl.BlockSpec((bb, n_out, d, n_in), lambda i: (i, 0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bb, n_out, d), lambda i: (i, 0, 0)),
            pl.BlockSpec((bb, n_out, n_in), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, n_out, d), u_hat.dtype),
            jax.ShapeDtypeStruct((bsz, n_out, n_in), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bb, n_out, n_in), jnp.float32)],
        interpret=interpret,
    )(u_hat)
    return v, c
