"""Dynamic routing between capsules (Sabour et al., paper Fig. 4).

Inputs: prediction vectors ``u_hat`` of shape (B, N_out, D_out, N_in) where
``u_hat[b, j, :, i]`` is capsule i's prediction for parent capsule j.  The
input-capsule axis is minor-most so the fused kernel keeps it in the TPU
lanes (see kernels/routing); every variant takes the same layout.

Algorithm (r iterations, r=3 in the paper):

    b_ij = 0
    repeat r times:
        c_i: = softmax(b_i:)                 over parents j     (Softmax step)
        s_j  = sum_i c_ij * u_hat_ij                            (FC step)
        v_j  = squash(s_j)                                      (Squash step)
        b_ij += <u_hat_ij, v_j>                                 (Agreement step)

Variant selection lives in ``repro.deploy``: build a typed
``RoutingSpec`` and ``resolve()`` it through the registry; the free
functions below are the registered implementations.

Variants (``mode``):
  * ``reference``  — exact softmax/div, einsum contractions; the oracle.
  * ``optimized``  — the FastCaps §III-B simplifications mapped to TPU:
        - Taylor-series exp (Eq. 2) in the softmax, optional exp/log div
          (Eq. 3);
        - the Agreement/FC contractions expressed as (N_out*D)-shaped
          matmuls (the paper's loop reordering: j,k become the outer loops,
          removing the write conflict — here the MXU-shaped contraction);
  * ``pallas``     — kernels/routing: the whole r-iteration loop fused in
        one VMEM-resident Pallas kernel (the paper's "everything in BRAM").

All variants return (v, c_last): parent capsules (B, N_out, D_out) and the
final coupling coefficients (B, N_out, N_in).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core import approx_math


def _softmax_parents(b: jax.Array, mode: str, use_div_exp_log: bool = False
                     ) -> jax.Array:
    """Softmax over the parent axis (axis 1 of (B, N_out, N_in))."""
    if mode == "taylor":
        return approx_math.taylor_softmax(
            b, axis=1, range_reduce=True, use_div_exp_log=use_div_exp_log)
    return jax.nn.softmax(b, axis=1)


def route_reference(u_hat: jax.Array, n_iters: int = 3,
                    ) -> Tuple[jax.Array, jax.Array]:
    """Oracle implementation — direct transcription of Fig. 4."""
    bsz, n_out, d, n_in = u_hat.shape
    uf = u_hat.astype(jnp.float32)
    b = jnp.zeros((bsz, n_out, n_in), jnp.float32)
    c = v = None
    for _ in range(n_iters):
        c = jax.nn.softmax(b, axis=1)                        # (B, J, I)
        s = jnp.einsum("bji,bjdi->bjd", c, uf)               # FC
        v = approx_math.squash(s, axis=-1)                   # Squash
        b = b + jnp.einsum("bjdi,bjd->bji", uf, v)           # Agreement
    return v.astype(u_hat.dtype), c


def route_optimized(u_hat: jax.Array, n_iters: int = 3,
                    softmax_mode: str = "taylor",
                    use_div_exp_log: bool = False,
                    ) -> Tuple[jax.Array, jax.Array]:
    """FastCaps-optimized routing (paper §III-B) in pure JAX.

    The contraction layout is the TPU analogue of the paper's reordered
    loops (Code 2): with the parent axis j leading, the FC step
    ``s_j = u_j @ c_j`` and the Agreement step ``b_j += v_j @ u_j`` are
    per-parent matmuls batched over (B, J) and contracted over the
    input-capsule axis, with no scatter into ``b`` (the write conflict the
    paper removes by making j,k outer loops).
    """
    uf = u_hat.astype(jnp.float32)
    bsz, n_out, d, n_in = uf.shape
    b = jnp.zeros((bsz, n_out, n_in), jnp.float32)
    c = v = None
    for _ in range(n_iters):
        c = _softmax_parents(b, softmax_mode, use_div_exp_log)
        s = jnp.einsum("bji,bjdi->bjd", c, uf)               # FC
        v = approx_math.squash_fast(s, axis=-1)
        b = b + jnp.einsum("bjdi,bjd->bji", uf, v)           # Agreement
    return v.astype(u_hat.dtype), c


def route_pallas(u_hat: jax.Array, n_iters: int = 3,
                 softmax_mode: str = "taylor",
                 interpret: bool | None = None
                 ) -> Tuple[jax.Array, jax.Array]:
    """Fused VMEM-resident routing kernel, dispatched through the
    :data:`repro.kernels.registry` (block sizes come from the tuner cache
    or the deterministic legalized defaults).

    ``interpret=None`` lets the registry probe the backend (compiled on
    TPU, interpret mode elsewhere).
    """
    from repro import kernels

    return kernels.fused_routing(
        u_hat, n_iters=n_iters, softmax_mode=softmax_mode,
        interpret=interpret)


def routing_flops(bsz: int, n_in: int, n_out: int, d: int, n_iters: int = 3
                  ) -> int:
    """Analytic FLOP count of the routing loop (for Fig. 8 / roofline)."""
    per_iter = (
        2 * bsz * n_in * n_out * d      # FC (mul+add)
        + 2 * bsz * n_in * n_out * d    # Agreement
        + 6 * bsz * n_in * n_out        # softmax (exp + norm, ~6 flops/elt)
        + 6 * bsz * n_out * d           # squash
    )
    return per_iter * n_iters
