"""Counter-based fused sampling: the math, shared by kernel and oracle.

Sampling must be reproducible and *slot-order independent*: a request's
token at sequence position ``pos`` may be drawn on any engine, any slot,
any batch composition, before or after a preemption or a disaggregated
handoff.  So the randomness is a pure counter-based hash of
``(request seed, position, vocab lane)`` — no RNG state object travels
anywhere — and the draw is a Gumbel-argmax over the kept lanes:

    h    = seed ^ (pos * 0x9E3779B9) ^ (lane * 0x85EBCA6B)   (uint32)
    h    = fmix32(h)                    # murmur3 finalizer
    u    = (h >> 8) * 2^-24, clamped >= 1e-7
    tok  = argmax_{kept lanes}( logits/T + (-log(-log u)) )

Top-k / top-p restrict the kept lanes via a 30-step bisection over the
scaled-logit value range (vectorized over rows; no sort, no O(V^2)
pairwise compare — both are hostile to the TPU vector unit).  The steps
run as a ``fori_loop``: unrolled, 60 full-vocabulary passes make the
TPU kernel compiler emit code for every one of them.  The argmax
lane is always kept, and greedy (``temperature <= 0``) bypasses the draw
entirely with an exact raw-logits argmax, so temperature=0 decode is
bit-identical to the pre-kernel path.

:func:`sample_tokens` is the single source of truth: the Pallas kernel
body calls it on its VMEM blocks and :func:`fused_sampling_ref` calls it
whole-batch, which is what makes kernel-vs-oracle parity exact (same op
sequence, not merely allclose).  :func:`sample_token_host` is the numpy
mirror used by the host-sampling engine path — same algorithm and
constants; libm vs XLA transcendentals may differ in the last ulp, so
cross-path identity is only asserted for greedy.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

NEG_INF = -1e30
_GOLD = 0x9E3779B9        # 2^32 / golden ratio — position stride
_MIX1 = 0x85EBCA6B        # murmur3 fmix32 constants
_MIX2 = 0xC2B2AE35
_BISECT_STEPS = 30        # halves the f32 value range to ~1e-9 resolution


def _uniform_lanes(seeds, pos, b: int, v: int):
    """(b, v) uniforms in (0, 1), pure function of (seed, pos, lane)."""
    lane = jax.lax.broadcasted_iota(jnp.uint32, (b, v), 1)
    h = (seeds.astype(jnp.uint32)[:, None]
         ^ (pos.astype(jnp.uint32)[:, None] * jnp.uint32(_GOLD))
         ^ (lane * jnp.uint32(_MIX1)))
    h = h ^ (h >> 16)
    h = h * jnp.uint32(_MIX1)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(_MIX2)
    h = h ^ (h >> 16)
    # (h >> 8) < 2^24 is exact in int32 and in f32; the TPU has no
    # direct uint32 -> f32 conversion, so the cast goes through int32
    u = ((h >> 8).astype(jnp.int32).astype(jnp.float32)
         * jnp.float32(1.0 / (1 << 24)))
    return jnp.maximum(u, jnp.float32(1e-7))


def _topk_mask(z, k):
    """Keep lanes >= the k-th largest value of each row (ties kept).

    Bisection invariant: ``count(z >= lo) >= k`` always holds, so the
    final ``z >= lo`` mask never keeps fewer than k lanes.  ``k <= 0``
    means no top-k restriction.
    """
    b, v = z.shape
    k_eff = jnp.clip(jnp.where(k <= 0, v, k), 1, v).astype(jnp.int32)

    def step(_, lo_hi):
        lo, hi = lo_hi
        mid = 0.5 * (lo + hi)
        ge = jnp.sum((z >= mid[:, None]).astype(jnp.int32), axis=-1) >= k_eff
        return jnp.where(ge, mid, lo), jnp.where(ge, hi, mid)

    lo, _ = jax.lax.fori_loop(0, _BISECT_STEPS, step,
                              (jnp.min(z, axis=-1), jnp.max(z, axis=-1)))
    return z >= lo[:, None]


def _topp_mask(z, p):
    """Keep the smallest prefix of probability mass >= p (nucleus).

    Bisection invariant: ``sum(softmax(z)[z > lo]) >= p``, so ``z > lo``
    is the minimal covering set up to value-resolution ties.  ``p >= 1``
    keeps everything.
    """
    m = jnp.max(z, axis=-1, keepdims=True)
    e = jnp.exp(z - m)
    probs = e / jnp.sum(e, axis=-1, keepdims=True)

    def step(_, lo_hi):
        lo, hi = lo_hi
        mid = 0.5 * (lo + hi)
        ge = jnp.sum(jnp.where(z > mid[:, None], probs, 0.0), axis=-1) >= p
        return jnp.where(ge, mid, lo), jnp.where(ge, hi, mid)

    lo, _ = jax.lax.fori_loop(0, _BISECT_STEPS, step,
                              (jnp.min(z, axis=-1) - 1.0, jnp.max(z, axis=-1)))
    return (z > lo[:, None]) | (p >= 1.0)[:, None]


def sample_tokens(logits, temperature, seeds, pos, top_k, top_p):
    """logits (B, V); per-row temperature/seeds/pos/top_k/top_p (B,)
    -> (B,) int32 tokens.  Pure jnp; runs identically as the Pallas
    kernel body and as the whole-batch oracle."""
    x = logits.astype(jnp.float32)
    b, v = x.shape
    temperature = temperature.astype(jnp.float32).reshape(b)
    top_p = top_p.astype(jnp.float32).reshape(b)
    greedy = jnp.argmax(x, axis=-1).astype(jnp.int32)
    u = _uniform_lanes(seeds.reshape(b), pos.reshape(b), b, v)
    gumbel = -jnp.log(-jnp.log(u))
    z = x / jnp.maximum(temperature, 1e-6)[:, None]
    keep = _topk_mask(z, top_k.reshape(b)) & _topp_mask(z, top_p)
    lane = jax.lax.broadcasted_iota(jnp.int32, (b, v), 1)
    keep = keep | (lane == greedy[:, None])     # argmax is always a candidate
    sampled = jnp.argmax(jnp.where(keep, z + gumbel, NEG_INF),
                         axis=-1).astype(jnp.int32)
    return jnp.where(temperature <= 0.0, greedy, sampled)


def fused_sampling_ref(logits, temperature, seeds, pos, top_k, top_p):
    """Oracle for the fused sampling kernel — the same math, unblocked."""
    return sample_tokens(logits, temperature, seeds, pos, top_k, top_p)


def sample_token_host(logits_row, temperature, seed, pos,
                      top_k: int = 0, top_p: float = 1.0) -> int:
    """numpy mirror of :func:`sample_tokens` for one row — the host
    sampling path.  Greedy is bitwise the same argmax; temperature>0
    follows the identical algorithm (hash, bisections, Gumbel-argmax)."""
    x = np.asarray(logits_row, np.float32)
    if temperature <= 0.0:
        return int(np.argmax(x))
    v = x.shape[0]
    base = (int(seed) ^ ((int(pos) * _GOLD) & 0xFFFFFFFF)) & 0xFFFFFFFF
    lane = np.arange(v, dtype=np.uint32)
    h = np.uint32(base) ^ (lane * np.uint32(_MIX1))
    h = h ^ (h >> np.uint32(16))
    h = h * np.uint32(_MIX1)
    h = h ^ (h >> np.uint32(13))
    h = h * np.uint32(_MIX2)
    h = h ^ (h >> np.uint32(16))
    u = (h >> np.uint32(8)).astype(np.float32) * np.float32(1.0 / (1 << 24))
    u = np.maximum(u, np.float32(1e-7))
    gumbel = -np.log(-np.log(u))
    z = x / np.float32(max(float(temperature), 1e-6))
    k_eff = v if top_k <= 0 else min(max(int(top_k), 1), v)
    lo, hi = np.float32(z.min()), np.float32(z.max())
    for _ in range(_BISECT_STEPS):
        mid = np.float32(0.5) * (lo + hi)
        if int(np.sum(z >= mid)) >= k_eff:
            lo = mid
        else:
            hi = mid
    keep = z >= lo
    if top_p < 1.0:
        e = np.exp(z - z.max())
        probs = e / e.sum()
        lo, hi = np.float32(z.min() - 1.0), np.float32(z.max())
        for _ in range(_BISECT_STEPS):
            mid = np.float32(0.5) * (lo + hi)
            if float(probs[z > mid].sum()) >= top_p:
                lo = mid
            else:
                hi = mid
        keep &= z > lo
    keep[int(np.argmax(x))] = True
    return int(np.argmax(np.where(keep, z + gumbel, np.float32(NEG_INF))))
