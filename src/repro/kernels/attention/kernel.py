"""Blocked flash attention (Pallas, TPU target) with GQA and optional
Taylor-softmax (paper Eq. 2) as the exp.

Layout and grid
---------------
    q   (BK, G, S, D)    BK = batch * kv_heads, G = query heads per KV head
    k,v (BK, T, D)
    out (BK, G, S, D)

    grid = (BK, G, num_q_blocks, num_kv_blocks)     kv minor-most

The kv axis is the sequential ("arbitrary") axis: online-softmax running
max ``m``, denominator ``l`` and the output accumulator live in VMEM
scratch and persist across kv grid steps (canonical Pallas-TPU flash
pattern).  Block shapes default to (q=512, kv=512): with D=128 fp32 that is
q 256 KB + k/v 512 KB + acc 256 KB ~ 1.3 MB — comfortably VMEM-resident
with headroom for double buffering.

Causal masking: kv blocks fully above the diagonal are skipped with
``pl.when`` (no MXU work); the diagonal block applies the element mask.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.approx_math import E_A, TAYLOR_COEFFS

NEG_INF = -1e30


def _exp(x, mode: str):
    if mode != "taylor":
        return jnp.exp(x)
    c0, c1, c2, c3, c4, c5 = TAYLOR_COEFFS
    scale = 32.0
    x = jnp.clip(x, -scale, scale) / scale
    p = c4 + c5 * x
    p = c3 + x * p
    p = c2 + x * p
    p = c1 + x * p
    p = c0 + x * p
    y = E_A * p
    for _ in range(5):
        y = y * y
    return y


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  causal: bool, q_offset: int, q_block: int, kv_block: int,
                  n_kv_blocks: int, softmax_mode: str, scale: float):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * q_block + q_offset          # traced (depends on program_id)
    k_start = ki * kv_block

    def _body():
        q = q_ref[0, 0].astype(jnp.float32) * scale     # (Qb, D)
        k = k_ref[0].astype(jnp.float32)                # (Kb, D)
        v = v_ref[0].astype(jnp.float32)                # (Kb, D)
        s = jax.lax.dot_general(
            q, k, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)         # (Qb, Kb)
        if causal:
            qpos = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (q_block, kv_block), 0)
            kpos = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (q_block, kv_block), 1)
            mask = kpos <= qpos
            s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]                             # (Qb,)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        alpha = _exp(m_prev - m_new, softmax_mode)
        p = _exp(s - m_new[:, None], softmax_mode)
        if causal:  # zero lanes the approx exp left non-zero under the mask
            p = jnp.where(mask, p, 0.0)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    if causal:
        # skip kv blocks fully above the causal diagonal
        pl.when(k_start <= q_start + q_block - 1)(_body)
    else:
        _body()

    @pl.when(ki == n_kv_blocks - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_pallas(
    q: jax.Array, k: jax.Array, v: jax.Array,
    causal: bool = True, q_offset: int = 0,
    q_block: int = 512, kv_block: int = 512,
    softmax_mode: str = "exact",
    *,
    interpret: bool,
) -> jax.Array:
    """q (BK, G, S, D), k/v (BK, T, D) -> (BK, G, S, D)."""
    bk, g, s, d = q.shape
    t = k.shape[1]
    qb = min(q_block, s)
    while s % qb:
        qb //= 2
    kb = min(kv_block, t)
    while t % kb:
        kb //= 2
    n_kv = t // kb
    grid = (bk, g, s // qb, n_kv)
    kernel = functools.partial(
        _flash_kernel, causal=causal, q_offset=q_offset, q_block=qb,
        kv_block=kb, n_kv_blocks=n_kv, softmax_mode=softmax_mode,
        scale=1.0 / math.sqrt(d))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, qb, d), lambda b, g_, i, j: (b, g_, i, 0)),
            pl.BlockSpec((1, kb, d), lambda b, g_, i, j: (b, j, 0)),
            pl.BlockSpec((1, kb, d), lambda b, g_, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, qb, d),
                               lambda b, g_, i, j: (b, g_, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bk, g, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((qb,), jnp.float32),
            pltpu.VMEM((qb,), jnp.float32),
            pltpu.VMEM((qb, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)


# ---------------------------------------------------------------------------
# Dequantizing flash attention (int8 KV pages + per-row fp32 scales)
# ---------------------------------------------------------------------------


def _flash_dequant_kernel(q_ref, kq_ref, ks_ref, vq_ref, vs_ref, o_ref,
                          m_scr, l_scr, acc_scr, *,
                          causal: bool, q_offset: int, q_block: int,
                          kv_block: int, n_kv_blocks: int,
                          softmax_mode: str, scale: float):
    """The flash body of :func:`_flash_kernel` with int8 KV blocks
    dequantized on read: each KV block streams in as int8 plus its
    per-row fp32 scales, laid out (1, Kb) so the KV axis is the lane
    axis.  A row scale multiplies its score column (s = (q . kq) * ks)
    and its probability column (p * vs) — the same products as scaling
    the rows, with no fp32 copy of the block — and the resident cache
    stays int8 end to end."""
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * q_block + q_offset
    k_start = ki * kv_block

    def _body():
        q = q_ref[0, 0].astype(jnp.float32) * scale              # (Qb, D)
        k = kq_ref[0].astype(jnp.float32)                        # (Kb, D)
        v = vq_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * ks_ref[0]      # (Qb, Kb)
        if causal:
            qpos = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (q_block, kv_block), 0)
            kpos = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (q_block, kv_block), 1)
            mask = kpos <= qpos
            s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        alpha = _exp(m_prev - m_new, softmax_mode)
        p = _exp(s - m_new[:, None], softmax_mode)
        if causal:
            p = jnp.where(mask, p, 0.0)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            p * vs_ref[0], v, dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    if causal:
        pl.when(k_start <= q_start + q_block - 1)(_body)
    else:
        _body()

    @pl.when(ki == n_kv_blocks - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_dequant_pallas(
    q: jax.Array, kq: jax.Array, ks: jax.Array,
    vq: jax.Array, vs: jax.Array,
    causal: bool = True, q_offset: int = 0,
    q_block: int = 512, kv_block: int = 512, page_size: int = 1,
    softmax_mode: str = "exact",
    *,
    interpret: bool,
) -> jax.Array:
    """q (BK, G, S, D); kq/vq (BK, T, D) int8; ks/vs (BK, T) fp32.

    ``page_size`` is the paged-cache page length the KV axis was written
    in: KV blocks are kept page-aligned (``kv_block`` a multiple of
    ``page_size`` whenever the sequence allows it), so a block's scale
    rows never straddle a partially-resident page.
    """
    bk, g, s, d = q.shape
    t = kq.shape[1]
    qb = min(q_block, s)
    while s % qb:
        qb //= 2
    ps = max(int(page_size), 1)
    while t % ps:                      # degrade like the block sizes do
        ps = max(ps // 2, 1)
    kb = max(min(kv_block, t) // ps * ps, ps)
    while t % kb:
        kb -= ps
    if kb % 128:                       # lane-axis scale blocks: (1, kb)
        kb = t
    n_kv = t // kb
    grid = (bk, g, s // qb, n_kv)
    kernel = functools.partial(
        _flash_dequant_kernel, causal=causal, q_offset=q_offset, q_block=qb,
        kv_block=kb, n_kv_blocks=n_kv, softmax_mode=softmax_mode,
        scale=1.0 / math.sqrt(d))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, qb, d), lambda b, g_, i, j: (b, g_, i, 0)),
            pl.BlockSpec((1, kb, d), lambda b, g_, i, j: (b, j, 0)),
            pl.BlockSpec((1, 1, kb), lambda b, g_, i, j: (b, 0, j)),
            pl.BlockSpec((1, kb, d), lambda b, g_, i, j: (b, j, 0)),
            pl.BlockSpec((1, 1, kb), lambda b, g_, i, j: (b, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, 1, qb, d),
                               lambda b, g_, i, j: (b, g_, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bk, g, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((qb,), jnp.float32),
            pltpu.VMEM((qb,), jnp.float32),
            pltpu.VMEM((qb, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, kq, ks.astype(jnp.float32).reshape(bk, 1, t), vq,
      vs.astype(jnp.float32).reshape(bk, 1, t))


# ---------------------------------------------------------------------------
# Decode attention (q_len = 1, ragged kv_valid_len, dense or paged cache)
# ---------------------------------------------------------------------------
#
# Layout: q (B, K, G, D) — one query token per slot, grouped heads.
#   dense cache   k/v (B, T, K, D);  grid (B/slot_block, T/kv_block)
#   paged cache   k/v are pool leaves (n_pages, page, K, D); the per-slot
#                 page tables ride in as a scalar-prefetch operand and the
#                 kv BlockSpec index_map reads ``tables[slot, page_idx]``
#                 directly, so blocks stream straight out of the pool with
#                 no gather-to-dense materialization; grid (B, P)
#
# Every grid step carries ALL KV heads: K/V blocks are (sb, kb, K, D), so
# the two minor block dims (K, D) are the array's own — the TPU tiling
# rule (last two block dims divisible by (8, 128) or full) holds for any
# head count — and the kernel loops over heads statically.  int8 row
# scales arrive as (B, 1, T) / (n_pages, 1, page) so the KV axis is the
# lane axis; they scale the scores and probabilities of their KV column
# (s = (q . kq) * ks, p * vs) instead of the cache rows.
#
# The kv axis stays minor-most/sequential; m/l/acc scratch persists across
# kv steps exactly like the flash kernels above.  ``kv_valid_len`` masks
# ragged tails (and, paged, any sentinel page past the write head); the
# lengths also ride in as scalar prefetch, and blocks entirely past every
# slot's valid length are skipped with ``pl.when`` — for the paged grid
# (slot_block=1) that means a slot only ever touches its own resident
# pages.  NEG_INF is finite, so fully-masked rows keep m = NEG_INF, l = 0
# without NaNs and finish as zeros.


def _decode_kernel(lens_ref, *refs, quant: bool, n_prefetch: int,
                   slot_block: int, kv_block: int, n_kv_blocks: int,
                   n_kv_heads: int, softmax_mode: str, scale: float):
    refs = refs[n_prefetch - 1:]          # page tables feed index_maps only
    if quant:
        (q_ref, k_ref, ks_ref, v_ref, vs_ref, valid_ref, o_ref,
         m_scr, l_scr, acc_scr) = refs
    else:
        q_ref, k_ref, v_ref, valid_ref, o_ref, m_scr, l_scr, acc_scr = refs
    si = pl.program_id(0)
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    k_start = ki * kv_block
    longest = functools.reduce(
        jnp.maximum, [lens_ref[si * slot_block + s] for s in range(slot_block)])

    @pl.when(k_start < longest)
    def _body():
        g = q_ref.shape[2]
        kpos = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (slot_block, g, kv_block), 2)
        mask = kpos < valid_ref[...]                    # (Sb, G, Kb)
        for h in range(n_kv_heads):
            q = q_ref[:, h].astype(jnp.float32) * scale  # (Sb, G, D)
            k = k_ref[:, :, h, :].astype(jnp.float32)    # (Sb, Kb, D)
            v = v_ref[:, :, h, :].astype(jnp.float32)
            s = jax.lax.dot_general(
                q, k, dimension_numbers=(((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)      # (Sb, G, Kb)
            if quant:
                s = s * ks_ref[...]                      # (Sb, 1, Kb)
            s = jnp.where(mask, s, NEG_INF)
            m_prev = m_scr[h]                            # (Sb, G, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = _exp(m_prev - m_new, softmax_mode)
            p = jnp.where(mask, _exp(s - m_new, softmax_mode), 0.0)
            l_scr[h] = l_scr[h] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            if quant:
                p = p * vs_ref[...]
            acc_scr[h] = acc_scr[h] * alpha + jax.lax.dot_general(
                p, v, dimension_numbers=(((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)      # (Sb, G, D)
            m_scr[h] = m_new

    @pl.when(ki == n_kv_blocks - 1)
    def _finish():
        for h in range(n_kv_heads):
            l = jnp.maximum(l_scr[h], 1e-30)
            o_ref[:, h] = (acc_scr[h] / l).astype(o_ref.dtype)


def _decode_blocks(b: int, t: int, kv_block: int, slot_block: int,
                   quant: bool = False):
    sb = max(min(int(slot_block), b), 1)
    while b % sb:
        sb -= 1
    kb = max(min(int(kv_block), t), 1)
    while t % kb:
        kb //= 2
    if quant and kb % 128:
        kb = t            # lane-axis scale blocks: 128-multiple or whole
    return sb, kb


def _decode_call(q, k, v, kv_valid_len, scales, tables, *, kv_block: int,
                 slot_block: int, softmax_mode: str, interpret: bool):
    """Shared pallas_call for the four decode variants.  ``scales`` is
    None or (ks, vs); ``tables`` is None (dense) or the (B, P) page table
    (k/v are then pool leaves and each grid step reads one page)."""
    b, nkv, g, d = q.shape
    quant = scales is not None
    lens = kv_valid_len.astype(jnp.int32).reshape(b)
    if tables is None:
        t = k.shape[1]
        sb, kb = _decode_blocks(b, t, kv_block, slot_block, quant)
        n_kv = t // kb
        prefetch = (lens,)

        def block(si, ji, *_):        # (row, kv block) of the slot cache
            return si, ji
    else:
        sb, kb = 1, k.shape[1]
        n_kv = tables.shape[1]
        prefetch = (lens, tables.astype(jnp.int32))

        def block(si, ji, lens_ref, tb):   # (pool page, first row)
            return tb[si, ji], 0

    def kv_map(si, ji, *refs):
        row, col = block(si, ji, *refs)
        return row, col, 0, 0

    def scale_map(si, ji, *refs):
        row, col = block(si, ji, *refs)
        return row, 0, col

    def slot_map(si, ji, *_):
        return si, 0, 0, 0

    def valid_map(si, ji, *_):
        return si, 0, 0

    kv_spec = pl.BlockSpec((sb, kb, nkv, d), kv_map)
    operands, in_specs = [q], [pl.BlockSpec((sb, nkv, g, d), slot_map)]
    if quant:
        ks, vs = (x.astype(jnp.float32).reshape(x.shape[0], 1, x.shape[1])
                  for x in scales)
        scale_spec = pl.BlockSpec((sb, 1, kb), scale_map)
        operands += [k, ks, v, vs]
        in_specs += [kv_spec, scale_spec, kv_spec, scale_spec]
    else:
        operands += [k, v]
        in_specs += [kv_spec, kv_spec]
    operands.append(lens.reshape(b, 1, 1))
    in_specs.append(pl.BlockSpec((sb, 1, 1), valid_map))
    kernel = functools.partial(
        _decode_kernel, quant=quant, n_prefetch=len(prefetch),
        slot_block=sb, kv_block=kb, n_kv_blocks=n_kv, n_kv_heads=nkv,
        softmax_mode=softmax_mode, scale=1.0 / math.sqrt(d))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(b // sb, n_kv),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((sb, nkv, g, d), slot_map),
        scratch_shapes=[pltpu.VMEM((nkv, sb, g, 1), jnp.float32),
                        pltpu.VMEM((nkv, sb, g, 1), jnp.float32),
                        pltpu.VMEM((nkv, sb, g, d), jnp.float32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, nkv, g, d), q.dtype),
        interpret=interpret,
    )(*prefetch, *operands)


def decode_attention_pallas(
    q: jax.Array, k: jax.Array, v: jax.Array, kv_valid_len: jax.Array, *,
    kv_block: int = 512, slot_block: int = 1,
    softmax_mode: str = "exact", interpret: bool,
) -> jax.Array:
    """q (B, K, G, D); k/v (B, T, K, D); kv_valid_len (B,) -> (B, K, G, D)."""
    return _decode_call(q, k, v, kv_valid_len, None, None,
                        kv_block=kv_block, slot_block=slot_block,
                        softmax_mode=softmax_mode, interpret=interpret)


def decode_attention_dequant_pallas(
    q: jax.Array, kq: jax.Array, ks: jax.Array,
    vq: jax.Array, vs: jax.Array, kv_valid_len: jax.Array, *,
    kv_block: int = 512, slot_block: int = 1,
    softmax_mode: str = "exact", interpret: bool,
) -> jax.Array:
    """q (B, K, G, D); kq/vq (B, T, K, D) int8; ks/vs (B, T) fp32."""
    return _decode_call(q, kq, vq, kv_valid_len, (ks, vs), None,
                        kv_block=kv_block, slot_block=slot_block,
                        softmax_mode=softmax_mode, interpret=interpret)


def decode_attention_paged_pallas(
    q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
    kv_valid_len: jax.Array, tables: jax.Array, *,
    softmax_mode: str = "exact", interpret: bool,
) -> jax.Array:
    """q (B, K, G, D); k/v pool leaves (n_pages, page, K, D); ``tables``
    (B, P) pre-clipped page ids (scalar-prefetch operand, read inside the
    kv index_maps); kv_valid_len (B,) slot-local lengths."""
    return _decode_call(q, k_pages, v_pages, kv_valid_len, None, tables,
                        kv_block=k_pages.shape[1], slot_block=1,
                        softmax_mode=softmax_mode, interpret=interpret)


def decode_attention_paged_dequant_pallas(
    q: jax.Array, k_pages: jax.Array, ks_pages: jax.Array,
    v_pages: jax.Array, vs_pages: jax.Array,
    kv_valid_len: jax.Array, tables: jax.Array, *,
    softmax_mode: str = "exact", interpret: bool,
) -> jax.Array:
    """Paged decode over int8 pool leaves with per-row fp32 scale leaves
    (n_pages, page) — dequantized block-at-a-time on read."""
    return _decode_call(q, k_pages, v_pages, kv_valid_len,
                        (ks_pages, vs_pages), tables,
                        kv_block=k_pages.shape[1], slot_block=1,
                        softmax_mode=softmax_mode, interpret=interpret)
