"""Unified kernel registry: typed specs + capability-probed dispatch.

This generalizes the capability-probe design of
``repro.deploy.registry`` from routing variants to *every* Pallas kernel
in the repo.  One :class:`KernelSpec` per kernel declares:

  * ``build()`` — the jitted Pallas entry point (lazy import, so merely
    importing ``repro.kernels`` never pulls ``jax.experimental.pallas``);
  * ``reference()`` — the pure-jnp oracle with the same semantics;
  * ``is_available()`` — the capability probe (Pallas importable;
    dispatch raises when it is false);
  * ``space`` — the FastCaps design space for this kernel: the tunable
    block sizes (measured by :mod:`repro.kernels.tuning`) plus the
    numerics-changing knobs (``softmax_mode``) that benchmarks and the
    parity harness sweep but the timing tuner never flips;
  * ``legalize`` — shape-aware config legalization (every block size
    becomes a divisor of its dimension via ``largest_divisor``);
  * ``example_cases`` / ``make_example`` — canonical inputs shared by
    the parity tests, the selfcheck CLI and the pretuner.

Dispatch (:meth:`KernelRegistry.call`) resolves, in order: explicit
per-call overrides > tuned config from the on-disk cache (when the
:func:`repro.kernels.tuning.tuning` scope or ``tune=`` asks for it) >
the deterministic legalized defaults (the ``tune=False`` CI path).
Backend capability (``interpret`` mode off-TPU) is probed in exactly one
place — :func:`repro.kernels.tuning.needs_interpret`.  A kernel whose
Pallas toolchain is unavailable raises at dispatch: nothing silently
swaps in the reference oracle, so a run that reports a kernel ran it.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Mapping, Optional, Tuple

from repro.kernels import tuning
from repro.kernels.tuning import largest_divisor, needs_interpret  # noqa: F401


@dataclasses.dataclass(frozen=True)
class KernelSpec:
    """One registered kernel: impls, probe, and its tunable design space.

    ``space`` maps every design-space knob to its candidate values;
    ``tuned`` names the subset the measured autotuner may vary (block
    sizes — numerics-preserving by construction).  ``base_config`` holds
    the historical hard-coded values; ``legalize(config, *args, **kw)``
    clamps a candidate to the concrete shapes (divisibility).  The
    ``example_cases`` dicts drive the registry-wide parity harness and
    the pretune CLI: ``make_example(case) -> (args, kwargs)``.
    """

    name: str
    build: Callable[[], Callable[..., Any]]
    reference: Callable[[], Callable[..., Any]]
    space: Mapping[str, tuple]
    tuned: Tuple[str, ...]
    base_config: Mapping[str, Any]
    legalize: Callable[..., Dict[str, Any]]
    make_example: Callable[[Mapping[str, Any]], Tuple[tuple, dict]]
    example_cases: Tuple[Mapping[str, Any], ...] = ()
    ref_accepts: Tuple[str, ...] = ()     # semantic kwargs the oracle takes
    is_available: Callable[[], bool] = lambda: True
    #: tuned key -> the array dimension it must divide, evaluated on the
    #: call arguments (ShapeDtypeStructs suffice — only ``.shape`` is
    #: read).  ``legalize`` is derived from this via
    #: :func:`_legalize_blocks`, so the capslint kernel-legality checker
    #: verifies the *same* dimension mapping dispatch uses.
    block_dims: Optional[Callable[..., Dict[str, int]]] = None
    #: cross-knob divisibility constraints: each ``(a, b)`` pair declares
    #: that the legalized ``config[a]`` must divide ``config[b]`` (e.g. a
    #: paged-cache ``page_size`` dividing the ``kv_block`` so KV blocks
    #: stay page-aligned).  ``_legalize_blocks`` enforces the pairs and
    #: the capslint kernel-legality checker proves them on every tuner
    #: candidate.
    block_divisors: Tuple[Tuple[str, str], ...] = ()

    def ref_call(self, *args, **kwargs):
        """Invoke the jnp oracle, filtering kwargs it does not accept."""
        fn = self.reference()
        return fn(*args, **{k: v for k, v in kwargs.items()
                            if k in self.ref_accepts})


class KernelRegistry:
    """Name -> :class:`KernelSpec`; resolution + dispatch."""

    def __init__(self):
        self._specs: Dict[str, KernelSpec] = {}

    def register(self, spec: KernelSpec) -> KernelSpec:
        self._specs[spec.name] = spec
        return spec

    def names(self):
        return sorted(self._specs)

    def get(self, name: str) -> KernelSpec:
        try:
            return self._specs[name]
        except KeyError:
            raise ValueError(f"unknown kernel {name!r}; registered: "
                             f"{self.names()}") from None

    # -- config resolution -------------------------------------------------

    def default_config(self, name: str, *args, **kwargs) -> Dict[str, Any]:
        """The deterministic ``tune=False`` config for these shapes."""
        spec = self.get(name)
        return spec.legalize(dict(spec.base_config), *args, **kwargs)

    def resolve_config(self, name: str, *args,
                       overrides: Optional[Dict[str, Any]] = None,
                       tune: Optional[bool] = None, **kwargs
                       ) -> Dict[str, Any]:
        """Overrides > tuned cache entry (if tuning) > legalized defaults.

        With tuning on and a cache miss, concrete arguments trigger a
        measured :func:`repro.kernels.tuning.autotune` on the spot;
        tracers (dispatch at ``jax.jit`` trace time) only read the cache.
        """
        spec = self.get(name)
        config = spec.legalize(dict(spec.base_config), *args, **kwargs)
        use_tune = tune if tune is not None else tuning.tune_enabled()
        if use_tune:
            cache = tuning.default_cache()
            cached = cache.get(tuning.cache_key_for(spec, args))
            if cached is None and _all_concrete(args):
                cached, _ = tuning.autotune(spec, args, kwargs, cache=cache)
            if cached is not None:
                merged = dict(spec.base_config)
                merged.update(cached)
                config = spec.legalize(merged, *args, **kwargs)
        if overrides:
            config.update({k: v for k, v in overrides.items()
                           if v is not None})
            config = spec.legalize(config, *args, **kwargs)
        return config

    # -- dispatch ----------------------------------------------------------

    def call(self, name: str, *args,
             config: Optional[Dict[str, Any]] = None,
             interpret: Optional[bool] = None,
             tune: Optional[bool] = None, **kwargs) -> Any:
        """Dispatch ``name`` on ``args``: the Pallas impl with the resolved
        config.  ``kwargs`` are semantic (``n_iters``, ``softmax_mode``,
        ``causal``, ...); tunable overrides ride in ``config``.  Raises
        :class:`RuntimeError` when the kernel is unavailable — the oracle
        is for parity checks, never a stand-in."""
        spec = self.get(name)
        if not spec.is_available():
            raise RuntimeError(
                f"kernel {name!r} is unavailable on this installation "
                f"(jax.experimental.pallas cannot be imported)")
        resolved = self.resolve_config(name, *args, overrides=config,
                                       tune=tune, **kwargs)
        if interpret is None:
            interpret = needs_interpret()
        return spec.build()(*args, interpret=interpret, **kwargs, **resolved)


def _all_concrete(args) -> bool:
    import jax

    return not any(isinstance(a, jax.core.Tracer) for a in args)


def _pallas_available() -> bool:
    try:
        from jax.experimental import pallas  # noqa: F401
    except ImportError:
        return False
    return True


def _legalize_blocks(dims_fn: Callable[..., Dict[str, int]],
                     divisors: Tuple[Tuple[str, str], ...] = ()
                     ) -> Callable[..., Dict[str, Any]]:
    """Build a spec ``legalize`` from its ``block_dims`` mapping: every
    block-size key becomes ``largest_divisor(dim, requested)``.  Keeping
    legalization derived from the dimension map (rather than hand-written
    per kernel) is what lets ``repro.analysis``'s kernel-legality rule
    *prove* divisibility — the checker evaluates the same ``dims_fn``.

    ``divisors`` pairs (the spec's ``block_divisors``) are enforced
    after the divisor pass: for each ``(a, b)``, ``config[a]`` is first
    clamped to divide ``b``'s dimension, then ``config[b]`` is walked
    down in ``config[a]``-sized steps until it both divides the
    dimension and is a multiple of ``config[a]`` — so e.g. a KV block
    never straddles a cache-page boundary.  The procedure is idempotent
    (a requirement the legality checker's ``unstable-legalize`` rule
    enforces)."""

    def legalize(config: Dict[str, Any], *args, **kwargs) -> Dict[str, Any]:
        dims = dims_fn(*args, **kwargs)
        for key, dim in dims.items():
            config[key] = largest_divisor(dim, config[key])
        for a, b in divisors:
            dim = dims.get(b)
            va = int(config[a])
            if dim is not None:
                va = largest_divisor(dim, va)
                config[a] = va
            vb = max(int(config[b]), va)
            vb = vb // va * va
            if dim is not None:
                while vb > va and dim % vb:
                    vb -= va
            config[b] = vb
        return config

    return legalize


# ---------------------------------------------------------------------------
# Registered kernels (jitted entry points live here; the kernel packages
# keep only the Pallas bodies and the jnp oracles)
# ---------------------------------------------------------------------------

registry = KernelRegistry()


def _rand(seed: int, shape, dtype="float32", scale: float = 1.0):
    import jax
    import jax.numpy as jnp

    x = jax.random.normal(jax.random.key(seed), shape) * scale
    return x.astype(jnp.dtype(dtype))


# -- fused_routing ----------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _build_fused_routing():
    import jax

    from repro.kernels.routing.routing_kernel import fused_routing_pallas

    @functools.partial(jax.jit, static_argnames=(
        "n_iters", "softmax_mode", "batch_block", "interpret"))
    def fused_routing_entry(u_hat, n_iters=3, softmax_mode="exact",
                            batch_block=8, *, interpret):
        return fused_routing_pallas(
            u_hat, n_iters=n_iters, softmax_mode=softmax_mode,
            batch_block=batch_block, interpret=interpret)

    return fused_routing_entry


def _routing_reference():
    from repro.kernels.routing.ref import fused_routing_ref

    return fused_routing_ref


def _routing_block_dims(u_hat, **kwargs):
    return {"batch_block": u_hat.shape[0]}


def _routing_example(case):
    shape = case.get("shape", (4, 10, 16, 24))           # (B, J, D, I)
    u = _rand(case.get("seed", 0), shape, case.get("dtype", "float32"),
              scale=0.2)
    return (u,), {"n_iters": case.get("n_iters", 3),
                  "softmax_mode": case.get("softmax_mode", "exact")}


registry.register(KernelSpec(
    name="fused_routing",
    build=_build_fused_routing,
    reference=_routing_reference,
    space={"batch_block": (1, 2, 4, 8, 16),
           "softmax_mode": ("exact", "taylor")},
    tuned=("batch_block",),
    base_config={"batch_block": 8},
    legalize=_legalize_blocks(_routing_block_dims),
    block_dims=_routing_block_dims,
    make_example=_routing_example,
    example_cases=(
        {"shape": (4, 10, 16, 24), "softmax_mode": "exact", "atol": 1e-5},
        {"shape": (9, 10, 16, 30), "softmax_mode": "exact", "atol": 1e-5},
        {"shape": (6, 5, 8, 36), "softmax_mode": "taylor", "atol": 1e-4},
        {"shape": (3, 10, 16, 252), "softmax_mode": "taylor", "atol": 1e-4},
    ),
    ref_accepts=("n_iters", "softmax_mode"),
    is_available=_pallas_available,
))


# -- taylor_softmax ---------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _build_taylor_softmax():
    import jax

    from repro.kernels.softmax.kernel import taylor_softmax_pallas

    @functools.partial(jax.jit, static_argnames=("row_block", "interpret"))
    def taylor_softmax_entry(x, row_block=256, *, interpret):
        return taylor_softmax_pallas(x, row_block=row_block,
                                     interpret=interpret)

    return taylor_softmax_entry


def _softmax_reference():
    from repro.kernels.softmax.ref import taylor_softmax_ref

    return taylor_softmax_ref


def _softmax_block_dims(x, **kwargs):
    rows = 1
    for d in x.shape[:-1]:
        rows *= d
    return {"row_block": rows}


def _softmax_example(case):
    shape = case.get("shape", (8, 16))
    x = _rand(case.get("seed", 0), shape, case.get("dtype", "float32"),
              scale=case.get("scale", 5.0))
    return (x,), {}


registry.register(KernelSpec(
    name="taylor_softmax",
    build=_build_taylor_softmax,
    reference=_softmax_reference,
    space={"row_block": (32, 64, 128, 256, 512)},
    tuned=("row_block",),
    base_config={"row_block": 256},
    legalize=_legalize_blocks(_softmax_block_dims),
    block_dims=_softmax_block_dims,
    make_example=_softmax_example,
    example_cases=(
        {"shape": (8, 16), "atol": 1e-6},
        {"shape": (33, 250), "atol": 1e-6},          # odd/ragged rows
        {"shape": (4, 7, 64), "atol": 1e-6},
        {"shape": (1, 1024), "atol": 1e-6},
        {"shape": (16, 64), "dtype": "bfloat16", "scale": 3.0,
         "atol": 1e-2},
    ),
    ref_accepts=(),
    is_available=_pallas_available,
))


# -- flash_attention --------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _build_flash_attention():
    import jax

    from repro.kernels.attention.kernel import flash_attention_pallas

    @functools.partial(jax.jit, static_argnames=(
        "causal", "q_offset", "q_block", "kv_block", "softmax_mode",
        "interpret"))
    def flash_attention_entry(q, k, v, causal=True, q_offset=0,
                              softmax_mode="exact", q_block=512,
                              kv_block=512, *, interpret):
        """(B, S, H, D) GQA API over the (BK, G, S, D) flash kernel."""
        b, s, h, d = q.shape
        t, nkv = k.shape[1], k.shape[2]
        g = h // nkv
        qr = (q.reshape(b, s, nkv, g, d).transpose(0, 2, 3, 1, 4)
              .reshape(b * nkv, g, s, d))
        kr = k.transpose(0, 2, 1, 3).reshape(b * nkv, t, d)
        vr = v.transpose(0, 2, 1, 3).reshape(b * nkv, t, d)
        o = flash_attention_pallas(
            qr, kr, vr, causal=causal, q_offset=q_offset, q_block=q_block,
            kv_block=kv_block, softmax_mode=softmax_mode,
            interpret=interpret)
        return (o.reshape(b, nkv, g, s, d).transpose(0, 3, 1, 2, 4)
                .reshape(b, s, h, d))

    return flash_attention_entry


def _attention_reference():
    from repro.kernels.attention.ref import attention_ref

    return attention_ref


def _attention_block_dims(q, k=None, v=None, **kwargs):
    s = q.shape[1]
    t = k.shape[1] if k is not None else s
    return {"q_block": s, "kv_block": t}


def _attention_example(case):
    b, s, t, h, k, d = case.get("dims", (2, 128, 128, 4, 2, 32))
    dtype = case.get("dtype", "float32")
    q = _rand(case.get("seed", 0), (b, s, h, d), dtype)
    kk = _rand(case.get("seed", 0) + 1, (b, t, k, d), dtype)
    v = _rand(case.get("seed", 0) + 2, (b, t, k, d), dtype)
    return (q, kk, v), {"causal": case.get("causal", True),
                        "q_offset": case.get("q_offset", 0),
                        "softmax_mode": case.get("softmax_mode", "exact")}


registry.register(KernelSpec(
    name="flash_attention",
    build=_build_flash_attention,
    reference=_attention_reference,
    space={"q_block": (64, 128, 256, 512),
           "kv_block": (64, 128, 256, 512),
           "softmax_mode": ("exact", "taylor")},
    tuned=("q_block", "kv_block"),
    base_config={"q_block": 512, "kv_block": 512},
    legalize=_legalize_blocks(_attention_block_dims),
    block_dims=_attention_block_dims,
    make_example=_attention_example,
    example_cases=(
        {"dims": (2, 128, 128, 8, 4, 32), "causal": True, "atol": 2e-5},
        {"dims": (2, 64, 256, 8, 2, 32), "causal": False, "atol": 2e-5},
        {"dims": (1, 192, 192, 2, 1, 64), "causal": True,
         "atol": 2e-5},                               # non-pow2 seq
        {"dims": (1, 64, 256, 4, 2, 32), "causal": True, "q_offset": 192,
         "atol": 2e-5},                               # decode window
        {"dims": (1, 128, 128, 4, 2, 32), "softmax_mode": "taylor",
         "atol": 5e-2},                # vs exact oracle: approx-exp bound
    ),
    ref_accepts=("causal", "q_offset"),
    is_available=_pallas_available,
))


# -- flash_attention_dequant ------------------------------------------------
# Dequant-on-read attention over int8 KV pages (repro.serving.pages):
# k/v arrive quantized with per-row fp32 scales and are dequantized
# block-at-a-time inside the kernel, so the resident cache stays int8.
# ``page_size`` is a structural knob (the pool's page length, not
# tuned); ``block_divisors`` keeps the KV block a multiple of it, so a
# block's scale rows never straddle a page boundary.


@functools.lru_cache(maxsize=None)
def _build_flash_attention_dequant():
    import jax
    import jax.numpy as jnp

    from repro.kernels.attention.kernel import flash_attention_dequant_pallas

    @functools.partial(jax.jit, static_argnames=(
        "causal", "q_offset", "q_block", "kv_block", "page_size",
        "softmax_mode", "interpret"))
    def flash_attention_dequant_entry(q, kq, ks, vq, vs, causal=True,
                                      q_offset=0, softmax_mode="exact",
                                      q_block=512, kv_block=512,
                                      page_size=64, *, interpret):
        """(B, S, H, D) GQA API over the int8-KV flash kernel; scales
        (B, T) are shared by the KV heads (per-row quantization)."""
        b, s, h, d = q.shape
        t, nkv = kq.shape[1], kq.shape[2]
        g = h // nkv
        qr = (q.reshape(b, s, nkv, g, d).transpose(0, 2, 3, 1, 4)
              .reshape(b * nkv, g, s, d))
        kr = kq.transpose(0, 2, 1, 3).reshape(b * nkv, t, d)
        vr = vq.transpose(0, 2, 1, 3).reshape(b * nkv, t, d)
        ksr = jnp.repeat(ks.astype(jnp.float32), nkv, axis=0)
        vsr = jnp.repeat(vs.astype(jnp.float32), nkv, axis=0)
        o = flash_attention_dequant_pallas(
            qr, kr, ksr, vr, vsr, causal=causal, q_offset=q_offset,
            q_block=q_block, kv_block=kv_block, page_size=page_size,
            softmax_mode=softmax_mode, interpret=interpret)
        return (o.reshape(b, nkv, g, s, d).transpose(0, 3, 1, 2, 4)
                .reshape(b, s, h, d))

    return flash_attention_dequant_entry


def _attention_dequant_reference():
    from repro.kernels.attention.ref import attention_dequant_ref

    return attention_dequant_ref


def _attention_dequant_block_dims(q, kq=None, ks=None, vq=None, vs=None,
                                  **kwargs):
    s = q.shape[1]
    t = kq.shape[1] if kq is not None else s
    return {"q_block": s, "kv_block": t}


def _attention_dequant_example(case):
    import jax.numpy as jnp

    from repro.models.attention import quantize_kv_rows

    b, s, t, h, k, d = case.get("dims", (2, 128, 128, 4, 2, 32))
    q = _rand(case.get("seed", 0), (b, s, h, d), "float32")
    kk = _rand(case.get("seed", 0) + 1, (b, t, k, d), "float32")
    v = _rand(case.get("seed", 0) + 2, (b, t, k, d), "float32")
    kq, ks = quantize_kv_rows(kk)
    vq, vs = quantize_kv_rows(v)
    return ((q, kq.astype(jnp.int8), ks, vq.astype(jnp.int8), vs),
            {"causal": case.get("causal", True),
             "q_offset": case.get("q_offset", 0),
             "softmax_mode": case.get("softmax_mode", "exact")})


registry.register(KernelSpec(
    name="flash_attention_dequant",
    build=_build_flash_attention_dequant,
    reference=_attention_dequant_reference,
    space={"q_block": (64, 128, 256, 512),
           "kv_block": (64, 128, 256, 512),
           "page_size": (8, 16, 32, 64, 128),
           "softmax_mode": ("exact", "taylor")},
    tuned=("q_block", "kv_block"),
    base_config={"q_block": 512, "kv_block": 512, "page_size": 64},
    legalize=_legalize_blocks(_attention_dequant_block_dims,
                              divisors=(("page_size", "kv_block"),)),
    block_dims=_attention_dequant_block_dims,
    block_divisors=(("page_size", "kv_block"),),
    make_example=_attention_dequant_example,
    example_cases=(
        # parity vs the dequantizing oracle is tight: both read the same
        # int8 rows, so quantization error cancels and only the online
        # softmax differs.  (The *quantization* tolerance vs an
        # unquantized cache is asserted end-to-end in the serving tests.)
        {"dims": (2, 128, 128, 8, 4, 32), "causal": True, "atol": 2e-5},
        {"dims": (2, 64, 256, 8, 2, 32), "causal": False, "atol": 2e-5},
        {"dims": (1, 64, 256, 4, 2, 32), "causal": True, "q_offset": 192,
         "atol": 2e-5},                               # decode window
        {"dims": (1, 192, 192, 2, 1, 64), "causal": True,
         "atol": 2e-5},                               # non-pow2 seq
    ),
    ref_accepts=("causal", "q_offset"),
    is_available=_pallas_available,
))


# -- decode_attention -------------------------------------------------------
# q_len=1 serving decode: one query token per slot against a ragged KV
# cache (``kv_valid_len`` masks each slot's tail).  The cache arrives
# either dense (B, T, K, D) — optionally int8 with (B, T) row scales —
# or as the paged pool's native (n_pages, page, K, D) leaves plus the
# per-slot page ``tables`` (B, P): the kernel reads pages through the
# table as a scalar-prefetch operand, so serving skips the
# gather-to-dense materialization entirely.  ``page_size`` is structural
# (the pool's page length); ``block_divisors`` keeps the dense-path KV
# block page-aligned exactly like ``flash_attention_dequant``.


@functools.lru_cache(maxsize=None)
def _build_decode_attention():
    import jax

    from repro.kernels.attention.kernel import (
        decode_attention_dequant_pallas,
        decode_attention_paged_dequant_pallas, decode_attention_paged_pallas,
        decode_attention_pallas)

    @functools.partial(jax.jit, static_argnames=(
        "softmax_mode", "kv_block", "slot_block", "page_size", "interpret"))
    def decode_attention_entry(q, k, v, kv_valid_len, tables=None, ks=None,
                               vs=None, softmax_mode="exact", kv_block=512,
                               slot_block=1, page_size=64, *, interpret):
        """(B, 1, H, D) GQA decode API over the (B, K, G, D) kernels.

        Dense cache: k/v (B, T, K, D) (+ optional int8 scales ks/vs
        (B, T)).  Paged cache: k/v are pool leaves (n_pages, page, K, D)
        (+ optional pool scale leaves (n_pages, page)) and ``tables``
        (B, P) holds pre-clipped page ids.
        """
        b, s, h, d = q.shape
        nkv = k.shape[-2]
        g = h // nkv
        qr = q.reshape(b, nkv, g, d)
        # `tables`/`ks` being None is pytree *structure*, fixed at trace
        # time (jit retraces when an optional cache input appears) — the
        # branches below never inspect a tracer's value.
        # capslint: disable=jit-purity — None-vs-array is static structure
        if tables is not None:
            # capslint: disable=jit-purity — None-vs-array is static
            if ks is not None:
                o = decode_attention_paged_dequant_pallas(
                    qr, k, ks, v, vs, kv_valid_len, tables,
                    softmax_mode=softmax_mode, interpret=interpret)
            else:
                o = decode_attention_paged_pallas(
                    qr, k, v, kv_valid_len, tables,
                    softmax_mode=softmax_mode, interpret=interpret)
        # capslint: disable=jit-purity — None-vs-array is static
        elif ks is not None:
            o = decode_attention_dequant_pallas(
                qr, k, ks, v, vs, kv_valid_len, kv_block=kv_block,
                slot_block=slot_block, softmax_mode=softmax_mode,
                interpret=interpret)
        else:
            o = decode_attention_pallas(
                qr, k, v, kv_valid_len, kv_block=kv_block,
                slot_block=slot_block, softmax_mode=softmax_mode,
                interpret=interpret)
        return o.reshape(b, 1, h, d)

    return decode_attention_entry


def _decode_attention_reference():
    from repro.kernels.attention.ref import decode_attention_ref

    return decode_attention_ref


def _decode_attention_block_dims(q, k=None, v=None, kv_valid_len=None,
                                 tables=None, **kwargs):
    if tables is not None and k is not None:
        t = int(tables.shape[1]) * int(k.shape[1])   # pages x page length
    elif k is not None:
        t = k.shape[1]
    else:
        t = q.shape[1]
    return {"kv_block": t, "slot_block": q.shape[0]}


def _decode_attention_example(case):
    import jax.numpy as jnp

    from repro.models.attention import quantize_kv_rows

    b, t, h, nkv, d = case.get("dims", (4, 128, 8, 4, 32))
    seed = case.get("seed", 0)
    q = _rand(seed, (b, 1, h, d), "float32")
    valid = jnp.asarray(case["valid"], jnp.int32)
    kwargs = {"softmax_mode": case.get("softmax_mode", "exact")}
    paged = case.get("paged")
    if paged:
        n_pages, page, p_per = paged
        kk = _rand(seed + 1, (n_pages, page, nkv, d), "float32")
        v = _rand(seed + 2, (n_pages, page, nkv, d), "float32")
        kwargs["tables"] = ((jnp.arange(b * p_per, dtype=jnp.int32)
                             .reshape(b, p_per)) * 7 + 3) % n_pages
    else:
        kk = _rand(seed + 1, (b, t, nkv, d), "float32")
        v = _rand(seed + 2, (b, t, nkv, d), "float32")
    if case.get("quant"):
        kq, ks = quantize_kv_rows(kk)
        vq, vs = quantize_kv_rows(v)
        kk, v = kq.astype(jnp.int8), vq.astype(jnp.int8)
        kwargs["ks"] = ks
        kwargs["vs"] = vs
    return (q, kk, v, valid), kwargs


registry.register(KernelSpec(
    name="decode_attention",
    build=_build_decode_attention,
    reference=_decode_attention_reference,
    space={"kv_block": (64, 128, 256, 512),
           "slot_block": (1, 2, 4, 8),
           "page_size": (8, 16, 32, 64, 128),
           "softmax_mode": ("exact", "taylor")},
    tuned=("kv_block", "slot_block"),
    base_config={"kv_block": 512, "slot_block": 1, "page_size": 64},
    legalize=_legalize_blocks(_decode_attention_block_dims,
                              divisors=(("page_size", "kv_block"),)),
    block_dims=_decode_attention_block_dims,
    block_divisors=(("page_size", "kv_block"),),
    make_example=_decode_attention_example,
    example_cases=(
        # NB: the batch axis value is kept distinct from every other
        # axis in each case — the legality checker's bucket scaling
        # rewrites *all* axes equal to a block dimension's value, so a
        # batch that collides with e.g. the KV-head count would scale
        # the head axis to serving-bucket size unblocked.
        {"dims": (4, 128, 8, 2, 32), "valid": (128, 64, 1, 97),
         "atol": 2e-5},
        # ragged odd lengths + a fully-masked slot (valid=0 -> zeros)
        {"dims": (3, 96, 4, 2, 16), "valid": (5, 96, 0), "atol": 2e-5},
        {"dims": (6, 128, 4, 2, 32), "valid": (128, 31, 77, 1, 64, 9),
         "quant": True, "atol": 2e-5},
        # paged: (n_pages, page, pages_per_slot) pool, table indirection
        {"dims": (3, 64, 4, 2, 32), "valid": (64, 17, 1),
         "paged": (12, 16, 4), "atol": 2e-5},
        {"dims": (3, 64, 4, 2, 32), "valid": (49, 64, 8),
         "paged": (12, 16, 4), "quant": True, "atol": 2e-5},
        {"dims": (5, 128, 4, 2, 32), "valid": (100, 128, 64, 1, 27),
         "softmax_mode": "taylor", "atol": 5e-2},
    ),
    ref_accepts=("tables", "ks", "vs"),
    is_available=_pallas_available,
))


# -- fused_sampling ---------------------------------------------------------
# Temperature / top-k / top-p masking + the categorical draw fused into
# one launch over the serving tick's logits, with counter-based
# randomness (request seed x sequence position x vocab lane), so a
# sampled token is a pure function of (seed, pos, logits) — independent
# of slot order, batch composition, preemption and handoff.  Greedy
# (temperature <= 0) is an exact raw-logits argmax.


@functools.lru_cache(maxsize=None)
def _build_fused_sampling():
    import jax

    from repro.kernels.sampling.kernel import fused_sampling_pallas

    @functools.partial(jax.jit, static_argnames=("row_block", "interpret"))
    def fused_sampling_entry(logits, temperature, seeds, pos, top_k, top_p,
                             row_block=8, *, interpret):
        return fused_sampling_pallas(
            logits, temperature, seeds, pos, top_k, top_p,
            row_block=row_block, interpret=interpret)

    return fused_sampling_entry


def _sampling_reference():
    from repro.kernels.sampling.ref import fused_sampling_ref

    return fused_sampling_ref


def _sampling_block_dims(logits, *args, **kwargs):
    return {"row_block": logits.shape[0]}


def _sampling_example(case):
    import jax.numpy as jnp

    b, v = case.get("dims", (8, 64))
    logits = _rand(case.get("seed", 0), (b, v), "float32", scale=3.0)
    temperature = jnp.asarray(case.get("temperature", (1.0,) * b),
                              jnp.float32)
    seeds = jnp.asarray([(i * 0x9E3779B1 + 17) & 0x7FFFFFFF
                         for i in range(b)], jnp.int32)
    pos = jnp.asarray([i * 5 + case.get("pos0", 1) for i in range(b)],
                      jnp.int32)
    top_k = jnp.asarray(case.get("top_k", (0,) * b), jnp.int32)
    top_p = jnp.asarray(case.get("top_p", (1.0,) * b), jnp.float32)
    return (logits, temperature, seeds, pos, top_k, top_p), {}


registry.register(KernelSpec(
    name="fused_sampling",
    build=_build_fused_sampling,
    reference=_sampling_reference,
    space={"row_block": (1, 2, 4, 8, 16)},
    tuned=("row_block",),
    base_config={"row_block": 8},
    legalize=_legalize_blocks(_sampling_block_dims),
    block_dims=_sampling_block_dims,
    make_example=_sampling_example,
    example_cases=(
        # tokens are int32 — the parity harness's allclose means *equal*
        {"dims": (8, 64), "temperature": (0.0,) * 8},          # greedy
        {"dims": (8, 64)},                                     # temp 1.0
        {"dims": (6, 50), "temperature": (0.0, 0.7, 1.0, 1.3, 0.0, 2.0)},
        {"dims": (4, 64), "top_k": (5, 1, 64, 0)},
        {"dims": (4, 64), "top_p": (0.1, 0.5, 0.9, 1.0)},
        {"dims": (3, 33), "temperature": (0.8, 0.9, 1.1),
         "top_k": (7, 0, 3), "top_p": (0.9, 0.3, 1.0), "pos0": 11},
    ),
    ref_accepts=(),
    is_available=_pallas_available,
))


# ---------------------------------------------------------------------------
# Public dispatch wrappers (ergonomic signatures over registry.call)
# ---------------------------------------------------------------------------


def fused_routing(u_hat, n_iters: int = 3, softmax_mode: str = "exact",
                  batch_block: Optional[int] = None,
                  interpret: Optional[bool] = None,
                  tune: Optional[bool] = None):
    """Fused dynamic routing: u_hat (B, J, D, I) -> (v (B, J, D),
    c (B, J, I))."""
    return registry.call(
        "fused_routing", u_hat, n_iters=n_iters, softmax_mode=softmax_mode,
        config={"batch_block": batch_block}, interpret=interpret, tune=tune)


def taylor_softmax(x, row_block: Optional[int] = None,
                   interpret: Optional[bool] = None,
                   tune: Optional[bool] = None):
    """Eq. 2 Taylor softmax over the last axis (any leading shape)."""
    return registry.call("taylor_softmax", x,
                         config={"row_block": row_block},
                         interpret=interpret, tune=tune)


def flash_attention(q, k, v, causal: bool = True, q_offset: int = 0,
                    softmax_mode: str = "exact",
                    q_block: Optional[int] = None,
                    kv_block: Optional[int] = None,
                    interpret: Optional[bool] = None,
                    tune: Optional[bool] = None):
    """q (B, S, H, D); k, v (B, T, K, D); H = K * G -> (B, S, H, D)."""
    return registry.call(
        "flash_attention", q, k, v, causal=causal, q_offset=q_offset,
        softmax_mode=softmax_mode,
        config={"q_block": q_block, "kv_block": kv_block},
        interpret=interpret, tune=tune)


def flash_attention_dequant(q, kq, ks, vq, vs, causal: bool = True,
                            q_offset: int = 0, softmax_mode: str = "exact",
                            q_block: Optional[int] = None,
                            kv_block: Optional[int] = None,
                            page_size: Optional[int] = None,
                            interpret: Optional[bool] = None,
                            tune: Optional[bool] = None):
    """q (B, S, H, D); kq, vq (B, T, K, D) int8 with per-row fp32
    scales ks, vs (B, T) (``quantize_kv_rows`` layout) -> (B, S, H, D).
    ``page_size`` (the cache pool's page length) keeps the legalized KV
    block page-aligned so dequant scales never straddle a page."""
    return registry.call(
        "flash_attention_dequant", q, kq, ks, vq, vs, causal=causal,
        q_offset=q_offset, softmax_mode=softmax_mode,
        config={"q_block": q_block, "kv_block": kv_block,
                "page_size": page_size},
        interpret=interpret, tune=tune)


def decode_attention(q, k, v, kv_valid_len, tables=None, ks=None, vs=None,
                     softmax_mode: str = "exact",
                     kv_block: Optional[int] = None,
                     slot_block: Optional[int] = None,
                     page_size: Optional[int] = None,
                     interpret: Optional[bool] = None,
                     tune: Optional[bool] = None):
    """q_len=1 decode attention: q (B, 1, H, D) -> (B, 1, H, D).

    Dense cache: k/v (B, T, K, D), optionally int8 with per-row fp32
    scales ks/vs (B, T); ``kv_valid_len`` (B,) masks each slot's ragged
    tail.  Paged cache: k/v are the pool's (n_pages, page, K, D) leaves
    (scales (n_pages, page)) and ``tables`` (B, P) holds each slot's
    page ids, pre-clipped to valid pool pages (sentinel entries rely on
    ``kv_valid_len`` masking).
    """
    return registry.call(
        "decode_attention", q, k, v, kv_valid_len, tables=tables,
        ks=ks, vs=vs, softmax_mode=softmax_mode,
        config={"kv_block": kv_block, "slot_block": slot_block,
                "page_size": page_size},
        interpret=interpret, tune=tune)


def fused_sampling(logits, temperature, seeds, pos, top_k=None, top_p=None,
                   row_block: Optional[int] = None,
                   interpret: Optional[bool] = None,
                   tune: Optional[bool] = None):
    """Fused device sampling: logits (B, V) + per-row temperature /
    seed / position / top_k / top_p -> (B,) int32 tokens.  Scalars are
    broadcast; ``top_k=None``/``0`` and ``top_p=None``/``1.0`` disable
    the respective restriction."""
    import jax.numpy as jnp

    b = logits.shape[0]

    def _row(x, dtype, default):
        if x is None:
            x = default
        return jnp.broadcast_to(jnp.asarray(x, dtype), (b,))

    return registry.call(
        "fused_sampling", logits,
        _row(temperature, jnp.float32, 0.0),
        _row(seeds, jnp.int32, 0),
        _row(pos, jnp.int32, 0),
        _row(top_k, jnp.int32, 0),
        _row(top_p, jnp.float32, 1.0),
        config={"row_block": row_block}, interpret=interpret, tune=tune)
