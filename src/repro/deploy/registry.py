"""Routing-variant registry: typed specs + capability-probed dispatch.

This replaces the stringly-typed ``mode=... softmax_mode=... interpret=...``
kwargs threading in ``core/routing.py``.  A routing variant is registered
once with:

  * a ``build(spec)`` factory returning the concrete route function
    ``fn(u_hat, n_iters) -> (v, c)``;
  * an availability probe (e.g. "is the Pallas toolchain importable");
    resolving an unavailable variant raises — no other variant is
    silently swapped in.

Callers hold a :class:`RoutingSpec` — a small frozen dataclass carried by
``CapsNetConfig.routing`` — and resolve it to a callable via
:func:`resolve`.  Backend-dependent choices (Pallas interpret mode off-TPU)
are made here, by probing ``jax.default_backend()``, never hardcoded at the
call site.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, Optional, Tuple

import jax

RouteFn = Callable[..., Tuple[jax.Array, jax.Array]]

_SOFTMAX_MODES = ("exact", "taylor")


@dataclasses.dataclass(frozen=True)
class RoutingSpec:
    """Typed description of a dynamic-routing configuration.

    ``interpret=None`` means "let the registry probe the backend": Pallas
    kernels run compiled on TPU and in interpret mode everywhere else.
    """

    mode: str = "reference"           # registered variant name
    softmax: str = "exact"            # exact | taylor (paper Eq. 2)
    div_exp_log: bool = False         # paper Eq. 3 (optimized variant only)
    interpret: Optional[bool] = None  # pallas only; None -> backend probe

    def __post_init__(self):
        if self.softmax not in _SOFTMAX_MODES:
            raise ValueError(
                f"softmax must be one of {_SOFTMAX_MODES}, got "
                f"{self.softmax!r}")

    # -- canonical constructors --------------------------------------------

    @classmethod
    def reference(cls) -> "RoutingSpec":
        return cls(mode="reference")

    @classmethod
    def optimized(cls, softmax: str = "taylor",
                  div_exp_log: bool = False) -> "RoutingSpec":
        return cls(mode="optimized", softmax=softmax,
                   div_exp_log=div_exp_log)

    @classmethod
    def pallas(cls, softmax: str = "taylor",
               interpret: Optional[bool] = None) -> "RoutingSpec":
        return cls(mode="pallas", softmax=softmax, interpret=interpret)

    @classmethod
    def named(cls, name: str) -> "RoutingSpec":
        """The deployment-default spec for a variant name (paper §III-B:
        the optimized/pallas paths ship with the Taylor softmax)."""
        table = {"reference": cls.reference(),
                 "optimized": cls.optimized(),
                 "pallas": cls.pallas()}
        if name not in table:
            raise ValueError(
                f"unknown routing variant {name!r}; known: "
                f"{sorted(table)}")
        return table[name]


@dataclasses.dataclass(frozen=True)
class RoutingVariant:
    """One registered routing implementation."""

    name: str
    build: Callable[[RoutingSpec], RouteFn]
    is_available: Callable[[], bool] = lambda: True


class RoutingRegistry:
    def __init__(self):
        self._variants: Dict[str, RoutingVariant] = {}

    def register(self, variant: RoutingVariant) -> RoutingVariant:
        self._variants[variant.name] = variant
        return variant

    def names(self):
        return sorted(self._variants)

    def get(self, name: str) -> RoutingVariant:
        try:
            return self._variants[name]
        except KeyError:
            raise ValueError(
                f"unknown routing mode {name!r}; registered: "
                f"{self.names()}") from None

    def normalize(self, spec: RoutingSpec) -> RoutingSpec:
        """Fill backend-dependent fields.

        The returned spec is fully concrete: its mode names an available
        variant and (for pallas) ``interpret`` is True/False, chosen from
        ``jax.default_backend()`` unless the caller pinned it.  An
        unavailable variant raises :class:`RuntimeError`.
        """
        variant = self.get(spec.mode)
        if not variant.is_available():
            raise RuntimeError(
                f"routing variant {variant.name!r} is unavailable on this "
                f"installation")
        if spec.mode == "pallas" and spec.interpret is None:
            from repro.kernels import needs_interpret

            spec = dataclasses.replace(spec, interpret=needs_interpret())
        return spec

    def resolve(self, spec: RoutingSpec) -> RouteFn:
        """Spec -> concrete ``fn(u_hat, n_iters) -> (v, c)``."""
        spec = self.normalize(spec)
        return self.get(spec.mode).build(spec)


# ---------------------------------------------------------------------------
# Default registry: the three paper variants
# ---------------------------------------------------------------------------

registry = RoutingRegistry()


def _build_reference(spec: RoutingSpec) -> RouteFn:
    from repro.core import routing

    return routing.route_reference


def _build_optimized(spec: RoutingSpec) -> RouteFn:
    from repro.core import routing

    return functools.partial(
        routing.route_optimized, softmax_mode=spec.softmax,
        use_div_exp_log=spec.div_exp_log)


def _pallas_available() -> bool:
    # thin view over the kernel registry: availability is whatever the
    # fused_routing KernelSpec's own probe says
    from repro.kernels.registry import registry as kernel_registry

    return kernel_registry.get("fused_routing").is_available()


def _build_pallas(spec: RoutingSpec) -> RouteFn:
    from repro import kernels

    def route_pallas(u_hat, n_iters: int = 3):
        return kernels.fused_routing(
            u_hat, n_iters=n_iters, softmax_mode=spec.softmax,
            interpret=spec.interpret)

    return route_pallas


registry.register(RoutingVariant("reference", _build_reference))
registry.register(RoutingVariant("optimized", _build_optimized))
registry.register(RoutingVariant("pallas", _build_pallas,
                                 is_available=_pallas_available))


def resolve(spec: RoutingSpec) -> RouteFn:
    return registry.resolve(spec)


def normalize(spec: RoutingSpec) -> RoutingSpec:
    return registry.normalize(spec)
