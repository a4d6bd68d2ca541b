"""FastCapsPipeline: the paper's Fig. 6 methodology as one object.

    pipe = FastCapsPipeline(cfg).build(seed=0)
    pipe.prune(sparsity_conv1=0.6, sparsity_conv2=0.9, type_keep=7)
    pipe.finetune(finetune_fn)          # optional (masked fine-tuning)
    pipe.compact()                      # 1152 -> 252 capsules
    deployed = pipe.compile(routing="pallas")

``compile`` returns an immutable :class:`DeployedCapsNet`: config + params
frozen together with a jitted fixed-signature forward, parameter/FLOP
accounting, and a checkpoint hook — the artifact
:class:`repro.serving.CapsuleEngine` serves.  ``deployed.serve(
scheduler=...)`` wraps it in that engine directly, so the Fig. 6 pipeline
flows into SLO-scheduled serving in one chain.

Stages are enforced in order (``prune`` before ``compact``; ``compact``
before a second ``prune``), matching the one-way arrows of Fig. 6; every
stage returns ``self`` so the pipeline chains.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Any, Callable, Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.checkpointing import checkpoint
from repro.core import capsnet as capsnet_lib
from repro.core import lakp as lakp_lib
from repro.core import routing as routing_lib
from repro.deploy.registry import RoutingSpec, normalize


class PipelineError(RuntimeError):
    """A pipeline stage was invoked out of Fig. 6 order."""


def capsnet_flops_per_image(cfg: capsnet_lib.CapsNetConfig) -> int:
    """Analytic forward FLOPs (conv + prediction + routing) per image."""
    conv1 = 2 * cfg.conv1_out_hw ** 2 * cfg.conv1_channels * (
        cfg.in_channels * cfg.conv1_kernel ** 2)
    conv2 = 2 * cfg.caps_out_hw ** 2 * cfg.primary_conv_channels * (
        cfg.conv1_channels * cfg.caps_kernel ** 2)
    pred = 2 * cfg.n_primary_caps * cfg.n_classes * cfg.caps_dim * \
        cfg.digit_dim
    route = routing_lib.routing_flops(
        1, cfg.n_primary_caps, cfg.n_classes, cfg.digit_dim,
        cfg.routing_iters)
    return conv1 + conv2 + pred + route


@dataclasses.dataclass(frozen=True)
class DeployedCapsNet:
    """Immutable deployment artifact: config + params + jitted forward."""

    cfg: capsnet_lib.CapsNetConfig
    params: Dict[str, Any]
    spec: RoutingSpec                 # normalized (backend-concrete)
    n_params: int
    flops_per_image: int
    _forward: Callable[[Dict[str, Any], jax.Array], jax.Array] = \
        dataclasses.field(repr=False, compare=False, default=None)

    def forward(self, images: jax.Array) -> jax.Array:
        """images (B, H, W, C) -> class capsule lengths (B, n_classes)."""
        return self._forward(self.params, images)

    __call__ = forward

    def classify(self, images: jax.Array) -> jax.Array:
        """images -> predicted class ids (B,)."""
        return jnp.argmax(self.forward(images), axis=-1)

    def serve(self, batch_size: int = 32, scheduler: Any = None,
              kernel_tune: Any = None):
        """Wrap this artifact in a :class:`repro.serving.CapsuleEngine`
        so the Fig. 6 pipeline flows straight into serving:

            engine = pipe.compile(routing="pallas").serve(
                scheduler=SLOBatchScheduler(target_p95_ms=20))

        ``batch_size`` is the engine capacity (max frames per tick);
        ``scheduler`` is any :class:`repro.serving.Scheduler` (FIFO when
        None).  The returned engine's ``submit()`` is thread-safe and
        non-blocking; drive it with ``run_until_idle()`` or a ``tick()``
        loop and read per-class latency p50/p95 from ``stats()``.
        ``kernel_tune=True`` makes ``engine.warmup()`` autotune the fused
        routing kernel's block sizes and bind the winners into the tick
        executables (see :mod:`repro.kernels.tuning`).
        """
        from repro.serving import CapsuleEngine

        return CapsuleEngine(self, batch_size=batch_size,
                             scheduler=scheduler, kernel_tune=kernel_tune)

    def save(self, directory: str, step: int = 0) -> str:
        """Checkpoint the params (atomic publish) + a deploy manifest."""
        path = checkpoint.save(directory, step, self.params)
        meta = {"cfg": dataclasses.asdict(
                    dataclasses.replace(self.cfg, routing=None)),
                "routing": dataclasses.asdict(self.spec),
                "n_params": self.n_params,
                "flops_per_image": self.flops_per_image}
        with open(os.path.join(directory, "deploy.json"), "w") as f:
            json.dump(meta, f, indent=2)
        return path


class FastCapsPipeline:
    """Chainable Fig. 6 pipeline; the canonical `repro.deploy` entry point.

    ``FastCapsPipeline(cfg, params=...)`` adopts already-trained params
    (skipping ``build``); otherwise call ``build(seed=...)`` first.
    """

    _ORDER = ("init", "built", "pruned", "finetuned", "compacted")

    def __init__(self, cfg: capsnet_lib.CapsNetConfig,
                 params: Optional[Dict[str, Any]] = None):
        self.cfg = cfg
        self.params = params
        self.masks: Optional[Tuple[jax.Array, jax.Array]] = None
        self.index: Dict[str, jax.Array] = {}
        self.compression: Optional[float] = None
        self.index_overhead_frac: Optional[float] = None
        self._stage = "built" if params is not None else "init"

    # -- stage machinery ---------------------------------------------------

    def _require(self, *stages: str) -> None:
        if self._stage not in stages:
            raise PipelineError(
                f"stage {self._stage!r} cannot run this step; expected one "
                f"of {stages}")

    @property
    def stage(self) -> str:
        return self._stage

    # -- Fig. 6 stages -----------------------------------------------------

    def build(self, seed: int = 0,
              key: Optional[jax.Array] = None) -> "FastCapsPipeline":
        """Initialize dense params (or adopt a key for reproducibility)."""
        self._require("init")
        self.params = capsnet_lib.init(
            self.cfg, key if key is not None else jax.random.key(seed))
        self._stage = "built"
        return self

    def prune(self, sparsity_conv1: float, sparsity_conv2: float,
              method: str = "lakp", norm: str = "l1",
              type_keep: Optional[int] = None) -> "FastCapsPipeline":
        """LAKP/KP kernel scoring + masking (+ capsule-type elimination)."""
        self._require("built", "compacted")
        self.masks = capsnet_lib.lakp_masks(
            self.params, self.cfg, sparsity_conv1, sparsity_conv2,
            method=method, norm=norm, type_keep=type_keep)
        conv_ws = [self.params["conv1"]["w"], self.params["conv2"]["w"]]
        self.compression = lakp_lib.effective_compression(
            list(self.masks), conv_ws)
        self.params = capsnet_lib.apply_masks(self.params, self.masks)
        self._stage = "pruned"
        return self

    def finetune(self, finetune_fn: Callable[[Dict[str, Any], Any],
                                             Dict[str, Any]]
                 ) -> "FastCapsPipeline":
        """Masked fine-tuning: ``finetune_fn(masked_params, masks)`` is
        injected by the trainer (keeps the pipeline optimizer-free)."""
        self._require("pruned")
        self.params = finetune_fn(self.params, self.masks)
        self._stage = "finetuned"
        return self

    def compact(self) -> "FastCapsPipeline":
        """Physically remove dead kernels/capsule types (index study)."""
        self._require("pruned", "finetuned")
        self.params, self.cfg, self.index = capsnet_lib.compact(
            self.params, self.cfg, self.masks)
        surviving = sum(int(x.size) for x in jax.tree.leaves(self.params))
        self.index_overhead_frac = lakp_lib.index_overhead_bytes(
            list(self.masks)) / max(surviving * 4, 1)
        self._stage = "compacted"
        return self

    def compile(self, routing: Union[None, str, RoutingSpec] = None,
                ) -> DeployedCapsNet:
        """Freeze the current model into a :class:`DeployedCapsNet`.

        ``routing``: a :class:`RoutingSpec`, a variant name (deployment
        defaults via ``RoutingSpec.named``), or None to keep the config's
        own spec.  Valid from any stage with params (deploy-the-dense-model
        is the Fig. 1 baseline).
        """
        self._require("built", "pruned", "finetuned", "compacted")
        if routing is None:
            spec = self.cfg.routing_spec()
        elif isinstance(routing, str):
            spec = RoutingSpec.named(routing)
        else:
            spec = routing
        spec = normalize(spec)
        cfg = dataclasses.replace(self.cfg, routing=spec)

        def capsnet_forward(p, x):
            return capsnet_lib.forward(p, cfg, x)[0]

        # a named program: jit_capsnet_forward in HLO and device traces
        fwd = jax.jit(capsnet_forward)
        return DeployedCapsNet(
            cfg=cfg,
            params=self.params,
            spec=spec,
            n_params=capsnet_lib.param_count(self.params),
            flops_per_image=capsnet_flops_per_image(cfg),
            _forward=fwd,
        )

    # -- one-call convenience ----------------------------------------------

    def deploy(self, sparsity_conv1: float, sparsity_conv2: float,
               method: str = "lakp", type_keep: Optional[int] = None,
               finetune_fn: Optional[Callable] = None,
               routing: Union[None, str, RoutingSpec] = "pallas",
               ) -> DeployedCapsNet:
        """build -> prune -> [finetune] -> compact -> compile in one call."""
        if self._stage == "init":
            self.build()
        self.prune(sparsity_conv1, sparsity_conv2, method=method,
                   type_keep=type_keep)
        if finetune_fn is not None:
            self.finetune(finetune_fn)
        self.compact()
        return self.compile(routing=routing)
