"""CapsuleEngine: batched CapsNet image serving over the shared EngineCore.

The paper's throughput story (Fig. 1: 82 -> 1351 FPS) is a *served*
workload, not a bare jit loop.  This adapter serves image-classification
requests through one fixed-shape jitted forward:

* **Request expansion** — requests carry a ragged number of frames; each
  frame becomes one slot task, so frames from different requests share a
  tick's batch (slot recycling).
* **Scheduler-shaped batches** — every tick packs the occupied slots into
  a batch whose size the scheduler chose: the FIFO scheduler always runs
  the one full-capacity executable (zero-padding the tail), the SLO
  scheduler shrinks/grows power-of-two buckets against a p95 target, and
  the sharded scheduler places the batch across a mesh.
* **Async admission** — ``submit()`` is thread-safe and non-blocking;
  frames submitted while a tick is in flight join the next tick.
* **FPS / latency stats** — cumulative frames, ticks, padding waste and
  wall-clock, plus per-request latency from submit to completion.
* **Per-run assembly and scatter** — frames move between requests and
  the batch as runs: consecutive batch rows that hold consecutive
  frames of one request, found from the tick's slots, each copied in and
  its lengths copied back as one slice.  FIFO admission of whole
  requests gives a few long runs a tick; fragmented slots (ragged
  requests, SLO buckets, preemption) give shorter ones, down to one
  frame.  ``EngineStats.counts["caps.runs"]`` counts the runs moved.
* **Phase spans** — inside ``serving.step`` each tick is split into
  ``caps.assemble`` (finding the runs, one slice copy per run into the
  batch), ``caps.device`` (placement, the forward, the blocking
  readback) and ``caps.scatter`` (one slice of lengths per run, and the
  stream events of streaming requests under one lock), each a profiler
  span and an ``EngineStats.phase_s`` entry.

``engine = deployed.serve(scheduler=...)`` (on a
:class:`repro.deploy.DeployedCapsNet`) is the canonical way in.  To
serve one deployment from a *pool* of engines behind a single
``submit()/poll()`` surface, wrap CapsuleEngines in a
:class:`repro.serving.DisaggregatedEngine` with ``prefill=None`` (the
stateless form of disaggregated serving — image tasks carry no cache,
so the handoff is pure dispatch); ``bench_fig1_throughput.py
--scheduler disagg`` measures exactly that topology.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.serving.core import (EngineCore, EngineStats,  # noqa: F401
                                SlotTask, StreamEvent)
from repro.serving.schedulers import Scheduler, pow2_bucket


@dataclasses.dataclass
class ImageRequest:
    """A batch-of-frames classification request (ragged ``images`` count).

    ``rid=None`` lets the engine assign the next free id at submit time.
    ``stream=True`` emits one :class:`repro.serving.StreamEvent` per
    classified frame (``item=(frame_index, class_id)``) on the
    ``poll(stream=True)`` channel as ticks complete, instead of waiting
    for the whole request.
    """

    images: np.ndarray                # (n_frames, H, W, C)
    rid: Optional[int] = None
    stream: bool = False


@dataclasses.dataclass
class ImageCompletion:
    rid: int
    classes: np.ndarray               # (n_frames,) int32 predictions
    lengths: np.ndarray               # (n_frames, n_classes) capsule lengths
    latency_s: float                  # submit -> completion wall-clock


class CapsuleEngine(EngineCore):
    """Fixed-shape micro-batched inference over a :class:`DeployedCapsNet`.

    ``deployed`` is any object with ``cfg`` (a CapsNetConfig) and
    ``forward(images) -> lengths`` — in practice the artifact returned by
    ``FastCapsPipeline.compile``.  ``batch_size`` is the engine capacity
    (max frames per tick); the scheduler decides how much of it each tick
    actually uses.
    """

    def __init__(self, deployed: Any, batch_size: int = 32,
                 scheduler: Optional[Scheduler] = None,
                 clock=time.perf_counter,
                 kernel_tune: Optional[bool] = None):
        self.deployed = deployed
        self.batch_size = batch_size
        cfg = deployed.cfg
        self._frame_shape = (cfg.image_hw, cfg.image_hw, cfg.in_channels)
        self._n_classes = cfg.n_classes
        super().__init__(capacity=batch_size, scheduler=scheduler,
                         clock=clock, kernel_tune=kernel_tune)

    # -- workload hooks ----------------------------------------------------

    def _expand(self, request: ImageRequest
                ) -> Tuple[List[SlotTask], Dict[str, Any]]:
        imgs = np.asarray(request.images, np.float32)
        if imgs.ndim != 4 or imgs.shape[1:] != self._frame_shape:
            raise ValueError(
                f"request images must be (n,) + {self._frame_shape}, got "
                f"{imgs.shape}")
        request.images = imgs
        n = imgs.shape[0]
        state = {"images": imgs,
                 "lengths": np.zeros((n, self._n_classes), np.float32)}
        return [SlotTask(payload=k) for k in range(n)], state

    def _runs(self, active: List[Tuple[int, SlotTask]]) -> List[List[Any]]:
        """``active`` (slot order) as runs ``[entry, k0, i0, i1]``: batch
        rows ``i0:i1`` hold frames ``k0:k0 + i1 - i0`` of ``entry``'s
        request."""
        runs: List[List[Any]] = []
        rid = k = None
        for i, (_, task) in enumerate(active):
            if task.rid == rid and task.payload == k + 1:
                k += 1
                continue
            if runs:
                runs[-1].append(i)
            rid, k = task.rid, task.payload
            runs.append([self._requests[rid], k, i])
        runs[-1].append(len(active))
        return runs

    def _step(self, active: List[Tuple[int, SlotTask]], n_batch: int
              ) -> Tuple[List[int], int]:
        # three spans inside serving.step: host batch assembly, the
        # device forward with its dispatch and readback, the scatter of
        # results; assembly and scatter take one slice per run
        n = len(active)
        with self._span("caps.assemble"):
            runs = self._runs(active)
            batch = np.empty((n_batch,) + self._frame_shape, np.float32)
            batch[n:] = 0
            for entry, k0, i0, i1 in runs:
                batch[i0:i1] = entry.state["images"][k0:k0 + i1 - i0]
        with self._span("caps.device"):
            lengths = np.asarray(jax.block_until_ready(
                self.deployed.forward(self.scheduler.place(batch))))
        with self._span("caps.scatter"):
            streams = []
            for run in runs:
                entry, k0, i0, i1 = run
                entry.state["lengths"][k0:k0 + i1 - i0] = lengths[i0:i1]
                if entry.stream:
                    streams.append(run)
            classes = (np.argmax(lengths[:n], -1).tolist() if streams
                       else None)
            with self._lock:
                counts = self._stats.counts
                counts["caps.runs"] = counts.get("caps.runs", 0) + len(runs)
                for entry, k0, i0, i1 in streams:
                    rid = entry.request.rid
                    for j in range(i1 - i0):
                        self._events.append(StreamEvent(
                            rid=rid, seq=entry.emitted,
                            item=(k0 + j, classes[i0 + j])))
                        entry.emitted += 1
        return [s for s, _ in active], len(active)

    def _request_class(self, request: ImageRequest) -> str:
        """Latency histogram key: frame counts bucketed to powers of two
        (``"image/f4"`` = requests carrying 3-4 frames)."""
        return f"image/f{pow2_bucket(len(request.images), self.capacity)}"

    def _finalize(self, entry, latency_s: float) -> ImageCompletion:
        buf = entry.state["lengths"]
        return ImageCompletion(
            rid=entry.request.rid,
            classes=np.argmax(buf, -1).astype(np.int32),
            lengths=buf,
            latency_s=latency_s)

    def _warmup(self) -> None:
        # compile every batch shape the scheduler can emit, so no tick
        # (and no SLO latency observation) pays compile time
        for n in self.scheduler.shapes(self.capacity):
            dummy = np.zeros((n,) + self._frame_shape, np.float32)
            jax.block_until_ready(
                self.deployed.forward(self.scheduler.place(dummy)))

    def _pretune(self) -> None:
        # bind-time kernel tuning: measure fused_routing block sizes for
        # every u_hat shape the scheduler's batch shapes imply, so the
        # warm-up traces of deployed.forward resolve tuned configs
        spec = getattr(self.deployed, "spec", None)
        if spec is None or spec.mode != "pallas":
            return
        from repro.kernels import tuning as ktuning
        from repro.kernels.registry import registry as kernel_registry

        kspec = kernel_registry.get("fused_routing")
        if not kspec.is_available():
            return
        cfg = self.deployed.cfg
        cache = ktuning.default_cache()
        for n in self.scheduler.shapes(self.capacity):
            u_hat = (jax.random.normal(
                jax.random.key(0),
                (n, cfg.n_classes, cfg.digit_dim, cfg.n_primary_caps))
                * 0.2)
            if cache.get(ktuning.cache_key_for(kspec, (u_hat,))) is None:
                ktuning.autotune(
                    kspec, (u_hat,),
                    {"n_iters": cfg.routing_iters,
                     "softmax_mode": spec.softmax}, cache=cache)
