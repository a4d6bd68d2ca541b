"""CapsNet deployments (FastCaps) served by CapsuleEngine.

The engine is built through the program's own entry points:
``FastCapsPipeline(cfg, params=<weights from the seed>)`` ->
``.compile(RoutingSpec.pallas(softmax="taylor"))`` ->
``.serve(batch_size=<cell capacity>, kernel_tune=False)`` with the
default FIFO scheduler.  Requests are ``ImageRequest``s of frames cut
from a pool of images drawn from the seed.

Correctness: after the window, a sample of finished requests drawn from
the seed (at least ``SAMPLE_FRAMES`` frames) is run through the plain
reference (``capsnet_ref.py``), at the precisions the configuration
states, on weights it makes again from the seed.  Each served class
capsule length L is turned back into the norm of the capsule before its
squash, |s| = sqrt(L / (1 - L)) (Eq. 1 inverted), and two numbers of the
relative gap |s - s_ref| / s_ref are held to the configuration's limits:
its widest value, ``s_norm_gap``, which any wrong answer moves, and its
root mean square over the sample, ``s_norm_rms``, which a precision
lowered anywhere in the forward moves.

``FAULTS`` breaks the timed path for the checks that the comparison
catches what it has to (``bench/control.py --fault``, the tests).
"""

from __future__ import annotations

import math
import os

import numpy as np

SAMPLE_FRAMES = 2048          # frames compared per run
BLOCK = 512                   # frames per reference call
POOL_FRAMES = 4096            # distinct images a run's requests cut from


def _ref():
    from harness import load_module

    return load_module(os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "capsnet_ref.py"), "bench_capsnet_ref")


def program_config(sz: dict):
    """The program's CapsNetConfig for the deployed sizes."""
    from repro.core.capsnet import CapsNetConfig

    return CapsNetConfig(
        arch_id=sz["name"], image_hw=sz["image_hw"],
        in_channels=sz["in_channels"], n_classes=sz["n_classes"],
        conv1_channels=sz["conv1_channels"],
        conv1_kernel=sz["conv1_kernel"], caps_types=sz["caps_types"],
        caps_dim=sz["caps_dim"], caps_kernel=sz["caps_kernel"],
        caps_stride=sz["caps_stride"], digit_dim=sz["digit_dim"],
        routing_iters=sz["routing_iters"], param_dtype=sz["param_dtype"])


class Deployment:
    stream = False            # completion-level: one stamp per request

    def __init__(self, cell, seed: int, sched):
        from repro.deploy import FastCapsPipeline, RoutingSpec
        from repro.serving import FIFOScheduler

        self.sz = cell.sizes
        self.seed = seed
        self.ref = _ref()
        serve = cell.serve()
        if serve.get("scheduler", "fifo") != "fifo":
            raise ValueError("capsnet cells serve with FIFO")
        self.capacity = int(serve["batch_size"])
        cfg = program_config(self.sz)
        if cfg.n_primary_caps != self.sz["n_primary_caps"]:
            raise ValueError("sizes do not give the stated capsule count")
        weights = self.ref.make_weights(self.sz, seed)
        self.deployed = FastCapsPipeline(cfg, params=weights).compile(
            RoutingSpec.pallas(softmax=self.sz["softmax"]))
        self.engine = self.deployed.serve(
            batch_size=self.capacity, scheduler=FIFOScheduler(),
            kernel_tune=bool(serve.get("kernel_tune", False)))
        from traffic import generator

        r = generator.rng(seed, "payload")
        hw, ch = self.sz["image_hw"], self.sz["in_channels"]
        self.pool = r.random((POOL_FRAMES, hw, hw, ch), np.float32)
        self.served = {}          # rid -> (first pool frame, lengths)

    def _frames(self, index: int, n: int) -> int:
        return (index * 613) % (POOL_FRAMES - n + 1)

    def warm(self) -> None:
        """Compile every batch shape the scheduler emits, then serve one
        full batch through submit/tick so the host path is warm too."""
        from repro.serving import ImageRequest

        self.engine.warmup()
        self.engine.submit(ImageRequest(images=self.pool[:self.capacity]))
        self.engine.run_until_idle()

    def request(self, item):
        from repro.serving import ImageRequest

        n = item.sizes["frames"]
        a = self._frames(item.index, n)
        return ImageRequest(images=self.pool[a:a + n])

    def on_done(self, req, completion) -> None:
        a = self._frames(req.index, req.sizes["frames"])
        self.served[req.rid] = (a, np.asarray(completion.lengths))

    def facts(self) -> dict:
        return {**{k: v for k, v in self.sz.items()
                   if isinstance(v, (int, float))},
                "batch": self.capacity}

    def info(self, run) -> dict:
        return {}

    def release(self) -> None:
        self.engine = None
        self.deployed = None

    def sample(self, rng):
        """Frames of finished requests drawn from ``rng`` (whole requests,
        at least ``SAMPLE_FRAMES`` frames or all there are) and the
        lengths the engine served for them."""
        done = sorted(self.served)
        frames, served, n = [], [], 0
        for i in rng.permutation(len(done)):
            a, lengths = self.served[done[i]]
            frames.append(self.pool[a:a + lengths.shape[0]])
            served.append(lengths)
            n += lengths.shape[0]
            if n >= SAMPLE_FRAMES:
                break
        return np.concatenate(frames), np.concatenate(served)

    def reference(self, weights, frames, precision: str = "stated"):
        """Reference lengths, in fixed blocks of ``BLOCK`` frames."""
        import jax.numpy as jnp

        n = frames.shape[0]
        pad = np.zeros((-n % BLOCK,) + frames.shape[1:], frames.dtype)
        x = np.concatenate([frames, pad])
        out = [np.asarray(self.ref.forward_jit(
            weights, jnp.asarray(x[k:k + BLOCK]), _frozen(self.sz),
            precision)) for k in range(0, x.shape[0], BLOCK)]
        return np.concatenate(out)[:n]

    def control(self, rng, precision: str) -> dict:
        """The same numbers with the reference at ``precision`` put in
        the program's place, on the same sampled frames."""
        frames, _ = self.sample(rng)
        weights = self.ref.make_weights(self.sz, self.seed)
        return s_norm_gaps(self.reference(weights, frames, precision),
                           self.reference(weights, frames))

    def check(self, run, rng) -> list:
        """The relative gaps of the pre-squash norm over the sample."""
        frames, served = self.sample(rng)
        weights = self.ref.make_weights(self.sz, self.seed)
        gaps = s_norm_gaps(served, self.reference(weights, frames))
        limits = self.sz["limits"]
        return [{"name": k, "value": v,
                 "limit": math.inf if limits.get(k) is None else limits[k],
                 "judged": limits.get(k) is not None}
                for k, v in gaps.items()]


def s_norm_gaps(lengths, want) -> dict:
    """Widest and root-mean-square |s - s_ref| / s_ref, with
    |s| = sqrt(L / (1 - L)) read back from each capsule length L.  NaN
    if any length is NaN."""
    def norm(x):
        x = np.asarray(x, np.float64)
        return np.sqrt(x / (1.0 - x))

    ref = norm(want)
    rel = np.abs(norm(lengths) - ref) / ref
    return {"s_norm_gap": float(np.max(rel)),
            "s_norm_rms": float(np.sqrt(np.mean(np.square(rel))))}


def _swap_frames(dep) -> None:
    """Frames 0 and 1 of every batch get each other's answers."""
    import jax.numpy as jnp

    fwd = dep.deployed._forward
    order = jnp.array([1, 0])

    def swapped(params, x):
        y = fwd(params, x)
        return y.at[:2].set(y[order])

    object.__setattr__(dep.deployed, "_forward", swapped)


def _alter_answer(dep) -> None:
    """One capsule length of every batch moved by 0.01."""
    fwd = dep.deployed._forward

    def altered(params, x):
        return fwd(params, x).at[0, 0].add(0.01)

    object.__setattr__(dep.deployed, "_forward", altered)


FAULTS = {"swap_frames": _swap_frames, "alter_answer": _alter_answer}


class _frozen(dict):
    """A hashable view of the sizes, for jit's static arguments."""

    def __hash__(self):
        return hash(tuple(sorted((k, v) for k, v in self.items()
                                 if isinstance(v, (int, float, str)))))
