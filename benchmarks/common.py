"""Shared benchmark utilities: timing, CapsNet training, result tables."""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import capsnet as cn
from repro.core import pruning as pr
from repro.data import synthetic_digits as sd
# every benchmark entry point calls bc.enable_compile_cache() first
from repro.launch.compile_cache import enable_compile_cache  # noqa: F401
from repro.optim import AdamWConfig
from repro.training import Trainer, TrainerConfig


def time_fn(fn: Callable[[], Any], warmup: int = 2, iters: int = 5) -> float:
    """Median wall-clock seconds (block_until_ready on pytree outputs)."""
    for _ in range(warmup):
        jax.block_until_ready(fn())
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def bench_capsnet_cfg(quick: bool) -> cn.CapsNetConfig:
    """Paper-shaped CapsNet; quick mode shrinks channels (CPU budget)."""
    if quick:
        return cn.CapsNetConfig(arch_id="capsnet-bench", conv1_channels=32,
                                caps_types=8, decoder_hidden=(64, 128))
    return cn.CapsNetConfig(arch_id="capsnet-bench")


def train_capsnet(cfg: cn.CapsNetConfig, variant: str, steps: int,
                  n_train: int = 512, lr: float = 2e-3,
                  seed: int = 0):
    data = sd.load(sd.DigitsConfig(variant=variant, n_train=n_train,
                                   n_test=max(n_train // 2, 128),
                                   seed=seed))
    tr_x, tr_y = data["train"]

    def loss_fn(p, b):
        return cn.loss_fn(p, cfg, b["images"], b["labels"])

    def batches():
        for bx, by in sd.batches(tr_x, tr_y, 32, seed, epochs=1000):
            yield {"images": bx, "labels": by}

    tcfg = TrainerConfig(optim=AdamWConfig(lr=lr, weight_decay=0.0,
                                           warmup_steps=max(steps // 10, 1),
                                           total_steps=steps),
                         log_every=max(steps // 4, 1))
    res = Trainer(tcfg, loss_fn, lambda k: cn.init(cfg, k)).run(
        batches(), steps, key=jax.random.key(seed))
    return res.params, data


def finetune_fn_factory(cfg, data, steps: int, lr: float = 5e-4, seed: int = 7):
    tr_x, tr_y = data["train"]

    def loss_fn(p, b):
        return cn.loss_fn(p, cfg, b["images"], b["labels"])

    def batches():
        for bx, by in sd.batches(tr_x, tr_y, 32, seed, epochs=1000):
            yield {"images": bx, "labels": by}

    def finetune(masked, masks):
        tr = Trainer(
            TrainerConfig(optim=AdamWConfig(lr=lr, weight_decay=0.0,
                                            warmup_steps=1,
                                            total_steps=steps),
                          log_every=max(steps, 1)),
            loss_fn, lambda k: masked,
            mask_fn=lambda g: pr.mask_gradients(g, masks))
        return tr.run(batches(), steps).params

    return finetune


def test_error(params, cfg, data) -> float:
    te_x, te_y = data["test"]
    fwd = jax.jit(lambda p, x: cn.forward(p, cfg, x)[0])
    preds = jnp.argmax(fwd(params, te_x), -1)
    return 100.0 * (1.0 - float(jnp.mean((preds == te_y))))


def jsonable(x: Any) -> Any:
    """Best-effort conversion of a bench result tree to JSON types."""
    if isinstance(x, dict):
        return {str(k): jsonable(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [jsonable(v) for v in x]
    if isinstance(x, (np.floating, np.integer)):
        return x.item()
    if isinstance(x, (jnp.ndarray, np.ndarray)):
        return np.asarray(x).tolist()
    if isinstance(x, (str, int, float, bool)) or x is None:
        return x
    return repr(x)


def write_bench_json(path: str, bench: str, results: Any,
                     mode: str = "quick") -> str:
    """Write one machine-readable ``BENCH_<key>.json`` perf-trajectory
    record: the bench's result dict plus enough metadata (timestamp,
    backend, mode) for CI artifacts to accumulate into a history."""
    import json
    import os
    import platform

    payload = {
        "schema": "repro-bench-v1",
        "bench": bench,
        "mode": mode,
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
        "backend": jax.default_backend(),
        "python": platform.python_version(),
        "results": jsonable(results),
    }
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    with open(path, "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True)
    print(f"[bench] wrote {path}")
    return path


def print_table(title: str, header: List[str],
                rows: List[List[Any]]) -> None:
    print(f"\n== {title} ==")
    widths = [max(len(str(h)), *(len(str(r[i])) for r in rows))
              for i, h in enumerate(header)] if rows else [len(h) for h in header]
    print("  " + " | ".join(str(h).ljust(w) for h, w in zip(header, widths)))
    print("  " + "-+-".join("-" * w for w in widths))
    for r in rows:
        print("  " + " | ".join(str(c).ljust(w) for c, w in zip(r, widths)))
