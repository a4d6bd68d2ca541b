"""Training launcher: end-to-end driver for any registered architecture.

    # reduced config on CPU (smoke / examples):
    PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b \
        --reduced --steps 50 --batch 8 --seq 64

    # CapsNet (the paper's model) with the FastCaps prune pipeline:
    PYTHONPATH=src python -m repro.launch.train --arch capsnet-mnist \
        --steps 200 --prune lakp:0.97 --finetune-steps 100

On a real fleet the same driver runs under the production mesh with the
sharded train step (parallel/sharding.py); here it runs on the host
devices so the full loop (data -> step -> checkpoint -> resume) is
exercised end to end.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs as cfg_lib
from repro.core import capsnet as capsnet_lib
from repro.core import pruning as pruning_lib
from repro.data import synthetic_digits, tokens
from repro.deploy import FastCapsPipeline
from repro.launch.compile_cache import enable_compile_cache
from repro.models import lm
from repro.optim import AdamWConfig
from repro.training import Trainer, TrainerConfig


def train_lm(args) -> None:
    cfg = cfg_lib.get_config(args.arch)
    if args.reduced:
        cfg = cfg_lib.reduced(cfg)
    stream = tokens.TokenStream(tokens.TokenStreamConfig(vocab=cfg.vocab))
    tcfg = TrainerConfig(
        optim=AdamWConfig(lr=args.lr, warmup_steps=max(args.steps // 10, 1),
                          total_steps=args.steps),
        ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
        log_every=args.log_every)
    trainer = Trainer(tcfg, lambda p, b: lm.loss_fn(p, cfg, b),
                      lambda k: lm.init(cfg, k))
    t0 = time.time()
    res = trainer.run(stream.batches(args.batch, args.seq, args.steps),
                      args.steps)
    dt = time.time() - t0
    print(f"[{cfg.arch_id}] {res.step} steps in {dt:.1f}s "
          f"({res.step / dt:.2f} steps/s)")
    for h in res.history:
        print("  ", {k: round(v, 4) for k, v in h.items()})


def train_capsnet(args) -> None:
    cfg = cfg_lib.get_config(args.arch)
    if args.reduced:
        cfg = cfg_lib.reduced(cfg)
    variant = "fashion" if "fmnist" in args.arch else "digits"
    data = synthetic_digits.load(synthetic_digits.DigitsConfig(
        variant=variant, n_train=args.n_train, n_test=args.n_test))
    tr_x, tr_y = data["train"]
    te_x, te_y = data["test"]

    def loss_fn(p, b):
        return capsnet_lib.loss_fn(p, cfg, b["images"], b["labels"])

    def batches(n, seed=0):
        for bx, by in synthetic_digits.batches(tr_x, tr_y, args.batch, seed,
                                               epochs=1000):
            yield {"images": bx, "labels": by}

    tcfg = TrainerConfig(
        optim=AdamWConfig(lr=args.lr, weight_decay=0.0,
                          warmup_steps=max(args.steps // 10, 1),
                          total_steps=args.steps),
        ckpt_dir=args.ckpt_dir, log_every=args.log_every)
    trainer = Trainer(tcfg, loss_fn, lambda k: capsnet_lib.init(cfg, k))
    res = trainer.run(batches(args.steps), args.steps)
    print(f"[{cfg.arch_id}] trained {res.step} steps; "
          f"final: {res.history[-1] if res.history else {}}")

    eval_fn = jax.jit(lambda p, x: capsnet_lib.forward(p, cfg, x)[0])
    acc = float(jnp.mean((jnp.argmax(eval_fn(res.params, te_x), -1)
                          == te_y)))
    print(f"  test acc (dense): {acc:.4f}")

    if args.prune:
        method, rate = args.prune.split(":")
        rate = float(rate)
        def finetune(masked, masks):
            ft = Trainer(
                TrainerConfig(optim=AdamWConfig(
                    lr=args.lr / 3, weight_decay=0.0,
                    warmup_steps=1, total_steps=args.finetune_steps)),
                loss_fn, lambda k: masked,
                mask_fn=lambda g: pruning_lib.mask_gradients(g, masks))
            return ft.run(batches(args.finetune_steps),
                          args.finetune_steps).params
        pipe = FastCapsPipeline(cfg, params=res.params)
        pipe.prune(rate, rate, method=method).finetune(finetune)
        acc_p = float(jnp.mean((jnp.argmax(
            eval_fn(pipe.params, te_x), -1) == te_y)))
        deployed = pipe.compact().compile()
        acc_c = float(jnp.mean((deployed.classify(te_x) == te_y)))
        c_cfg = deployed.cfg
        print(f"  pruned[{method}:{rate}] compression="
              f"{pipe.compression:.4f} "
              f"index_overhead={pipe.index_overhead_frac:.5f}")
        print(f"  test acc (pruned+finetuned): {acc_p:.4f}; "
              f"compacted ({c_cfg.caps_types}/{cfg.caps_types} capsule "
              f"types, {c_cfg.n_primary_caps} capsules): {acc_c:.4f}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True,
                    choices=cfg_lib.list_archs())
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    # capsnet options
    ap.add_argument("--prune", default=None, help="lakp:0.97 | kp:0.97")
    ap.add_argument("--finetune-steps", type=int, default=50)
    ap.add_argument("--n-train", type=int, default=512)
    ap.add_argument("--n-test", type=int, default=256)
    args = ap.parse_args()
    enable_compile_cache()
    if args.arch.startswith("capsnet"):
        train_capsnet(args)
    else:
        train_lm(args)


if __name__ == "__main__":
    main()
