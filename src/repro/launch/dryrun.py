import os
# 512 virtual host devices, and the CPU platform pinned: a dry run never
# takes an accelerator, so run_matrix can start one per cell beside a
# process that holds the chip.
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run: lower + compile every (architecture x input-shape x
mesh) cell and record memory / cost / collective analysis.

This is how the distribution config is proven coherent without hardware:
jax.jit(step, in_shardings, out_shardings).lower(**structs).compile() runs
the full GSPMD partitioner for the production mesh; sharding mismatches,
compile-time OOMs and unsupported collectives all fail HERE.

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-1.7b \
        --shape train_4k --mesh single --out experiments/dryrun
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both

Results (one JSON per cell) feed launch/roofline.py and EXPERIMENTS.md.
"""

import argparse
import dataclasses
import json
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro import configs as cfg_lib
from repro.core import capsnet as capsnet_lib
from repro.launch import hlo_analysis, hlo_cost
from repro.launch.mesh import (make_production_mesh,
                              require_virtual_devices)
from repro.models import common, lm
from repro.models.common import LMConfig
from repro.optim import adamw
from repro.parallel import sharding as shard_lib


# ---------------------------------------------------------------------------
# Cell construction: (fn, arg structs, arg shardings)
# ---------------------------------------------------------------------------


def _train_cell(cfg: LMConfig, shape: str, rules, mesh):
    params = lm.param_structs(cfg)
    opt = jax.eval_shape(adamw.init_state, params)
    batch = cfg_lib.input_specs(cfg, shape)

    params_ax = lm.specs(cfg)
    params_sh = shard_lib.shardings_for(params, params_ax, rules, mesh)
    opt_sh = {"m": params_sh, "v": params_sh,
              "step": shard_lib.shardings_for(
                  opt["step"], None, rules, mesh)}
    batch_sh = shard_lib.shardings_for(
        batch, cfg_lib.batch_axes(cfg, shape), rules, mesh)
    ocfg = adamw.AdamWConfig()

    def step(params, opt_state, batch):
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: lm.loss_fn(p, cfg, batch), has_aux=True)(params)
        new_p, new_o, om = adamw.apply_updates(params, grads, opt_state, ocfg)
        return new_p, new_o, dict(metrics, **om)

    return (step, (params, opt, batch), (params_sh, opt_sh, batch_sh),
            (params_sh, opt_sh, None))


def _prefill_cell(cfg: LMConfig, shape: str, rules, mesh):
    info = cfg_lib.SHAPES[shape]
    b, s = info["batch"], info["seq"]
    batch = cfg_lib.input_specs(cfg, shape)
    params = lm.param_structs(cfg)
    params_sh = shard_lib.shardings_for(params, lm.specs(cfg), rules, mesh)
    batch_sh = shard_lib.shardings_for(
        batch, cfg_lib.batch_axes(cfg, shape), rules, mesh)

    if cfg.family == "audio":
        def enc(params, batch):
            x, _, _ = lm.forward(params, cfg, batch)
            return common.unembed(params["embed"], cfg, x[:, -1:, :])
        return enc, (params, batch), (params_sh, batch_sh), None

    caches = lm.make_caches(cfg, b, s, as_structs=True)
    caches_sh = shard_lib.shardings_for(caches, lm.cache_specs(cfg), rules,
                                        mesh)

    def prefill(params, batch, caches):
        return lm.prefill_step(params, cfg, batch, caches)

    return (prefill, (params, batch, caches),
            (params_sh, batch_sh, caches_sh), (None, caches_sh))


def _decode_cell(cfg: LMConfig, shape: str, rules, mesh):
    info = cfg_lib.SHAPES[shape]
    b, s = info["batch"], info["seq"]
    batch = cfg_lib.input_specs(cfg, shape)
    params = lm.param_structs(cfg)
    params_sh = shard_lib.shardings_for(params, lm.specs(cfg), rules, mesh)
    batch_sh = shard_lib.shardings_for(
        batch, cfg_lib.batch_axes(cfg, shape), rules, mesh)
    caches = lm.make_caches(cfg, b, s, as_structs=True)
    caches_sh = shard_lib.shardings_for(caches, lm.cache_specs(cfg), rules,
                                        mesh)

    def decode(params, batch, caches):
        return lm.decode_step(params, cfg, batch, caches)

    return (decode, (params, batch, caches),
            (params_sh, batch_sh, caches_sh), (None, caches_sh))


def _capsnet_cell(cfg, shape: str, rules, mesh):
    b = {"train_1k": 1024, "infer_1k": 1024}[shape]
    params = jax.eval_shape(
        lambda k: capsnet_lib.init(cfg, k),
        jax.ShapeDtypeStruct((2,), jnp.uint32))
    params_sh = shard_lib.shardings_for(params, capsnet_lib.specs(cfg),
                                        rules, mesh)
    images = jax.ShapeDtypeStruct((b, cfg.image_hw, cfg.image_hw,
                                   cfg.in_channels), jnp.float32)
    labels = jax.ShapeDtypeStruct((b,), jnp.int32)
    im_sh = shard_lib.shardings_for(images, ("batch", None, None, None),
                                    rules, mesh)
    lb_sh = shard_lib.shardings_for(labels, ("batch",), rules, mesh)

    if shape == "train_1k":
        opt = jax.eval_shape(adamw.init_state, params)
        opt_sh = {"m": params_sh, "v": params_sh,
                  "step": shard_lib.shardings_for(opt["step"], None, rules,
                                                  mesh)}
        ocfg = adamw.AdamWConfig()

        def step(params, opt_state, images, labels):
            (loss, m), grads = jax.value_and_grad(
                capsnet_lib.loss_fn, has_aux=True)(params, cfg, images,
                                                   labels)
            return adamw.apply_updates(params, grads, opt_state, ocfg)[:2]

        return (step, (params, opt, images, labels),
                (params_sh, opt_sh, im_sh, lb_sh), (params_sh, opt_sh))

    def infer(params, images):
        lengths, _ = capsnet_lib.forward(params, cfg, images)
        return lengths

    return infer, (params, images), (params_sh, im_sh), None


def apply_variant(cfg, variant: str, kind: str = "train"):
    """Config-level optimization bundles (§Perf).

    The SHIPPED defaults are the optimized settings ("opt"): H1 flash-bwd
    attention remat, H2 loss-chunk remat, H-B1 one-hot MoE dispatch,
    H-C1 global decode dispatch, bf16 deployment weights for inference
    kinds (the paper's own 16-bit-deployment finding).  ``--variant base``
    reverts to the pre-hillclimb baseline for A/B lowering."""
    if arch_is_capsnet(cfg):
        return cfg
    if variant == "base":
        kw = {"attn_scan_remat": False, "loss_remat": False}
        if getattr(cfg, "moe", None) is not None:
            kw["moe"] = dataclasses.replace(
                cfg.moe, dispatch="scatter", global_decode_dispatch=False)
        return dataclasses.replace(cfg, **kw)
    kw = {}
    if kind in ("prefill", "decode"):
        kw["param_dtype"] = "bfloat16"
    return dataclasses.replace(cfg, **kw) if kw else cfg


def arch_is_capsnet(cfg) -> bool:
    return not isinstance(cfg, LMConfig)


def build_cell(arch: str, shape: str, rules, mesh, variant: str = "base"):
    cfg = cfg_lib.get_config(arch)
    if arch.startswith("capsnet"):
        cfg = apply_variant(cfg, variant)
        return _capsnet_cell(cfg, shape, rules, mesh)
    kind = cfg_lib.SHAPES[shape]["kind"]
    cfg = apply_variant(cfg, variant, kind)
    if kind == "train":
        return _train_cell(cfg, shape, rules, mesh)
    if kind == "prefill":
        return _prefill_cell(cfg, shape, rules, mesh)
    return _decode_cell(cfg, shape, rules, mesh)


# ---------------------------------------------------------------------------
# Runner
# ---------------------------------------------------------------------------


def run_cell(arch: str, shape: str, multi_pod: bool,
             out_dir: Optional[str] = None,
             hlo_dir: Optional[str] = None,
             variant: str = "base") -> Dict[str, Any]:
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    kind = (cfg_lib.SHAPES[shape]["kind"]
            if not arch.startswith("capsnet") else "train")
    rules = shard_lib.rules_for_arch(arch, kind=kind)
    t0 = time.time()
    fn, structs, in_sh, out_sh = build_cell(arch, shape, rules, mesh,
                                            variant)
    with jax.set_mesh(mesh):
        jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh)
        lowered = jitted.lower(*structs)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    coll = hlo_analysis.collective_stats(hlo)
    census = hlo_analysis.op_census(hlo)
    # trip-count-weighted costs (launch/hlo_cost.py): cost_analysis() counts
    # while bodies once, which undercounts scanned models by ~n_layers x.
    wc = hlo_cost.weighted_cost(hlo)

    rec = {
        "arch": arch,
        "shape": shape,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_chips": n_chips,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        # memory_analysis is per device
        "arg_bytes_per_dev": int(getattr(ma, "argument_size_in_bytes", 0)),
        "out_bytes_per_dev": int(getattr(ma, "output_size_in_bytes", 0)),
        "temp_bytes_per_dev": int(getattr(ma, "temp_size_in_bytes", 0)),
        "alias_bytes_per_dev": int(getattr(ma, "alias_size_in_bytes", 0)),
        # trip-count-weighted, per device (post-SPMD module)
        "flops_per_dev": float(wc.flops),
        "bytes_per_dev": float(wc.bytes),
        "transcendentals_per_dev": float(wc.transcendentals),
        "collective_bytes_per_dev": float(wc.collective_bytes),
        "collective_counts": {k: float(v)
                              for k, v in wc.collective_count.items()},
        "collective_bytes_by_kind": {
            k: float(v) for k, v in wc.collective_by_kind.items()},
        # raw (unweighted) cost_analysis for reference
        "raw_flops_per_dev": float(ca.get("flops", 0.0)),
        "raw_bytes_per_dev": float(ca.get("bytes accessed", 0.0)),
        "raw_collective_bytes_per_dev": int(coll.total_bytes),
        "op_census": census,
        "reshape_copy_bytes": hlo_analysis.reshape_transpose_bytes(hlo),
    }
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        fname = f"{arch}__{shape}__{rec['mesh']}.json"
        with open(os.path.join(out_dir, fname), "w") as f:
            json.dump(rec, f, indent=1)
    if hlo_dir:
        os.makedirs(hlo_dir, exist_ok=True)
        fname = f"{arch}__{shape}__{rec['mesh']}.hlo.txt"
        with open(os.path.join(hlo_dir, fname), "w") as f:
            f.write(hlo)
    return rec


CAPSNET_SHAPES = ["train_1k", "infer_1k"]


def all_cells(include_capsnet: bool = True):
    cells = list(cfg_lib.CELLS)
    if include_capsnet:
        cells += [(a, s) for a in cfg_lib.PAPER_ARCHS for s in CAPSNET_SHAPES]
    return cells


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--hlo-dir", default=None)
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--no-capsnet", action="store_true")
    ap.add_argument("--variant", choices=["base", "opt"], default="opt")
    args = ap.parse_args()

    require_virtual_devices(512)
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    if args.all:
        cells = all_cells(include_capsnet=not args.no_capsnet)
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    failures = []
    for arch, shape in cells:
        status = (cfg_lib.cell_status(arch, shape)
                  if not arch.startswith("capsnet") else None)
        if status:
            print(f"[skip] {arch:22s} {shape:12s} {status}")
            continue
        for mp in meshes:
            mesh_name = "2x16x16" if mp else "16x16"
            tag = f"{arch:22s} {shape:12s} {mesh_name}"
            if args.skip_existing and args.out:
                fname = os.path.join(args.out,
                                     f"{arch}__{shape}__{mesh_name}.json")
                if os.path.exists(fname):
                    print(f"[have] {tag}")
                    continue
            try:
                rec = run_cell(arch, shape, mp, args.out, args.hlo_dir,
                               variant=args.variant)
                print(f"[ ok ] {tag} "
                      f"flops/dev={rec['flops_per_dev']:.3e} "
                      f"coll={rec['collective_bytes_per_dev']:.3e}B "
                      f"temp={rec['temp_bytes_per_dev'] / 2**30:.2f}GiB "
                      f"compile={rec['compile_s']:.0f}s")
            except Exception as e:  # noqa: BLE001 — report every cell
                failures.append((arch, shape, mesh_name, repr(e)))
                print(f"[FAIL] {tag} {e!r}")
                traceback.print_exc(limit=3)
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f in failures:
            print("  ", *f)
        raise SystemExit(1)
    print("\nAll dry-run cells compiled.")


if __name__ == "__main__":
    main()
