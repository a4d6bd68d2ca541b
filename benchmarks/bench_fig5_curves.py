"""Fig. 5 reproduction: accuracy-vs-compression curves for structured LAKP,
structured KP and unstructured magnitude pruning on the CapsNet
(no fine-tuning — Fig. 5 compares raw pruning robustness)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import common as bc
from repro.core import capsnet as cn
from repro.core import lakp as lakp_lib


def run(quick: bool = True) -> dict:
    cfg = bc.bench_capsnet_cfg(quick)
    steps = 80 if quick else 300
    params, data = bc.train_capsnet(cfg, "digits", steps)
    rates = [0.0, 0.3, 0.6, 0.8, 0.9, 0.97]
    rows, out = [], {}
    for s in rates:
        errs = {}
        for method in ("lakp", "kp"):
            masks = cn.lakp_masks(params, cfg, s, s, method=method)
            masked = cn.apply_masks(params, masks)
            errs[method] = bc.test_error(masked, cfg, data)
        # unstructured magnitude at the same global sparsity
        m1 = lakp_lib.unstructured_mask(params["conv1"]["w"], s)
        m2 = lakp_lib.unstructured_mask(params["conv2"]["w"], s)
        un = jax.tree.map(lambda x: x, params)
        un["conv1"] = dict(params["conv1"])
        un["conv2"] = dict(params["conv2"])
        un["conv1"]["w"] = params["conv1"]["w"] * m1
        un["conv2"]["w"] = params["conv2"]["w"] * m2
        errs["unstructured"] = bc.test_error(un, cfg, data)
        rows.append([f"{s*100:.0f}%", f"{errs['lakp']:.2f}",
                     f"{errs['kp']:.2f}", f"{errs['unstructured']:.2f}"])
        out[s] = errs
    bc.print_table(
        "Fig.5: test error (%) vs pruning rate (no fine-tune)",
        ["pruned", "LAKP (struct)", "KP (struct)", "magnitude (unstruct)"],
        rows)
    return out


if __name__ == "__main__":
    bc.enable_compile_cache()
    run(quick=True)
