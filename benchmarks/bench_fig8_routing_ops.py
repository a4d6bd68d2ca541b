"""Fig. 8 reproduction: per-operation cost of the dynamic routing loop,
non-optimized vs optimized (the paper reports HLS cycle counts; here we
report CPU wall-clock per op and the analytic FLOPs per op, plus the
fused-kernel whole-loop comparison that is the TPU analogue of the
PE-array pipeline).

The paper's Fig. 1/8 methodology is a *design-space search* over kernel
configurations, so this bench also sweeps the kernel registry's tuned
vs. default block sizes: for each registered kernel the autotuner
measures every legalized candidate config and the table reports the
deterministic default against the measured winner.  The base config is
always a candidate, so the tuned config is never slower than the old
hard-coded blocks on the measuring machine.
"""

from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp

from benchmarks import common as bc
from repro.core import approx_math as am
from repro.deploy import RoutingSpec, resolve
from repro.kernels import tuning as ktuning
from repro.kernels.registry import registry as kernel_registry
from repro.kernels.routing import ref as rref


def sweep_tuned_vs_default(quick: bool = True) -> dict:
    """Autotune each registered kernel at a bench shape; report the
    deterministic default config against the measured winner (both read
    from the same timing table, so the comparison is apples-to-apples)."""
    shapes = {
        "fused_routing": {"shape": (32, 10, 16, 252),
                          "softmax_mode": "taylor"},
        "taylor_softmax": {"shape": (32 * 252, 10)},
    }
    if not quick:
        shapes["flash_attention"] = {"dims": (1, 256, 256, 4, 2, 64)}
    rows, out = [], {}
    for name, case in shapes.items():
        spec = kernel_registry.get(name)
        if not spec.is_available():
            continue
        args, kwargs = spec.make_example(case)
        default = kernel_registry.default_config(name, *args, **kwargs)
        tuned, timings = ktuning.autotune(spec, args, kwargs,
                                          iters=2 if quick else 3)
        t_def = timings[ktuning.config_label(default)]
        t_tuned = timings[ktuning.config_label(tuned)]
        rows.append([name, ktuning.config_label(default), f"{t_def*1e3:.2f}",
                     ktuning.config_label(tuned), f"{t_tuned*1e3:.2f}",
                     f"{t_def / t_tuned:.2f}x"])
        out[name] = {"default": {"config": default, "seconds": t_def},
                     "tuned": {"config": tuned, "seconds": t_tuned},
                     "timings": timings}
    bc.print_table(
        "Kernel registry: tuned vs default block sizes (autotuner sweep)",
        ["kernel", "default cfg", "default ms", "tuned cfg", "tuned ms",
         "speedup"], rows)
    print(f"  autotune cache: {ktuning.default_cache().path}")
    return out


def run(quick: bool = True) -> dict:
    # pruned-MNIST routing shape from the paper: 252 capsules -> 10 x 16
    b, i, j, d = (32, 252, 10, 16) if quick else (128, 252, 10, 16)
    u = jax.random.normal(jax.random.key(0), (b, j, d, i)) * 0.2  # routing layout
    blog = jax.random.normal(jax.random.key(1), (b, j, i))
    c = jax.nn.softmax(blog, 1)
    s = jnp.einsum("bji,bjdi->bjd", c, u)
    v = am.squash(s)

    ops = {
        "softmax(exact)": jax.jit(lambda x: jax.nn.softmax(x, 1)),
        "softmax(taylor Eq.2)": jax.jit(
            lambda x: am.taylor_softmax(x, 1, range_reduce=True)),
        "softmax(taylor+Eq.3 div)": jax.jit(
            lambda x: am.taylor_softmax(x, 1, range_reduce=True,
                                        use_div_exp_log=True)),
        "FC (s=c.u)": jax.jit(
            lambda c_: jnp.einsum("bji,bjdi->bjd", c_, u)),
        "squash": jax.jit(lambda s_: am.squash(s_)),
        "squash(fast rsqrt)": jax.jit(lambda s_: am.squash_fast(s_)),
        "agreement (b+=u.v)": jax.jit(
            lambda v_: jnp.einsum("bjdi,bjd->bji", u, v_)),
    }
    args = {"softmax(exact)": blog, "softmax(taylor Eq.2)": blog,
            "softmax(taylor+Eq.3 div)": blog, "FC (s=c.u)": c,
            "squash": s, "squash(fast rsqrt)": s, "agreement (b+=u.v)": v}
    rows = []
    out = {}
    for name, fn in ops.items():
        t = bc.time_fn(lambda fn=fn, a=args[name]: fn(a))
        rows.append([name, f"{t*1e6:.0f}"])
        out[name] = t
    bc.print_table("Fig.8: per-op wall-clock (routing steps, us/op)",
                   ["operation", "us"], rows)

    # whole-loop: unfused reference vs fused VMEM-resident kernel, with the
    # fused variants resolved through the repro.deploy routing registry —
    # itself a thin view over the repro.kernels registry (interpret mode
    # and block sizes chosen there)
    fused_exact = resolve(RoutingSpec.pallas(softmax="exact"))
    fused_taylor = resolve(RoutingSpec.pallas(softmax="taylor"))
    t_ref = bc.time_fn(lambda: rref.fused_routing_ref(u)[0])
    t_fused = bc.time_fn(lambda: fused_exact(u)[0])
    t_fused_taylor = bc.time_fn(lambda: fused_taylor(u)[0])
    bc.print_table(
        "Routing loop: unfused vs fused kernel (3 iterations, ms)",
        ["variant", "ms"],
        [["unfused jnp (HBM round-trips)", f"{t_ref*1e3:.2f}"],
         ["fused pallas (VMEM-resident)", f"{t_fused*1e3:.2f}"],
         ["fused + taylor softmax", f"{t_fused_taylor*1e3:.2f}"]])
    print("  NOTE: the pallas kernel runs in interpret mode on CPU (python"
          " emulation);\n  its VMEM-residency win is a TPU property —"
          " see EXPERIMENTS.md §Roofline for the\n  dry-run-derived"
          " bytes-moved comparison, which is the hardware-relevant metric.")
    out.update({"loop_ref": t_ref, "loop_fused": t_fused,
                "loop_fused_taylor": t_fused_taylor})

    out["tuning"] = sweep_tuned_vs_default(quick=quick)
    return out


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale settings (slow on CPU)")
    ap.add_argument("--json", default=None,
                    help="write a BENCH_fig8.json perf-trajectory record")
    cli = ap.parse_args()
    bc.enable_compile_cache()
    results = run(quick=not cli.full)
    if cli.json:
        bc.write_bench_json(cli.json, "fig8", results,
                            mode="full" if cli.full else "quick")
