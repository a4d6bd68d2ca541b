"""Tables II/III reproduction: resource accounting of original vs proposed
(pruned + optimized) CapsNet.  FPGA LUT/BRAM/DSP columns map to the TPU
deployment's analogues: parameter bytes (on-chip residency), index-memory
overhead, per-sample latency, and arithmetic-op census.

Both systems are built through ``repro.deploy.FastCapsPipeline`` and
timed via their compiled :class:`DeployedCapsNet` forwards."""

from __future__ import annotations

import jax

from benchmarks import common as bc
from repro.core import routing as routing_lib
from repro.deploy import FastCapsPipeline, RoutingSpec


def _bytes(params, dtype_bytes=4) -> int:
    return sum(int(x.size) for x in jax.tree.leaves(params)) * dtype_bytes


def run(quick: bool = True) -> dict:
    results = {}
    for variant, keep_frac in (("digits", 0.25), ("fashion", 0.375)):
        if quick and variant == "fashion":
            continue
        cfg = bc.bench_capsnet_cfg(quick)
        pipe = FastCapsPipeline(cfg).build(seed=0)
        dense_params = pipe.params
        dep_o = pipe.compile(routing="reference")
        pipe.prune(0.6, 0.9,
                   type_keep=max(int(cfg.caps_types * keep_frac), 1))
        pipe.compact()
        dep_p = pipe.compile(routing=RoutingSpec.pallas(softmax="taylor"))
        o_cfg = dep_p.cfg
        imgs = jax.random.uniform(jax.random.key(1), (1, 28, 28, 1))
        t_o = bc.time_fn(lambda: dep_o.forward(imgs))
        t_p = bc.time_fn(lambda: dep_p.forward(imgs))

        r_o = routing_lib.routing_flops(1, cfg.n_primary_caps,
                                        cfg.n_classes, cfg.digit_dim)
        r_p = routing_lib.routing_flops(1, o_cfg.n_primary_caps,
                                        o_cfg.n_classes, o_cfg.digit_dim)
        rows = [
            ["param bytes (16-bit deploy)",
             f"{_bytes(dense_params, 2):,}", f"{_bytes(dep_p.params, 2):,}"],
            ["routing weights",
             f"{dense_params['digit']['w'].size:,}",
             f"{dep_p.params['digit']['w'].size:,}"],
            ["primary capsules", f"{cfg.n_primary_caps}",
             f"{o_cfg.n_primary_caps}"],
            ["routing FLOPs/sample", f"{r_o:,}", f"{r_p:,}"],
            ["index overhead (frac of survivors)", "-",
             f"{pipe.index_overhead_frac:.5f}"],
            ["latency / sample (CPU, ms)", f"{t_o*1e3:.2f}",
             f"{t_p*1e3:.2f}"],
        ]
        bc.print_table(
            f"Table II/III analogue ({variant}): original vs proposed",
            ["resource", "original CapsNet", "proposed (pruned+opt)"],
            rows)
        results[variant] = {
            "param_bytes": (_bytes(dense_params, 2), _bytes(dep_p.params, 2)),
            "latency_ms": (t_o * 1e3, t_p * 1e3),
            "compression": pipe.compression,
        }
    return results


if __name__ == "__main__":
    bc.enable_compile_cache()
    run(quick=True)
