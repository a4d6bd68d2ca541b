"""Serving launcher: LM decode or batched CapsNet image inference, both
through the unified ``repro.serving`` engine API
(``submit() / poll() / run_until_idle() / stats()``).

    # LM: continuous-batching ragged prefill + decode on a reduced config
    PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b \
        --reduced --requests 6 --max-new 12

    # LM, token-streaming, prefill/decode-interleaved ticks
    PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b \
        --reduced --scheduler interleave --stream

    # LM, KV caches sharded across every local device (slot-parallel)
    PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b \
        --reduced --scheduler sharded --slots 4

    # LM, paged KV cache: global page pool + per-slot page tables,
    # content-addressed prefix reuse, optional int8 cache pages
    PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b \
        --reduced --paged --page-size 16 --pages 64 --quantize-pages

    # LM, disaggregated: prefill engine + 2 decode engines, cache handoffs
    PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b \
        --reduced --scheduler disagg --decode-engines 2

    # LM, multi-host disaggregated: prefill/decode on disjoint submeshes,
    # handoffs staged through the host (or device_to_device / auto)
    PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b \
        --reduced --scheduler disagg --multihost --transport host_staged

    # CapsNet: FastCapsPipeline -> DeployedCapsNet.serve(), FPS report
    PYTHONPATH=src python -m repro.launch.serve --arch capsnet-mnist \
        --requests 8 --batch 16 --routing pallas --scheduler slo --slo-ms 50

    # Traffic replay: seeded bursty arrivals against an autoscaled
    # disaggregated pool, with priority preemption + SLO admission
    PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b \
        --reduced --trace bursty --autoscale --priority \
        --trace-rate 30 --trace-horizon 2 --decode-engines 3
"""

from __future__ import annotations

import argparse

import jax
import numpy as np

from repro import configs as cfg_lib
from repro.launch.compile_cache import enable_compile_cache
from repro.models import lm
from repro.serving import (DecodeEngine, DisaggregatedEngine, FIFOScheduler,
                           ImageRequest, InterleavingScheduler,
                           PriorityScheduler, Request, ServeEngine,
                           ShardedScheduler, SLOBatchScheduler,
                           disaggregated_lm_engine,
                           multihost_disaggregated_lm_engine)


def _make_scheduler(args):
    if args.scheduler == "slo":
        return SLOBatchScheduler(target_p95_ms=args.slo_ms)
    if args.scheduler == "interleave":
        return InterleavingScheduler()
    if args.scheduler == "sharded":
        from repro.launch.mesh import make_mesh

        n = jax.device_count()
        return ShardedScheduler(make_mesh((n,), ("data",)))
    if args.priority:
        return PriorityScheduler()
    return FIFOScheduler()


def _paged_kwargs(args) -> dict:
    """Page-pool / decode-kernel engine kwargs from the CLI flags."""
    kw = dict(decode_kernel=args.decode_kernel)
    if args.paged:
        kw.update(page_size=args.page_size, n_pages=args.pages,
                  quantize_pages=args.quantize_pages)
    return kw


def _print_pages(stats) -> None:
    if getattr(stats, "pages", None):
        summary = " ".join(f"{k}={v}" for k, v in
                           sorted(stats.pages.items()))
        print(f"  pages: {summary}")


def _print_latency(stats) -> None:
    for cls, (n, p50, p95) in stats.latency_summary().items():
        print(f"  latency[{cls}]: n={n} p50={p50:.1f} ms p95={p95:.1f} ms")
    for phase, (n, p50, p95, peak) in stats.depth_summary().items():
        print(f"  depth[{phase}]: ticks={n} p50={p50:.0f} p95={p95:.0f} "
              f"peak={peak}")
    for stage, (n, p50, p95) in stats.transfer_summary().items():
        print(f"  transfer[{stage}]: n={n} p50={p50:.2f} ms "
              f"p95={p95:.2f} ms")


def _print_phases(stats, unit: str) -> None:
    """Host time of each serving phase per item, and the mean queue wait
    (``EngineStats.phase_s``, ``queue_wait_s``, ``admitted``)."""
    for name, us in stats.phase_summary().items():
        print(f"  phase[{name}]: {us:.1f} us/{unit}")
    print(f"  queue wait: mean {stats.queue_wait_ms:.2f} ms over "
          f"{stats.admitted} admitted")


def _print_scale_events(events) -> None:
    if not events:
        print("  autoscale: no scale events")
        return
    for e in events:
        print(f"  autoscale[{e.action}]: t={e.t:.3f}s -> "
              f"{e.n_live} live engine(s)")


def serve_traffic(args) -> None:
    """Replay a seeded arrival trace (``--trace poisson|bursty``) against
    an LM engine — optionally a disaggregated pool with closed-loop
    autoscaling (``--autoscale``), priority preemption (``--priority``)
    and SLO admission control (``--admission``)."""
    from repro.traffic import (AutoscaleController, SLOAdmission,
                               bursty_trace, default_classes,
                               default_factory, poisson_trace, replay)

    cfg = cfg_lib.get_config(args.arch)
    if args.reduced:
        cfg = cfg_lib.reduced(cfg)
    if cfg.family == "audio":
        raise SystemExit("encoder-only arch has no decode path")
    params = lm.init(cfg, jax.random.key(0))

    classes = default_classes()
    if args.trace == "bursty":
        trace = bursty_trace(classes, rates=[args.trace_rate / 6,
                                             args.trace_rate],
                             dwell=[0.4, 0.2], horizon=args.trace_horizon,
                             seed=args.trace_seed)
    else:
        trace = poisson_trace(classes, rate=args.trace_rate,
                              horizon=args.trace_horizon,
                              seed=args.trace_seed)

    pk = _paged_kwargs(args)
    controller = None
    if args.autoscale:
        def mk():
            return DecodeEngine(cfg, params, n_slots=args.slots,
                                max_len=args.max_len, **pk)
        engine = disaggregated_lm_engine(
            cfg, params, n_slots=args.slots, max_len=args.max_len,
            n_decode=1, transport=args.transport,
            decode_schedulers=[PriorityScheduler()] if args.priority
            else None, **pk)
        controller = AutoscaleController(mk, min_engines=1,
                                         max_engines=args.decode_engines)
    elif args.scheduler == "disagg":
        engine = disaggregated_lm_engine(
            cfg, params, n_slots=args.slots, max_len=args.max_len,
            n_decode=args.decode_engines, transport=args.transport,
            decode_schedulers=[PriorityScheduler()
                               for _ in range(args.decode_engines)]
            if args.priority else None, **pk)
    else:
        engine = ServeEngine(cfg, params, n_slots=args.slots,
                             max_len=args.max_len,
                             scheduler=_make_scheduler(args), **pk)
    admission = SLOAdmission() if args.admission else None

    rep = replay(engine, trace,
                 factory=default_factory(trace, vocab=cfg.vocab // 2),
                 controller=controller, admission=admission)

    stats = rep.stats
    print(f"[{cfg.arch_id}] trace={args.trace} seed={args.trace_seed}: "
          f"{len(trace)} arrivals over {trace.horizon:.1f}s "
          f"({trace.rate():.1f} req/s)")
    print(f"  submitted={rep.submitted} completed={rep.completed} "
          f"rejected={rep.rejected} dropped={rep.dropped} "
          f"preempted={stats.preempted}")
    assert rep.dropped == 0, "never-dropped invariant violated"
    print(f"  served {stats.items} new tokens in {stats.wall_s:.2f}s "
          f"({stats.throughput:.1f} tok/s, {stats.ms_per_tick:.1f} "
          f"ms/tick)")
    _print_latency(stats)
    _print_pages(stats)
    if controller is not None:
        _print_scale_events(rep.scale_events)
        if rep.mean_live_engines is not None:
            print(f"  autoscale: mean live engines = "
                  f"{rep.mean_live_engines:.2f} "
                  f"(max {args.decode_engines})")


def serve_lm(args) -> None:
    cfg = cfg_lib.get_config(args.arch)
    if args.reduced:
        cfg = cfg_lib.reduced(cfg)
    if cfg.family == "audio":
        raise SystemExit("encoder-only arch has no decode path")
    params = lm.init(cfg, jax.random.key(0))
    if args.scheduler == "disagg":
        # disaggregated prefill: admission/prefill on a dedicated engine,
        # decode on --decode-engines engines joined by cache handoffs
        # delivered over --transport; --multihost places prefill and each
        # decode engine on disjoint submeshes (handoffs cross meshes)
        factory = (multihost_disaggregated_lm_engine if args.multihost
                   else disaggregated_lm_engine)
        engine = factory(
            cfg, params, n_slots=args.slots, max_len=args.max_len,
            n_decode=args.decode_engines,
            kernel_tune=args.kernel_tune or None,
            transport=args.transport, **_paged_kwargs(args))
    else:
        engine = ServeEngine(cfg, params, n_slots=args.slots,
                             max_len=args.max_len,
                             scheduler=_make_scheduler(args),
                             kernel_tune=args.kernel_tune or None,
                             **_paged_kwargs(args))
    if args.kernel_tune:
        engine.warmup()
    rng = np.random.RandomState(0)
    reqs = [Request(prompt=list(rng.randint(1, cfg.vocab // 2,
                                            size=rng.randint(3, 9))),
                    max_new_tokens=args.max_new, rid=i, stream=args.stream)
            for i in range(args.requests)]
    if args.stream:
        # token-level results as they are generated (poll(stream=True))
        for r in reqs:
            engine.submit(r)
        completions = []
        while True:
            busy = engine.tick()
            for ev in engine.poll(stream=True):
                if ev.done:
                    completions.append(ev.completion)
                    print(f"  rid={ev.rid}: done")
                else:
                    print(f"  rid={ev.rid} #{ev.seq}: token {ev.item}")
            if not busy and engine.n_pending == 0:
                break
        engine.poll()                      # drain the compat channel
    else:
        completions = engine.serve(reqs)
    stats = engine.stats()
    # Completion.tokens includes the prompt; stats count generated tokens.
    print(f"[{cfg.arch_id}] served {stats.completed} requests "
          f"({stats.items} new tokens) in {stats.wall_s:.2f}s "
          f"({stats.throughput:.1f} tok/s, "
          f"{stats.ms_per_tick:.1f} ms/tick)")
    _print_latency(stats)
    _print_pages(stats)
    for c in sorted(completions, key=lambda c: c.rid):
        print(f"  rid={c.rid}: latency={c.latency_s * 1e3:.0f} ms "
              f"{c.tokens}")
    _print_phases(stats, "token")


def serve_capsnet(args) -> None:
    """The paper's deployment path: prune -> compact -> compile -> serve."""
    from repro.deploy import FastCapsPipeline

    cfg = cfg_lib.get_config(args.arch)
    if args.reduced:
        cfg = cfg_lib.reduced(cfg)
    pipe = FastCapsPipeline(cfg).build(seed=0)
    if args.sparsity > 0:
        pipe.prune(args.sparsity, args.sparsity,
                   type_keep=max(cfg.caps_types // 4, 1)).compact()
    deployed = pipe.compile(routing=args.routing)
    print(f"[{cfg.arch_id}] deployed: routing={deployed.spec.mode}"
          f"(softmax={deployed.spec.softmax}) "
          f"{deployed.n_params:,} params, "
          f"{deployed.flops_per_image / 1e6:.1f} MFLOP/image")

    if args.scheduler == "disagg":
        # stateless disaggregation: dispatch frames over an engine pool
        engine = DisaggregatedEngine(
            None, [deployed.serve(batch_size=args.batch,
                                  kernel_tune=args.kernel_tune or None)
                   for _ in range(args.decode_engines)])
    else:
        engine = deployed.serve(batch_size=args.batch,
                                scheduler=_make_scheduler(args),
                                kernel_tune=args.kernel_tune or None)
    engine.warmup()
    rng = np.random.RandomState(0)
    for i in range(args.requests):
        engine.submit(ImageRequest(
            images=rng.rand(rng.randint(1, 2 * args.batch),
                            cfg.image_hw, cfg.image_hw,
                            cfg.in_channels).astype(np.float32),
            rid=i))
    completions = engine.run_until_idle()
    stats = engine.stats()
    print(f"  served {stats.completed} requests / {stats.frames} frames "
          f"in {stats.batches} ticks ({stats.padded_frames} pad): "
          f"{stats.fps:.1f} FPS, {stats.ms_per_batch:.2f} ms/tick")
    _print_latency(stats)
    for c in sorted(completions, key=lambda c: c.rid):
        print(f"  rid={c.rid}: {len(c.classes)} frames, "
              f"latency={c.latency_s * 1e3:.1f} ms, "
              f"classes={c.classes[:8].tolist()}"
              f"{'...' if len(c.classes) > 8 else ''}")
    _print_phases(stats, "frame")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=cfg_lib.list_archs())
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="CPU-smoke-sized config (--no-reduced for the "
                         "published size)")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--scheduler", default="fifo",
                    choices=["fifo", "slo", "interleave", "sharded",
                             "disagg"],
                    help="tick scheduler (slo adapts batch to --slo-ms; "
                         "interleave separates prefill/decode ticks; "
                         "sharded places slots across all local devices; "
                         "disagg splits prefill and decode onto separate "
                         "engines joined by cache handoffs)")
    ap.add_argument("--slo-ms", type=float, default=100.0,
                    help="SLO scheduler p95 tick-latency target")
    ap.add_argument("--decode-engines", type=int, default=2,
                    help="disagg: number of decode engines behind the "
                         "prefill engine")
    ap.add_argument("--transport", default="auto",
                    choices=["auto", "in_process", "host_staged",
                             "device_to_device"],
                    help="disagg: cache-handoff delivery route (auto "
                         "selects by mesh placement — device-to-device "
                         "when decode owns a different mesh than prefill, "
                         "in-process otherwise)")
    ap.add_argument("--multihost", action="store_true",
                    help="disagg (LM): place prefill and each decode "
                         "engine on disjoint submeshes over the local "
                         "devices, so cache handoffs genuinely cross a "
                         "device boundary")
    # LM options
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--paged", action="store_true",
                    help="LM: block-paged KV cache (global page pool + "
                         "per-slot page tables) with content-addressed "
                         "prefix reuse across requests")
    ap.add_argument("--page-size", type=int, default=16,
                    help="paged: tokens per cache page")
    ap.add_argument("--pages", type=int, default=None,
                    help="paged: total pool pages (default sizes the "
                         "pool to n_slots * max_len tokens)")
    ap.add_argument("--quantize-pages", action="store_true",
                    help="paged: store KV pages as int8 with per-row "
                         "scales, dequantized on read in-kernel")
    ap.add_argument("--decode-kernel", action="store_true",
                    help="decode through the Pallas decode_attention "
                         "kernel (paged caches read in place through the "
                         "page tables; int8 pages dequantize in-kernel) "
                         "and draw tokens on device via fused_sampling")
    ap.add_argument("--kernel-tune", action="store_true",
                    help="autotune kernel block sizes at warm-up and bind "
                         "the winners into the tick executables")
    ap.add_argument("--stream", action="store_true",
                    help="LM: print token-level StreamEvents as they are "
                         "generated (poll(stream=True))")
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--max-len", type=int, default=128)
    # Traffic-replay options
    ap.add_argument("--trace", default="none",
                    choices=["none", "poisson", "bursty"],
                    help="replay a seeded arrival trace instead of a "
                         "fixed request batch (LM only)")
    ap.add_argument("--trace-rate", type=float, default=20.0,
                    help="mean arrival rate (req/s); bursty uses it as "
                         "the burst-state rate")
    ap.add_argument("--trace-horizon", type=float, default=2.0,
                    help="trace length in seconds")
    ap.add_argument("--trace-seed", type=int, default=0,
                    help="trace RNG seed (same seed -> same arrivals "
                         "and payloads)")
    ap.add_argument("--autoscale", action="store_true",
                    help="traffic: start one decode engine and let the "
                         "depth-signal controller grow/drain the pool "
                         "up to --decode-engines")
    ap.add_argument("--priority", action="store_true",
                    help="PriorityScheduler: urgent classes admit first "
                         "and may preempt (lossless) resident work")
    ap.add_argument("--admission", action="store_true",
                    help="traffic: SLO admission control (shed arrivals "
                         "whose class SLO is already unattainable)")
    # CapsNet options
    ap.add_argument("--batch", type=int, default=16,
                    help="CapsuleEngine capacity (max frames per tick)")
    ap.add_argument("--routing", default="pallas",
                    choices=["reference", "optimized", "pallas"])
    ap.add_argument("--sparsity", type=float, default=0.6,
                    help="LAKP sparsity for both conv layers (0 = dense)")
    args = ap.parse_args()
    enable_compile_cache()
    if args.multihost and args.scheduler != "disagg":
        ap.error("--multihost requires --scheduler disagg")
    if args.paged and args.arch.startswith("capsnet"):
        ap.error("--paged applies to LM serving only")
    if args.arch.startswith("capsnet"):
        serve_capsnet(args)
    elif args.trace != "none":
        serve_traffic(args)
    else:
        serve_lm(args)


if __name__ == "__main__":
    main()
