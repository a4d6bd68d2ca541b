"""Fig. 1 reproduction: throughput of original / pruned / pruned+optimized
CapsNet (the paper's 5 -> 82 -> 1351 FPS structure, measured here as CPU
wall-clock FPS — the relative ordering and the two speedup factors are the
claim; absolute FPS are hardware-specific).

The paper's numbers are *served* throughput, so each system is measured
through the redesigned ``repro.serving`` engine: the Fig. 6 pipeline's
``DeployedCapsNet.serve()`` wraps it in a ``CapsuleEngine`` driven by the
``SLOBatchScheduler``, ragged image requests are submitted asynchronously,
and FPS comes from the engine's cumulative stats.

Also prints the modelled TPU-v5e FPS from the analytic FLOP count for the
same three systems (197 TFLOP/s roofline), connecting to §Roofline.

    PYTHONPATH=src python benchmarks/bench_fig1_throughput.py [--tiny]

``--tiny`` is the CI smoke mode: a shrunken model and a handful of frames,
just enough to exercise the serving path end to end.
"""

from __future__ import annotations

import argparse

import numpy as np

from benchmarks import common as bc
from repro.core import capsnet as cn
from repro.deploy import (FastCapsPipeline, RoutingSpec,
                          capsnet_flops_per_image)
from repro.serving import (CapsuleEngine, DisaggregatedEngine, ImageRequest,
                           SLOBatchScheduler)


TRANSPORT_KINDS = ("in_process", "host_staged", "device_to_device")


def run_transport(tiny: bool = False) -> dict:
    """Handoff Transport comparison: the same LM request mix served
    through :func:`repro.serving.multihost_disaggregated_lm_engine`
    (prefill and decode on disjoint submeshes) once per
    :class:`repro.serving.Transport` kind, with bit-exactness asserted
    across kinds and per-leg transfer latencies compared.

    The headline numbers are the per-delivery ``total`` p95s computed
    from each transport's ``records`` ring (the first delivery is
    dropped — it syncs against prefill's compile and would dominate a
    small-sample p95); the EngineStats transfer histograms are printed
    alongside.  On a >=2-device host ``device_to_device`` dispatches
    asynchronously and should beat ``host_staged``'s blocking
    d2h+h2d round trip — the emitted ``d2d_faster`` records that.
    """
    import jax

    from repro.models import lm
    from repro.models.common import LMConfig
    from repro.serving import (Request, make_transport,
                               multihost_disaggregated_lm_engine)

    if tiny:
        cfg = LMConfig(arch_id="xfer-tiny", family="dense", n_layers=4,
                       d_model=128, n_heads=8, n_kv_heads=4, d_ff=256,
                       vocab=128, remat=False, compute_dtype="float32",
                       param_dtype="float32")
        max_len, n_requests, max_new = 256, 6, 4
    else:
        cfg = LMConfig(arch_id="xfer-bench", family="dense", n_layers=6,
                       d_model=256, n_heads=8, n_kv_heads=4, d_ff=512,
                       vocab=256, remat=False, compute_dtype="float32",
                       param_dtype="float32")
        max_len, n_requests, max_new = 512, 12, 8
    params = lm.init(cfg, jax.random.key(0))
    rng = np.random.RandomState(0)
    # one prompt-length bucket, so prefill compiles once (on the warmup
    # request) and measured deliveries see steady-state staging costs
    prompts = [[int(t) for t in rng.randint(1, cfg.vocab, size=12)]
               for _ in range(n_requests)]

    out = {"device_count": jax.device_count(), "per_transport": {}}
    rows, baseline = [], None
    for kind in TRANSPORT_KINDS:
        transport = make_transport(kind)
        eng = multihost_disaggregated_lm_engine(
            cfg, params, n_slots=2, max_len=max_len, n_decode=1,
            transport=transport)
        warm = eng.serve([Request(prompt=prompts[0], max_new_tokens=max_new,
                                  rid=10_000)])
        assert len(warm) == 1
        comps = {c.rid: list(c.tokens) for c in eng.serve(
            [Request(prompt=p, max_new_tokens=max_new, rid=i)
             for i, p in enumerate(prompts)])}
        if baseline is None:
            baseline = comps
        elif comps != baseline:
            raise AssertionError(f"{kind} diverged from in_process output")

        recs = list(transport.records)[1:]          # drop compile-tainted warmup
        totals_ms = np.asarray([r.total_s for r in recs]) * 1e3
        entry = {
            "handoffs": len(recs),
            "nbytes_per_handoff": int(recs[0].nbytes) if recs else 0,
            "total_p50_ms": float(np.percentile(totals_ms, 50)),
            "total_p95_ms": float(np.percentile(totals_ms, 95)),
            "legs": {leg: {"p50_ms": float(np.percentile(v, 50)),
                           "p95_ms": float(np.percentile(v, 95))}
                     for leg in (recs[0].legs if recs else {})
                     for v in [np.asarray([r.legs[leg]
                                           for r in recs]) * 1e3]},
            "histograms": {stage: {"count": n, "p50_ms": p50, "p95_ms": p95}
                           for stage, (n, p50, p95)
                           in eng.stats().transfer_summary().items()},
        }
        out["per_transport"][kind] = entry
        rows.append([kind, f"{len(recs)}", f"{entry['nbytes_per_handoff']}",
                     f"{entry['total_p50_ms']:.3f}",
                     f"{entry['total_p95_ms']:.3f}",
                     " ".join(f"{leg}={s['p95_ms']:.3f}"
                              for leg, s in entry["legs"].items())])

    host = out["per_transport"]["host_staged"]["total_p95_ms"]
    d2d = out["per_transport"]["device_to_device"]["total_p95_ms"]
    out["host_staged_p95_ms"] = host
    out["device_to_device_p95_ms"] = d2d
    out["d2d_faster"] = bool(d2d < host)
    bc.print_table(
        f"Fig.1 (transport): handoff delivery latency per Transport "
        f"({out['device_count']} device(s), multihost disagg topology)",
        ["transport", "handoffs", "bytes", "total p50 ms", "total p95 ms",
         "leg p95s"], rows)
    print(f"[bench] device_to_device p95 {d2d:.3f}ms vs host_staged p95 "
          f"{host:.3f}ms -> d2d_faster={out['d2d_faster']}")
    return out


def run_paged(tiny: bool = False) -> dict:
    """Paged-KV capacity benchmark: dense slot cache vs the block-paged
    pool of ``repro.serving.pages`` at the **same cache memory** (equal
    KV rows).  A dense engine must reserve ``max_len`` rows per slot, so
    its resident capacity is fixed at ``n_slots``; the paged engine
    allocates per 16-token page as sequences grow, so the same rows hold
    several times more concurrent requests (the ``capacity_ratio``
    headline).  Both engines serve the identical request mix and the
    paged tokens are asserted bit-identical to dense.

    A second paged run with a shared system prompt measures the
    content-addressed prefix cache: the shared span is prefilled once
    and every later request pins the cached pages, so
    ``prefill_tokens_shared`` drops below the no-reuse total by
    ``prefix_tokens_saved`` (asserted > 0, with ``prefix_hits`` /
    ``prefix_pages_hit`` from the engine's page counters).
    """
    import jax

    from repro.models import lm
    from repro.models.common import LMConfig
    from repro.serving import Request, ServeEngine

    if tiny:
        cfg = LMConfig(arch_id="paged-tiny", family="dense", n_layers=2,
                       d_model=32, n_heads=4, n_kv_heads=2, d_ff=64,
                       vocab=64, remat=False, compute_dtype="float32",
                       param_dtype="float32")
        dense_slots, max_len, page_size, max_new = 2, 64, 8, 4
    else:
        cfg = LMConfig(arch_id="paged-bench", family="dense", n_layers=4,
                       d_model=64, n_heads=8, n_kv_heads=4, d_ff=128,
                       vocab=128, remat=False, compute_dtype="float32",
                       param_dtype="float32")
        dense_slots, max_len, page_size, max_new = 4, 128, 16, 8
    params = lm.init(cfg, jax.random.key(0))
    cache_rows = dense_slots * max_len        # the fixed memory budget
    n_pages = cache_rows // page_size

    rng = np.random.RandomState(0)
    prompts = []
    # short conversational requests: the dense layout strands most of
    # each slot's max_len reservation; paged allocates only used pages
    plen = (6, 12)

    def pages_for(n_tokens: int) -> int:
        return -(-n_tokens // page_size)

    worst_pages = pages_for(plen[1] + max_new)
    paged_slots = n_pages // worst_pages
    prompts = [[int(t) for t in rng.randint(1, cfg.vocab // 2,
                                            size=rng.randint(*plen))]
               for _ in range(paged_slots)]

    def drive(engine) -> tuple:
        """Serve every prompt; returns ({rid: tokens}, peak_resident)."""
        for i, p in enumerate(prompts):
            engine.submit(Request(prompt=p, max_new_tokens=max_new, rid=i))
        peak, done = 0, []
        while True:
            busy = engine.tick()
            peak = max(peak, engine.n_pending - engine.n_queued)
            done.extend(engine.poll())
            if not busy and engine.n_pending == 0:
                break
        return {c.rid: list(c.tokens) for c in done}, peak

    dense = ServeEngine(cfg, params, n_slots=dense_slots, max_len=max_len)
    dense_out, dense_peak = drive(dense)
    dense_stats = dense.stats()

    paged = ServeEngine(cfg, params, n_slots=paged_slots, max_len=max_len,
                        page_size=page_size, n_pages=n_pages)
    paged_out, paged_peak = drive(paged)
    paged_stats = paged.stats()
    assert paged_out == dense_out, "paged tokens diverged from dense"
    assert paged_peak > dense_peak, (
        f"paged resident peak {paged_peak} <= dense {dense_peak} at "
        f"equal cache memory")

    # prefix reuse: every request shares a system preamble; sequential
    # waves so later requests find the registered pages
    shared = [int(t) for t in rng.randint(1, cfg.vocab // 2,
                                          size=4 * page_size)]
    tails = [[int(t) for t in rng.randint(1, cfg.vocab // 2, size=4)]
             for _ in range(min(paged_slots, 4))]
    reuse = ServeEngine(cfg, params, n_slots=2, max_len=max_len,
                        page_size=page_size, n_pages=n_pages)
    for i, tail in enumerate(tails):
        reuse.serve([Request(prompt=shared + tail, max_new_tokens=max_new,
                             rid=100 + i)])
    rp = reuse.stats().pages
    full_tokens = sum(len(shared) + len(t) for t in tails)
    saved = full_tokens - int(rp.get("prefill_tokens", 0))
    assert rp.get("prefix_hits", 0) >= len(tails) - 1, rp
    assert saved > 0, (full_tokens, rp)

    out = {
        "page_size": page_size,
        "cache_rows": cache_rows,
        "n_pages": n_pages,
        "dense_slots": dense_slots,
        "paged_slots": paged_slots,
        "requests": len(prompts),
        "dense_resident_peak": int(dense_peak),
        "paged_resident_peak": int(paged_peak),
        "capacity_ratio": paged_peak / max(dense_peak, 1),
        "dense_ticks": int(dense_stats.ticks),
        "paged_ticks": int(paged_stats.ticks),
        "dense_tok_s": dense_stats.throughput,
        "paged_tok_s": paged_stats.throughput,
        "prefix_requests": len(tails),
        "prefill_tokens_no_share": full_tokens,
        "prefill_tokens_shared": int(rp.get("prefill_tokens", 0)),
        "prefix_tokens_saved": saved,
        "prefix_hits": int(rp.get("prefix_hits", 0)),
        "prefix_pages_hit": int(rp.get("prefix_pages_hit", 0)),
    }
    bc.print_table(
        f"Fig.1 (paged): resident capacity at equal cache memory "
        f"({cache_rows} KV rows, page_size={page_size})",
        ["layout", "slots", "resident peak", "ticks", "tok/s"],
        [["dense", f"{dense_slots}", f"{dense_peak}",
          f"{dense_stats.ticks}", f"{dense_stats.throughput:.1f}"],
         ["paged", f"{paged_slots}", f"{paged_peak}",
          f"{paged_stats.ticks}", f"{paged_stats.throughput:.1f}"]])
    print(f"[bench] paged holds {paged_peak}/{dense_peak} = "
          f"{out['capacity_ratio']:.1f}x residents at equal memory; "
          f"prefix cache saved {saved}/{full_tokens} prefill tokens "
          f"({rp.get('prefix_hits', 0)} hits, "
          f"{rp.get('prefix_pages_hit', 0)} pages)")
    return out


def run_decode_kernel(tiny: bool = False) -> dict:
    """Decode-path kernel benchmark: the paged decode tick through the
    ``decode_attention`` kernel (pool leaves read in place through the
    page tables via scalar prefetch, fresh row written into its page)
    vs the gather-to-dense baseline (materialize the dense
    ``(n_slots, max_len)`` view, ordinary decode, scatter the row back).

    Correctness first: the same greedy request mix is served through the
    dense engine, the paged gather engine, and the paged kernel engine
    (plus the int8-paged kernel engine), and the first three are
    asserted bit-identical.  Then the jitted decode tick itself is timed
    at full load — every slot's table fully mapped and every position
    valid, so both paths touch the whole pool.

    Two claims are checked, with different scope:

    * **Cache traffic (always)** — per tick the gather baseline
      materializes the dense ``(n_slots, max_len)`` view out of the pool
      and scatters the fresh row's pool back (two pool-sized copies);
      the kernel reads resident pages where they sit and writes one row
      per slot.  The modelled bytes moved must be strictly lower for the
      kernel path.  This is the structural advantage and it holds on
      every backend.
    * **Wall clock (compiled backends only)** — kernel-path tok/s is
      asserted >= the gather baseline only when the kernels run
      compiled (``needs_interpret()`` is False).  Under the Pallas
      interpreter every grid step is a Python-level loop iteration, so
      interpret-mode wall clock measures interpreter overhead, not the
      memory system; both numbers are still reported.
    """
    import time

    import jax
    import jax.numpy as jnp

    from repro import kernels
    from repro.models import lm
    from repro.models.common import LMConfig
    from repro.serving import Request, ServeEngine

    if tiny:
        cfg = LMConfig(arch_id="paged-tiny", family="dense", n_layers=2,
                       d_model=32, n_heads=4, n_kv_heads=2, d_ff=64,
                       vocab=64, remat=False, compute_dtype="float32",
                       param_dtype="float32")
        n_slots, max_len, page_size, max_new, iters = 2, 64, 8, 4, 30
    else:
        cfg = LMConfig(arch_id="paged-bench", family="dense", n_layers=4,
                       d_model=64, n_heads=8, n_kv_heads=4, d_ff=128,
                       vocab=128, remat=False, compute_dtype="float32",
                       param_dtype="float32")
        n_slots, max_len, page_size, max_new, iters = 4, 128, 16, 8, 50
    params = lm.init(cfg, jax.random.key(0))
    pk = dict(page_size=page_size, n_pages=n_slots * max_len // page_size)

    rng = np.random.RandomState(0)
    prompts = [[int(t) for t in rng.randint(1, cfg.vocab // 2,
                                            size=rng.randint(4, 12))]
               for _ in range(2 * n_slots)]

    def serve_all(eng) -> dict:
        comps = eng.serve([Request(prompt=p, max_new_tokens=max_new, rid=i)
                           for i, p in enumerate(prompts)])
        return {c.rid: list(c.tokens) for c in comps}

    dense_out = serve_all(ServeEngine(cfg, params, n_slots=n_slots,
                                      max_len=max_len))
    engines = {
        "paged_gather": ServeEngine(cfg, params, n_slots=n_slots,
                                    max_len=max_len, **pk),
        "paged_kernel": ServeEngine(cfg, params, n_slots=n_slots,
                                    max_len=max_len, decode_kernel=True,
                                    **pk),
        "paged_kernel_int8": ServeEngine(cfg, params, n_slots=n_slots,
                                         max_len=max_len,
                                         decode_kernel=True,
                                         quantize_pages=True, **pk),
    }
    outs = {name: serve_all(eng) for name, eng in engines.items()}
    assert outs["paged_gather"] == dense_out, \
        "paged gather tokens diverged from dense"
    assert outs["paged_kernel"] == dense_out, \
        "paged kernel tokens diverged from dense"

    def time_tick(eng) -> float:
        """Median seconds per jitted decode tick at full load: all
        tables mapped, all positions at the last row."""
        pages = eng._pages
        tables = jnp.arange(n_slots * pages.pages_per_slot,
                            dtype=jnp.int32).reshape(n_slots, -1)
        tok = jnp.asarray(rng.randint(1, cfg.vocab, size=(n_slots, 1)),
                          jnp.int32)
        pos = jnp.full((n_slots,), max_len - 1, jnp.int32)
        args = (eng.params, tok, pos, tables, eng._pool, eng._residual)
        jax.block_until_ready(eng._decode_paged(*args))   # compile
        ts = []
        for _ in range(iters):
            t0 = time.perf_counter()
            jax.block_until_ready(eng._decode_paged(*args))
            ts.append(time.perf_counter() - t0)
        return float(np.median(ts))

    ticks = {name: time_tick(eng) for name, eng in engines.items()}
    tok_s = {name: n_slots / t for name, t in ticks.items()}

    # Modelled kv-cache bytes moved per full-load decode tick, from the
    # float pool's actual leaf shapes (the int8 engine has a different
    # pool dtype, so the proxy compares the two same-dtype paths only).
    pool_bytes = sum(int(np.prod(leaf.shape)) * leaf.dtype.itemsize
                     for leaf in engines["paged_gather"]._pool.values())
    row_bytes = pool_bytes // max_len          # one token row, all slots
    cache_bytes = {
        # gather out of the pool + scatter the updated view back
        "paged_gather": 2 * pool_bytes,
        # in-place page reads + one fresh row write per slot
        "paged_kernel": pool_bytes + row_bytes,
    }
    assert cache_bytes["paged_kernel"] < cache_bytes["paged_gather"], (
        "kernel path moves no fewer cache bytes per tick than the "
        "gather baseline")

    interpret = kernels.needs_interpret()
    if not interpret:
        assert tok_s["paged_kernel"] >= tok_s["paged_gather"], (
            f"kernel-path paged decode {tok_s['paged_kernel']:.1f} tok/s "
            f"is below the gather-to-dense baseline "
            f"{tok_s['paged_gather']:.1f} tok/s")

    out = {
        "n_slots": n_slots, "max_len": max_len, "page_size": page_size,
        "decode_iters": iters, "interpret": interpret,
        "tokens_match_dense": True,
        "int8_tokens_match_dense": outs["paged_kernel_int8"] == dense_out,
        "tick_ms": {k: v * 1e3 for k, v in ticks.items()},
        "decode_tok_s": tok_s,
        "kernel_speedup": tok_s["paged_kernel"] / tok_s["paged_gather"],
        "cache_bytes_per_tick": cache_bytes,
        "cache_bytes_fraction": (cache_bytes["paged_kernel"]
                                 / cache_bytes["paged_gather"]),
    }
    bc.print_table(
        f"Fig.1 (decode kernel): paged decode tick at full load "
        f"({n_slots} slots x {max_len} tokens, page_size={page_size})",
        ["path", "ms/tick", "tok/s", "vs gather"],
        [[name, f"{ticks[name] * 1e3:.2f}", f"{tok_s[name]:.1f}",
          f"{tok_s[name] / tok_s['paged_gather']:.2f}x"]
         for name in ("paged_gather", "paged_kernel",
                      "paged_kernel_int8")])
    print(f"[bench] decode_attention kernel path: "
          f"{out['kernel_speedup']:.2f}x wall clock, "
          f"{out['cache_bytes_fraction']:.2f}x cache bytes/tick vs the "
          f"gather-to-dense baseline (int8 pages match dense: "
          f"{out['int8_tokens_match_dense']}"
          f"{'; interpret mode — wall clock not asserted' if interpret else ''})")
    return out


def _make_engine(deployed, batch: int, slo_ms: float, scheduler: str):
    """``slo``: the single SLO-scheduled CapsuleEngine.  ``disagg``: a
    DisaggregatedEngine front-end dispatching over a 2-engine pool (the
    stateless form of disaggregated serving) — same results, and the
    stats gain per-phase queue-depth + handoff transfer histograms."""
    if scheduler == "disagg":
        return DisaggregatedEngine(
            None, [CapsuleEngine(deployed, batch_size=batch,
                                 scheduler=SLOBatchScheduler(
                                     target_p95_ms=slo_ms))
                   for _ in range(2)])
    return deployed.serve(
        batch_size=batch,
        scheduler=SLOBatchScheduler(target_p95_ms=slo_ms))


def _serve_fps(deployed, n_frames: int, batch: int, slo_ms: float,
               seed: int = 0, scheduler: str = "slo") -> tuple:
    """Served FPS of one deployment: SLO-scheduled CapsuleEngine over a
    ragged request mix (frames per request drawn in [1, batch])."""
    engine = _make_engine(deployed, batch, slo_ms, scheduler)
    engine.warmup()
    cfg = deployed.cfg
    rng = np.random.RandomState(seed)
    served = 0
    while served < n_frames:
        n = int(rng.randint(1, batch + 1))
        engine.submit(ImageRequest(
            rng.rand(n, cfg.image_hw, cfg.image_hw,
                     cfg.in_channels).astype(np.float32)))
        served += n
    engine.run_until_idle()
    stats = engine.stats()
    return stats.fps, stats


def run(quick: bool = True, tiny: bool = False, slo_ms: float = 200.0,
        scheduler: str = "slo") -> dict:
    if tiny:
        cfg = cn.CapsNetConfig(arch_id="capsnet-smoke", conv1_channels=8,
                               caps_types=4, decoder_hidden=(16, 32))
        batch, n_frames = 4, 12
    else:
        cfg = bc.bench_capsnet_cfg(quick)
        batch = 64 if quick else 128
        n_frames = 3 * batch
    pipe = FastCapsPipeline(cfg).build(seed=0)

    # 1) original (reference routing, exact math)
    dep_orig = pipe.compile(routing="reference")
    fps_orig, st_orig = _serve_fps(dep_orig, n_frames, batch, slo_ms,
                                   scheduler=scheduler)

    # 2) pruned (LAKP + compaction), reference routing
    pipe.prune(0.6, 0.9,
               type_keep=max(cfg.caps_types // 4, 1)).compact()
    dep_pruned = pipe.compile(routing="reference")
    fps_pruned, st_pruned = _serve_fps(dep_pruned, n_frames, batch, slo_ms,
                                       scheduler=scheduler)

    # 3) pruned + optimized routing (fused pallas kernel + Eq.2 softmax)
    dep_opt = pipe.compile(routing=RoutingSpec.pallas(softmax="taylor"))
    fps_opt, st_opt = _serve_fps(dep_opt, n_frames, batch, slo_ms,
                                 scheduler=scheduler)

    fps = [fps_orig, fps_pruned, fps_opt]
    rows = []
    for name, f, st in (("original", fps_orig, st_orig),
                        ("pruned (LAKP)", fps_pruned, st_pruned),
                        ("pruned+optimized", fps_opt, st_opt)):
        rows.append([name, f"{st.ms_per_tick:.1f}", f"{st.frames}",
                     f"{f:.1f}", f"{f / fps_orig:.1f}x"])
    bc.print_table(
        f"Fig.1: served CapsNet throughput (CPU wall-clock, "
        f"scheduler={scheduler}, SLO p95<={slo_ms:.0f}ms)",
        ["system", "ms/tick", "frames", "FPS", "speedup"], rows)

    if scheduler == "disagg":
        # per-phase queue depth + handoff transfer latency (EngineStats)
        ph_rows = []
        for name, st in (("original", st_orig),
                         ("pruned (LAKP)", st_pruned),
                         ("pruned+optimized", st_opt)):
            for ph, (n, p50, p95, peak) in st.depth_summary().items():
                ph_rows.append([name, ph, f"{n}", f"{p50:.0f}",
                                f"{p95:.0f}", f"{peak}"])
            for stage, (n, p50, p95) in st.transfer_summary().items():
                ph_rows.append([name, f"xfer:{stage}", f"{n}",
                                f"{p50:.2f}ms", f"{p95:.2f}ms", "-"])
        bc.print_table(
            "Fig.1 (disagg): per-phase queue depth / handoff transfer",
            ["system", "phase", "ticks", "p50", "p95", "peak"], ph_rows)

    # request-latency histograms (EngineStats): p50/p95 per request class
    # (frames-per-request bucket) for each served system
    lat_rows = []
    for name, st in (("original", st_orig), ("pruned (LAKP)", st_pruned),
                     ("pruned+optimized", st_opt)):
        for cls, (n, p50, p95) in st.latency_summary().items():
            lat_rows.append([name, cls, f"{n}", f"{p50:.1f}", f"{p95:.1f}"])
    bc.print_table(
        "Fig.1: served request latency (per request class)",
        ["system", "class", "requests", "p50 ms", "p95 ms"], lat_rows)

    # modelled TPU FPS from routing+conv FLOPs (single chip, 50% MFU),
    # using the deploy pipeline's own FLOP accounting
    def model_fps(flops_per_image: int) -> float:
        return 0.5 * 197e12 / flops_per_image

    bc.print_table(
        "Fig.1 (modelled single-chip TPU-v5e FPS @50% MFU)",
        ["system", "FPS"],
        [["original", f"{model_fps(capsnet_flops_per_image(cfg)):.0f}"],
         ["pruned", f"{model_fps(dep_pruned.flops_per_image):.0f}"]])
    return {"fps": fps, "speedup_pruned": fps[1] / fps[0],
            "speedup_opt": fps[2] / fps[0]}


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke: shrunken model, a handful of frames")
    ap.add_argument("--full", action="store_true",
                    help="paper-scale settings (slow on CPU)")
    ap.add_argument("--slo-ms", type=float, default=200.0,
                    help="SLO scheduler p95 tick-latency target")
    ap.add_argument("--scheduler", default="slo", choices=["slo", "disagg"],
                    help="serving topology: one SLO-scheduled engine, or a "
                         "disaggregated front-end over an engine pool "
                         "(adds per-phase depth/transfer histograms)")
    ap.add_argument("--paged", action="store_true",
                    help="benchmark the paged KV cache instead of the "
                         "CapsNet sweep: resident capacity vs the dense "
                         "slot layout at equal cache memory, plus "
                         "prefix-cache prefill savings (emits a "
                         "fig1_paged record via --json)")
    ap.add_argument("--transport", action="store_true",
                    help="with --scheduler disagg: compare handoff "
                         "Transport kinds over the multihost LM topology "
                         "instead of the CapsNet sweep (emits a "
                         "fig1_transport record via --json)")
    ap.add_argument("--decode-kernel", action="store_true",
                    help="benchmark the paged decode_attention kernel "
                         "path against the gather-to-dense baseline "
                         "(token bit-identity asserted; emits a "
                         "fig1_decode record via --json)")
    ap.add_argument("--json", default=None,
                    help="write a BENCH_fig1.json perf-trajectory record")
    args = ap.parse_args()
    bc.enable_compile_cache()
    mode = "tiny" if args.tiny else ("full" if args.full else "quick")
    if args.paged:
        results = run_paged(tiny=args.tiny)
        if args.json:
            bc.write_bench_json(args.json, "fig1_paged", results,
                                mode=mode)
    elif args.decode_kernel:
        results = run_decode_kernel(tiny=args.tiny)
        if args.json:
            bc.write_bench_json(args.json, "fig1_decode", results,
                                mode=mode)
    elif args.transport:
        if args.scheduler != "disagg":
            ap.error("--transport requires --scheduler disagg")
        results = run_transport(tiny=args.tiny)
        if args.json:
            bc.write_bench_json(args.json, "fig1_transport", results,
                                mode=mode)
    else:
        results = run(quick=not args.full, tiny=args.tiny,
                      slo_ms=args.slo_ms, scheduler=args.scheduler)
        if args.json:
            results["scheduler"] = args.scheduler
            bc.write_bench_json(args.json, "fig1", results, mode=mode)
