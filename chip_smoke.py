#!/usr/bin/env python3
"""Smoke run of the FastCaps serving path on a TPU, through its own entry
points, with every Pallas kernel compiled (no interpret mode, no fallback).

    python chip_smoke.py              # one chip: capsnet-mnist + qwen3-1.7b
    python chip_smoke.py --chips 4    # four chips: the across-chip LM paths

One chip, two models at their published widths (random weights from a seed):

* capsnet-mnist, the paper's deployment: ``FastCapsPipeline`` build ->
  LAKP prune (type_keep=7) -> compact (1152 -> 252 capsules) -> compile
  with ``RoutingSpec.pallas(softmax="taylor")``; ragged ``ImageRequest``s
  served by ``CapsuleEngine``; classes compared with the same params under
  reference routing, capsule lengths of the ``pallas(exact)`` deployment
  compared with reference.  The unpruned 1152-capsule forward runs through
  the kernel too.  Matmuls are pinned to f32 ("highest") for the whole
  phase, so both sides of each comparison differ only in the routing code.
* qwen3-1.7b: 4 ragged greedy requests x 16 new tokens through a paged
  ``ServeEngine`` with ``decode_kernel=True`` (paged ``decode_attention``
  and ``fused_sampling``), compared with the same requests on
  ``decode_kernel=False`` sharing the one params object.

``--chips 4`` runs only the paths that exist across chips, each compared
with single-chip ``ServeEngine`` tokens: ``multihost_disaggregated_lm_engine``
(prefill and decode on disjoint submeshes, ``device_to_device`` handoffs)
and ``ShardedScheduler`` slot-sharded decode over all four chips, with
qwen3-1.7b at full width and ``FOUR_CHIP_LAYERS`` layers.

Every line but the last is a JSON record of one phase (wall time, compile
time, compile-cache hits, agreement numbers, peak device memory).  The last
line is ``{"ok": true, "device": {...}}``.  Without a TPU the script exits
non-zero before any phase runs.  The compile cache goes where
``JAX_COMPILATION_CACHE_DIR`` says, else to ``.jax_cache`` in the checkout;
with ``--chips 4`` it is off (see ``repro.launch.compile_cache``).
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

SEED = 0
CAPS_FRAMES = (1, 7, 16, 3, 29, 8, 32, 32)   # ragged requests, 128 frames
CAPS_AGREEMENT_MIN = 0.99                    # served vs reference classes
CAPS_LENGTH_TOL = 1e-4      # |capsule length| diff, pallas(exact) vs ref
LM_PROMPT_LENS = (5, 11, 16, 23)
LM_NEW_TOKENS = 16
LM_MAX_LEN = 64
LM_PAGE = 16
LM_LOGIT_TOL = 0.05         # max |logit diff| / max |logit|, one decode tick
# Four-chip layers: full width, depth cut.  At all 28 layers the TPU
# compiler (libtpu 0.0.34) on a four-chip v5e host died of a stack
# overflow in the SPMD partitioner (HandleGather -> ReplicatePartial)
# compiling the first multihost-disagg program; the same programs
# compile for a described v5e:2x2 without a chip, and at 2 layers they
# compile and run on the chip.
FOUR_CHIP_LAYERS = 2


def emit(phase: str, **record) -> None:
    print(json.dumps({"phase": phase, **record}, default=float), flush=True)


@contextlib.contextmanager
def timed(phase: str, record: dict):
    """Wall time and compile accounting of one phase, emitted at its end."""
    from repro.launch.compile_cache import cache_events

    before = cache_events()
    t0 = time.perf_counter()
    yield record
    after = cache_events()
    emit(phase, wall_s=time.perf_counter() - t0,
         compile_s=after["compile_s"] - before["compile_s"],
         cache_hits=after["hits"] - before["hits"],
         cache_misses=after["misses"] - before["misses"], **record)


def require_tpu(n_chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform "
                 f"{devices[0].platform!r}); nothing was run")
    if len(devices) < n_chips:
        sys.exit(f"chip_smoke: --chips {n_chips} needs {n_chips} TPU "
                 f"devices, JAX found {len(devices)}")
    return devices


def assert_kernel_compiled(jitted, *args) -> None:
    """The compiled program holds a Pallas kernel, not an interpreter."""
    text = jitted.lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no tpu_custom_call in compiled HLO"


# ---------------------------------------------------------------------------
# capsnet-mnist
# ---------------------------------------------------------------------------


def capsnet_phase(cfg) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.deploy import FastCapsPipeline, RoutingSpec
    from repro.serving import ImageRequest

    n = sum(CAPS_FRAMES)
    images = jax.random.uniform(jax.random.key(SEED + 1),
                                (n, cfg.image_hw, cfg.image_hw,
                                 cfg.in_channels))
    pipe = FastCapsPipeline(cfg).build(seed=SEED)

    with timed("capsnet_unpruned_kernel", {}) as rec:
        full = FastCapsPipeline(cfg, params=pipe.params)
        dep_k = full.compile(RoutingSpec.pallas(softmax="exact"))
        assert dep_k.spec.interpret is False, dep_k.spec
        assert_kernel_compiled(dep_k._forward, dep_k.params, images[:8])
        got = dep_k.forward(images[:8])
        want = full.compile("reference").forward(images[:8])
        diff = float(jnp.max(jnp.abs(got - want)))
        rec.update(capsules=cfg.n_primary_caps, frames=8,
                   max_length_diff=diff, tol=CAPS_LENGTH_TOL)
        assert cfg.n_primary_caps == 1152, cfg.n_primary_caps
        assert diff <= CAPS_LENGTH_TOL, diff

    with timed("capsnet_prune_compact", {}) as rec:
        pipe.prune(0.6, 0.9, type_keep=7).compact()
        rec.update(capsules=pipe.cfg.n_primary_caps,
                   compression=pipe.compression)
        assert pipe.cfg.n_primary_caps == 252, pipe.cfg.n_primary_caps

    with timed("capsnet_serve_pallas_taylor", {}) as rec:
        dep = pipe.compile(RoutingSpec.pallas(softmax="taylor"))
        assert dep.spec.interpret is False, dep.spec
        assert_kernel_compiled(dep._forward, dep.params, images[:32])
        engine = dep.serve(batch_size=32, kernel_tune=False)
        engine.warmup()
        frames = np.asarray(images)
        bounds = np.cumsum((0,) + CAPS_FRAMES)
        rids = [engine.submit(ImageRequest(images=frames[a:b]))
                for a, b in zip(bounds[:-1], bounds[1:])]
        done = {c.rid: c for c in engine.run_until_idle()}
        served = np.concatenate([done[r].classes for r in rids])
        ref_classes = np.asarray(pipe.compile("reference").classify(images))
        agree = float(np.mean(served == ref_classes))
        st = engine.stats()
        rec.update(requests=len(rids), frames=int(served.size),
                   ticks=st.ticks, agreement=agree, min=CAPS_AGREEMENT_MIN)
        assert served.shape == (n,) and agree >= CAPS_AGREEMENT_MIN, agree

    with timed("capsnet_pallas_exact_vs_reference", {}) as rec:
        got = pipe.compile(RoutingSpec.pallas(softmax="exact")).forward(images)
        want = pipe.compile("reference").forward(images)
        diff = float(jnp.max(jnp.abs(got - want)))
        rec.update(frames=n, max_length_diff=diff, tol=CAPS_LENGTH_TOL)
        assert bool(jnp.all(jnp.isfinite(got))) and diff <= CAPS_LENGTH_TOL


# ---------------------------------------------------------------------------
# qwen3-1.7b
# ---------------------------------------------------------------------------


def lm_requests(cfg):
    import numpy as np

    from repro.serving import Request

    rng = np.random.default_rng(SEED)
    return [Request(prompt=[int(t) for t in rng.integers(1, cfg.vocab, n)],
                    max_new_tokens=LM_NEW_TOKENS)
            for n in LM_PROMPT_LENS]


def lm_init(cfg):
    import jax

    from repro.models import lm

    params = jax.jit(lambda k: lm.init(cfg, k))(jax.random.key(SEED))
    return jax.block_until_ready(params)


def serve_tokens(engine, cfg):
    """Generated tokens per request, in submission order."""
    reqs = lm_requests(cfg)
    rids = [engine.submit(r) for r in reqs]
    done = {c.rid: c for c in engine.run_until_idle()}
    return [list(done[r].tokens[len(q.prompt):]) for r, q in zip(rids, reqs)]


def record_first_decode(engine):
    """Keep the logits of the engine's first paged decode tick."""
    import numpy as np

    step, seen = engine._decode_paged, []

    def recording(*args):
        out = step(*args)
        if not seen:
            seen.append(np.asarray(out[0], np.float32))
        return out

    engine._decode_paged = recording
    return seen


def qwen3_phase(cfg) -> None:
    import numpy as np

    from repro.serving import ServeEngine

    with timed("qwen3_init", {}) as rec:
        params = lm_init(cfg)
        rec.update(n_layers=cfg.n_layers, d_model=cfg.d_model,
                   vocab=cfg.vocab)

    runs = {}
    for kernel in (True, False):
        phase = "qwen3_paged_decode_kernel" if kernel else "qwen3_paged_jnp"
        with timed(phase, {}) as rec:
            eng = ServeEngine(cfg, params, n_slots=len(LM_PROMPT_LENS),
                              max_len=LM_MAX_LEN, seed=SEED,
                              page_size=LM_PAGE, decode_kernel=kernel,
                              kernel_tune=False)
            logits = record_first_decode(eng)
            tokens = serve_tokens(eng, cfg)
            runs[kernel] = (tokens, logits[0])
            st = eng.stats()
            rec.update(requests=len(tokens), new_tokens=st.items,
                       ticks=st.ticks)
            assert all(len(t) == LM_NEW_TOKENS for t in tokens), tokens
            assert np.all(np.isfinite(logits[0]))
            del eng

    (tok_k, lg_k), (tok_j, lg_j) = runs[True], runs[False]
    rel = float(np.max(np.abs(lg_k - lg_j)) / np.max(np.abs(lg_j)))
    first_same = [a[0] == b[0] for a, b in zip(tok_k, tok_j)]
    emit("qwen3_kernel_vs_jnp", first_tokens_identical=all(first_same),
         sequences_identical=sum(a == b for a, b in zip(tok_k, tok_j)),
         requests=len(tok_k), logit_rel_diff=rel, tol=LM_LOGIT_TOL,
         next_token_argmax_identical=bool(np.array_equal(
             lg_k.argmax(-1), lg_j.argmax(-1))))
    assert all(first_same), (tok_k, tok_j)
    assert rel <= LM_LOGIT_TOL, rel


# ---------------------------------------------------------------------------
# qwen3-1.7b across four chips
# ---------------------------------------------------------------------------


def _device_ids(tree) -> set:
    import jax

    return {d.id for leaf in jax.tree.leaves(tree)
            for d in leaf.sharding.device_set}


def four_chip_phase(cfg) -> None:
    import dataclasses

    import jax

    from repro.launch.mesh import make_mesh
    from repro.serving import (ServeEngine, ShardedScheduler,
                               multihost_disaggregated_lm_engine)

    cfg = dataclasses.replace(cfg, n_layers=FOUR_CHIP_LAYERS)
    with timed("qwen3_single_chip_reference", {}) as rec:
        params = lm_init(cfg)
        eng = ServeEngine(cfg, params, n_slots=len(LM_PROMPT_LENS),
                          max_len=LM_MAX_LEN, seed=SEED, kernel_tune=False)
        ref = serve_tokens(eng, cfg)
        rec.update(devices=sorted(_device_ids(eng._caches)),
                   n_layers=cfg.n_layers, d_model=cfg.d_model)
        # one host copy feeds the four-chip engines; chip 0 is freed first
        params = jax.device_get(params)
        del eng
        gc.collect()

    with timed("qwen3_multihost_disagg", {}) as rec:
        eng = multihost_disaggregated_lm_engine(
            cfg, params, n_slots=len(LM_PROMPT_LENS), max_len=LM_MAX_LEN,
            seed=SEED, n_decode=1, transport="device_to_device",
            kernel_tune=False)
        pre = {d.id for d in eng.prefill.scheduler.mesh.devices.flat}
        dec = {d.id for d in eng.decodes[0].scheduler.mesh.devices.flat}
        tokens = serve_tokens(eng, cfg)
        dec_caches = _device_ids(eng.decodes[0]._caches)
        rec.update(prefill_devices=sorted(pre), decode_devices=sorted(dec),
                   decode_cache_devices=sorted(dec_caches),
                   transport=eng.transport.name,
                   sequences_identical=sum(a == b for a, b in zip(tokens, ref)),
                   requests=len(ref))
        del eng
        gc.collect()
        assert not pre & dec and len(pre | dec) == 4, (pre, dec)
        assert dec_caches == dec, dec_caches
        assert tokens == ref, (tokens, ref)

    with timed("qwen3_sharded_decode", {}) as rec:
        sched = ShardedScheduler(make_mesh((4,), ("data",)))
        eng = ServeEngine(cfg, params, n_slots=len(LM_PROMPT_LENS),
                          max_len=LM_MAX_LEN, seed=SEED, scheduler=sched,
                          kernel_tune=False)
        tokens = serve_tokens(eng, cfg)
        cache_devices = _device_ids(eng._caches)
        rec.update(cache_devices=sorted(cache_devices),
                   sequences_identical=sum(a == b for a, b in zip(tokens, ref)),
                   requests=len(ref))
        del eng
        gc.collect()
        assert len(cache_devices) == 4, cache_devices
        assert tokens == ref, (tokens, ref)


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip LM paths")
    args = ap.parse_args(argv)

    devices = require_tpu(args.chips)
    import jax

    from repro import configs as cfg_lib
    from repro.kernels import tuning
    from repro.launch.compile_cache import cache_events, enable_compile_cache

    emit("setup", cache_dir=enable_compile_cache(), jax=jax.__version__,
         devices=len(devices), kind=devices[0].device_kind)
    t0 = time.perf_counter()
    qwen3 = cfg_lib.get_config("qwen3-1.7b")
    with tuning.tuning(False):
        if args.chips == 4:
            four_chip_phase(qwen3)
        else:
            with jax.default_matmul_precision("highest"):
                capsnet_phase(cfg_lib.get_config("capsnet-mnist"))
            qwen3_phase(qwen3)
    emit("total", wall_s=time.perf_counter() - t0, **cache_events(),
         peak_bytes_in_use=[d.memory_stats().get("peak_bytes_in_use")
                            for d in devices[:args.chips]])
    d = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
