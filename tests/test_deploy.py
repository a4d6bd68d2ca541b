"""repro.deploy API: registry resolution/fallback, FastCapsPipeline
equivalence with the core free functions, CapsuleEngine batching."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import capsnet as cn
from repro.core import routing as routing_lib
from repro.deploy import (DeployedCapsNet, FastCapsPipeline, PipelineError,
                          RoutingSpec, normalize, registry, resolve)
from repro.serving import CapsuleEngine, ImageRequest


def tiny_cfg(**kw):
    base = dict(conv1_channels=16, caps_types=4, decoder_hidden=(32, 64))
    base.update(kw)
    return cn.CapsNetConfig(**base)


def u_hat(seed, b=2, i=24, j=10, d=16, scale=0.2):
    """Prediction vectors in the routing layout (B, J, D, I)."""
    return jax.random.normal(jax.random.key(seed), (b, j, d, i)) * scale


class TestRegistry:
    def test_variants_registered(self):
        assert {"reference", "optimized", "pallas"} <= set(registry.names())

    def test_unknown_mode_raises(self):
        with pytest.raises(ValueError, match="unknown routing mode"):
            resolve(RoutingSpec(mode="does-not-exist"))

    def test_unknown_named_spec_raises(self):
        with pytest.raises(ValueError, match="unknown routing variant"):
            RoutingSpec.named("hls")

    def test_bad_softmax_rejected(self):
        with pytest.raises(ValueError, match="softmax"):
            RoutingSpec(mode="optimized", softmax="newton")

    def test_pallas_interpret_probed_from_backend(self):
        """Off-TPU (tests force CPU) pallas must fall back to interpret
        mode — chosen by the registry probe, not hardcoded."""
        spec = normalize(RoutingSpec.pallas())
        assert spec.mode == "pallas"
        assert spec.interpret is (jax.default_backend() != "tpu")

    def test_pallas_interpret_pin_respected(self):
        spec = normalize(RoutingSpec.pallas(interpret=True))
        assert spec.interpret is True

    def test_unavailable_variant_falls_back(self):
        """An unavailable variant no longer falls back to another one,
        even when an available variant is registered: it raises, and
        the variant takes no fallback argument any more."""
        from repro.deploy.registry import RoutingRegistry, RoutingVariant

        reg = RoutingRegistry()
        reg.register(RoutingVariant("opt", lambda s: routing_lib.route_optimized))
        reg.register(RoutingVariant("fancy", lambda s: None,
                                    is_available=lambda: False))
        with pytest.raises(RuntimeError, match="'fancy' is unavailable"):
            reg.normalize(RoutingSpec(mode="fancy"))
        with pytest.raises(RuntimeError, match="unavailable"):
            reg.resolve(RoutingSpec(mode="fancy"))
        with pytest.raises(TypeError):
            RoutingVariant("x", lambda s: None, fallback="opt")

    def test_unavailable_without_fallback_raises(self):
        from repro.deploy.registry import RoutingRegistry, RoutingVariant

        reg = RoutingRegistry()
        reg.register(RoutingVariant("fancy", lambda s: None,
                                    is_available=lambda: False))
        with pytest.raises(RuntimeError, match="unavailable"):
            reg.normalize(RoutingSpec(mode="fancy"))

    def test_resolved_fns_agree_with_free_functions(self):
        uh = u_hat(0)
        v_reg, c_reg = resolve(RoutingSpec.optimized(softmax="exact"))(uh)
        v_ref, c_ref = routing_lib.route_reference(uh)
        np.testing.assert_allclose(np.asarray(v_reg), np.asarray(v_ref),
                                   atol=1e-6)
        np.testing.assert_allclose(np.asarray(c_reg), np.asarray(c_ref),
                                   atol=1e-6)

    def test_legacy_route_wrapper_gone(self):
        """The PR-1 deprecation cycle is finished: no free route()."""
        assert not hasattr(routing_lib, "route")

    def test_config_routing_spec_default_and_override(self):
        cfg = tiny_cfg()
        assert cfg.routing_spec() == RoutingSpec.reference()
        cfg2 = dataclasses.replace(
            cfg, routing=RoutingSpec.optimized(softmax="taylor"))
        assert cfg2.routing_spec() == RoutingSpec.optimized(softmax="taylor")


class TestFastCapsPipeline:
    def test_matches_core_free_functions(self):
        """Pipeline stages == the core mask/apply/compact free functions."""
        cfg = tiny_cfg()
        params = cn.init(cfg, jax.random.key(0))
        masks = cn.lakp_masks(params, cfg, 0.5, 0.75, type_keep=2)
        masked = cn.apply_masks(params, masks)
        compact_p, compact_cfg, _ = cn.compact(masked, cfg, masks)

        pipe = FastCapsPipeline(cfg, params=params)
        pipe.prune(0.5, 0.75, type_keep=2).compact()
        assert pipe.cfg == dataclasses.replace(
            compact_cfg, routing=pipe.cfg.routing)
        for a, b in zip(jax.tree.leaves(pipe.params),
                        jax.tree.leaves(compact_p)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert pipe.compression is not None
        assert pipe.index_overhead_frac is not None

    def test_compiled_forward_matches_free_function(self):
        cfg = tiny_cfg()
        pipe = FastCapsPipeline(cfg).build(seed=0)
        dep = pipe.compile(routing="reference")
        imgs = jax.random.uniform(jax.random.key(1), (3, 28, 28, 1))
        lengths_free, _ = cn.forward(pipe.params, cfg, imgs)
        np.testing.assert_allclose(np.asarray(dep.forward(imgs)),
                                   np.asarray(lengths_free), atol=1e-6)

    def test_optimized_agreement_on_fixed_seed(self):
        """Acceptance: optimized-vs-reference prediction agreement >= 99%."""
        pipe = FastCapsPipeline(tiny_cfg()).build(seed=0)
        pipe.prune(0.6, 0.9, type_keep=2).compact()
        dep_ref = pipe.compile(routing="reference")
        dep_opt = pipe.compile(routing=RoutingSpec.pallas(softmax="taylor"))
        imgs = jax.random.uniform(jax.random.key(1), (16, 28, 28, 1))
        agree = float(jnp.mean((dep_ref.classify(imgs)
                                == dep_opt.classify(imgs))))
        assert agree >= 0.99

    def test_stage_order_enforced(self):
        pipe = FastCapsPipeline(tiny_cfg())
        with pytest.raises(PipelineError):
            pipe.prune(0.5, 0.5)            # before build
        pipe.build()
        with pytest.raises(PipelineError):
            pipe.compact()                  # before prune
        pipe.prune(0.5, 0.5)
        with pytest.raises(PipelineError):
            pipe.build()                    # build twice
        pipe.compact()

    def test_deployed_is_immutable_with_accounting(self):
        pipe = FastCapsPipeline(tiny_cfg()).build(seed=0)
        dep = pipe.compile()
        assert isinstance(dep, DeployedCapsNet)
        assert dep.n_params == cn.param_count(pipe.params)
        assert dep.flops_per_image > 0
        with pytest.raises(dataclasses.FrozenInstanceError):
            dep.n_params = 0

    def test_save_roundtrip(self, tmp_path):
        from repro.checkpointing import checkpoint

        pipe = FastCapsPipeline(tiny_cfg()).build(seed=0)
        dep = pipe.compile()
        dep.save(str(tmp_path), step=3)
        assert (tmp_path / "deploy.json").exists()
        step, restored = checkpoint.load_latest(str(tmp_path), dep.params)
        assert step == 3
        for a, b in zip(jax.tree.leaves(restored),
                        jax.tree.leaves(dep.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_deploy_convenience(self):
        dep = FastCapsPipeline(tiny_cfg()).deploy(
            0.5, 0.75, type_keep=2, routing="optimized")
        assert dep.cfg.caps_types == 2
        assert dep.spec.mode == "optimized"


class TestCapsuleEngine:
    def _deployed(self, batch=4):
        pipe = FastCapsPipeline(tiny_cfg()).build(seed=0)
        return pipe.compile(routing="optimized")

    def _reqs(self, counts, seed=0):
        rng = np.random.RandomState(seed)
        return [ImageRequest(rng.rand(n, 28, 28, 1).astype(np.float32),
                             rid=i)
                for i, n in enumerate(counts)]

    def test_ragged_requests_complete(self):
        dep = self._deployed()
        eng = CapsuleEngine(dep, batch_size=4)
        comps = eng.serve(self._reqs([1, 5, 3, 2]))
        assert sorted(c.rid for c in comps) == [0, 1, 2, 3]
        assert [len(c.classes) for c in
                sorted(comps, key=lambda c: c.rid)] == [1, 5, 3, 2]
        stats = eng.stats()
        assert stats.frames == 11
        assert stats.batches == 3           # ceil(11 / 4)
        assert stats.padded_frames == 1

    def test_predictions_match_direct_forward(self):
        """Padding-to-batch and slot packing must not change predictions."""
        dep = self._deployed()
        eng = CapsuleEngine(dep, batch_size=4)
        reqs = self._reqs([3, 6])
        comps = {c.rid: c for c in eng.serve(reqs)}
        for r in reqs:
            direct = np.asarray(dep.classify(r.images))
            np.testing.assert_array_equal(comps[r.rid].classes, direct)

    def test_fps_stats_monotone(self):
        dep = self._deployed()
        eng = CapsuleEngine(dep, batch_size=4)
        eng.warmup()
        eng.serve(self._reqs([4, 2]))
        s1 = eng.stats()
        eng.serve(self._reqs([5], seed=1))
        s2 = eng.stats()
        assert s1.fps > 0
        assert s2.frames > s1.frames
        assert s2.batches > s1.batches
        assert s2.wall_s > s1.wall_s

    def test_bad_frame_shape_rejected(self):
        eng = CapsuleEngine(self._deployed(), batch_size=4)
        with pytest.raises(ValueError, match="request images"):
            eng.submit(ImageRequest(np.zeros((2, 14, 14, 1), np.float32)))

    def test_zero_frame_request_completes_empty(self):
        eng = CapsuleEngine(self._deployed(), batch_size=4)
        rid = eng.submit(ImageRequest(np.zeros((0, 28, 28, 1), np.float32)))
        comps = eng.run_until_idle()
        assert [c.rid for c in comps] == [rid]
        assert comps[0].classes.shape == (0,)
        assert eng._requests == {}          # no leaked in-flight entry

    def test_rid_auto_assignment(self):
        """Requests with rid=None get unique engine-assigned ids, also
        when mixed with explicit rids."""
        eng = CapsuleEngine(self._deployed(), batch_size=4)
        frames = np.zeros((1, 28, 28, 1), np.float32)
        r0 = eng.submit(ImageRequest(frames.copy()))
        r1 = eng.submit(ImageRequest(frames.copy(), rid=5))
        r2 = eng.submit(ImageRequest(frames.copy()))
        assert len({r0, r1, r2}) == 3
        assert r1 == 5 and r2 > 5
        comps = eng.run_until_idle()
        assert sorted(c.rid for c in comps) == sorted([r0, r1, r2])

    def test_duplicate_rid_rejected(self):
        eng = CapsuleEngine(self._deployed(), batch_size=4)
        eng.submit(ImageRequest(np.zeros((1, 28, 28, 1), np.float32),
                                rid=7))
        with pytest.raises(ValueError, match="duplicate"):
            eng.submit(ImageRequest(np.zeros((1, 28, 28, 1), np.float32),
                                    rid=7))
