"""Disaggregated prefill: PrefillEngine -> CacheHandoff -> DecodeEngine.

Covers the tentpole guarantees:

  * exactness — `DisaggregatedEngine` output matches per-request
    ``generate()`` bit-for-bit for dense/vlm/ssm/hybrid tiny configs
    (recurrent families ride the length-bucketed prefill path), on this
    host and on a forced 2-device host with sharded decode (subprocess);
  * streaming — per-rid StreamEvent ordering holds across the handoff
    boundary, and the done event carries the end-to-end completion;
  * fault injection — a decode engine rejects a mismatched handoff
    (dtype/shape/model-family) with a clear error before any state
    changes, and a decode engine killed mid-handoff — or a transport
    route erroring mid-transfer — causes a requeue + failover onto a
    surviving route, never a dropped request, under every transport;
  * stats — per-phase queue-depth and handoff transfer-latency
    histograms (including the per-transport per-leg keys) populate and
    aggregate.
"""

import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from engine_testlib import FlakyTransport
from repro.models import lm
from repro.models.common import LMConfig, SSMConfig, XLSTMConfig
from repro.serving import (CacheHandoff, DecodeEngine, DisaggregatedEngine,
                           HandoffRequest, PrefillEngine, Request,
                           ServeEngine, disaggregated_lm_engine,
                           multihost_disaggregated_lm_engine)

PROMPTS = [[1, 2, 3], [5, 6, 7, 8, 9, 10, 11], [2, 4]]
TRANSPORTS = ["in_process", "host_staged", "device_to_device"]


def tiny(family="dense", **kw):
    base = dict(arch_id="tiny-" + family, family=family, n_layers=2,
                d_model=32, n_heads=4, n_kv_heads=2, d_ff=64, vocab=64,
                remat=False, compute_dtype="float32", param_dtype="float32")
    base.update(kw)
    return LMConfig(**base)


def cfg_for(family):
    if family == "dense":
        return tiny()
    if family == "vlm":
        return tiny("vlm", n_layers=3, cross_attn_every=2, n_image_tokens=8)
    if family == "ssm":
        return tiny("ssm", d_model=16, n_heads=2, d_ff=0, vocab=32,
                    xlstm=XLSTMConfig(slstm_every=2, chunk_size=8))
    if family == "hybrid":
        return tiny("hybrid", d_model=16, n_heads=2, d_ff=32, vocab=32,
                    hybrid_attn_every=2,
                    ssm=SSMConfig(d_state=4, d_conv=4, expand=2, head_dim=8,
                                  n_groups=1, chunk_size=8))
    raise ValueError(family)


class TestExactness:
    """Acceptance: disaggregated serving == per-request generation."""

    @pytest.mark.parametrize("family", ["dense", "vlm", "ssm", "hybrid"])
    def test_matches_per_request_generate(self, family):
        cfg = cfg_for(family)
        params = lm.init(cfg, jax.random.key(0))
        n_decode = 2 if family == "dense" else 1
        eng = disaggregated_lm_engine(cfg, params, n_slots=2, max_len=32,
                                      n_decode=n_decode)
        ref = ServeEngine(cfg, params, n_slots=2, max_len=32)
        reqs = [Request(prompt=p, max_new_tokens=4, rid=i)
                for i, p in enumerate(PROMPTS)]
        comps = {c.rid: c for c in eng.serve(reqs)}
        for i, p in enumerate(PROMPTS):
            want = ref.generate([p], max_new_tokens=4)[0]
            assert comps[i].tokens == want, (family, i)

    @pytest.mark.parametrize("family", ["dense", "vlm", "ssm", "hybrid"])
    @pytest.mark.parametrize("transport", ["host_staged",
                                           "device_to_device"])
    def test_matches_generate_under_every_transport(self, family, transport):
        """The acceptance matrix: every moving transport variant stays
        bit-exact vs per-request generation for every cache family (the
        in-process default is the matrix's third row, pinned by
        ``test_matches_per_request_generate`` above)."""
        cfg = cfg_for(family)
        params = lm.init(cfg, jax.random.key(0))
        eng = disaggregated_lm_engine(cfg, params, n_slots=2, max_len=32,
                                      n_decode=2, transport=transport)
        ref = ServeEngine(cfg, params, n_slots=2, max_len=32)
        reqs = [Request(prompt=p, max_new_tokens=4, rid=i)
                for i, p in enumerate(PROMPTS)]
        comps = {c.rid: c for c in eng.serve(reqs)}
        for i, p in enumerate(PROMPTS):
            want = ref.generate([p], max_new_tokens=4)[0]
            assert comps[i].tokens == want, (family, transport, i)
        st = eng.stats()
        assert st.transfer[f"{transport}/total"].count == len(PROMPTS)

    def test_multihost_distinct_meshes_exact(self):
        """Prefill and decode engines on their own meshes (degenerate
        shared-device submeshes on a 1-device host — the 2-device case
        runs in the subprocess test below): still bit-exact, with the
        auto-selected transport."""
        cfg = cfg_for("dense")
        params = lm.init(cfg, jax.random.key(0))
        eng = multihost_disaggregated_lm_engine(cfg, params, n_slots=2,
                                                max_len=32, n_decode=1)
        ref = ServeEngine(cfg, params, n_slots=2, max_len=32)
        reqs = [Request(prompt=p, max_new_tokens=4, rid=i)
                for i, p in enumerate(PROMPTS)]
        comps = {c.rid: c for c in eng.serve(reqs)}
        for i, p in enumerate(PROMPTS):
            assert comps[i].tokens == ref.generate([p],
                                                   max_new_tokens=4)[0]

    def test_zero_new_tokens_identity(self):
        cfg = cfg_for("dense")
        eng = disaggregated_lm_engine(cfg, lm.init(cfg, jax.random.key(0)),
                                      n_slots=2, max_len=32)
        comps = eng.serve([Request(prompt=[4, 5, 6], max_new_tokens=0)])
        assert comps[0].tokens == [4, 5, 6]
        assert eng.stats().completed == 1

    def test_single_token_finishes_at_prefill(self):
        """max_new_tokens=1 is fully served by the prefill side; the done
        handoff still routes through decode so stream/stat accounting is
        one path."""
        cfg = cfg_for("dense")
        params = lm.init(cfg, jax.random.key(0))
        eng = disaggregated_lm_engine(cfg, params, n_slots=2, max_len=32)
        ref = ServeEngine(cfg, params, n_slots=2, max_len=32)
        comps = eng.serve([Request(prompt=[1, 2, 3], max_new_tokens=1)])
        assert comps[0].tokens == ref.generate([[1, 2, 3]],
                                               max_new_tokens=1)[0]


class TestStreaming:
    def test_token_order_across_handoff_boundary(self):
        cfg = cfg_for("dense")
        params = lm.init(cfg, jax.random.key(0))
        eng = disaggregated_lm_engine(cfg, params, n_slots=2, max_len=32,
                                      n_decode=2)
        ref = ServeEngine(cfg, params, n_slots=2, max_len=32)
        rids = [eng.submit(Request(prompt=p, max_new_tokens=3, stream=True))
                for p in PROMPTS]
        comps = {c.rid: c for c in eng.run_until_idle()}
        per_rid = {r: [] for r in rids}
        for ev in eng.poll(stream=True):
            per_rid[ev.rid].append(ev)
        for r, p in zip(rids, PROMPTS):
            evs = per_rid[r]
            assert [e.seq for e in evs] == list(range(len(evs)))
            assert evs[-1].done and evs[-1].item is None
            toks = [e.item for e in evs if not e.done]
            assert len(toks) == 3     # one event per generated token,
            #                           starting with the prefill-sampled one
            assert comps[r].tokens == list(p) + toks
            assert comps[r].tokens == ref.generate([p], max_new_tokens=3)[0]
            # the done event carries the same (end-to-end) completion
            assert evs[-1].completion is comps[r]


class TestStats:
    def test_phase_depth_and_transfer_histograms(self):
        cfg = cfg_for("dense")
        eng = disaggregated_lm_engine(cfg, lm.init(cfg, jax.random.key(0)),
                                      n_slots=2, max_len=32)
        eng.serve([Request(prompt=p, max_new_tokens=3, rid=i)
                   for i, p in enumerate(PROMPTS)])
        st = eng.stats()
        assert st.completed == 3
        assert st.items == 3 * 3      # generated tokens across both engines
        assert set(st.depth) >= {"prefill", "handoff", "decode"}
        assert st.depth["handoff"].peak >= 1
        assert st.transfer["handoff"].count == 3   # one transfer per request
        # per-transport per-leg critical-path histograms, one entry per
        # delivered handoff (default transport: in_process)
        assert st.transfer["in_process/pass"].count == 3
        assert st.transfer["in_process/total"].count == 3
        assert st.latency_summary() and st.depth_summary() \
            and st.transfer_summary()

    def test_snapshot_detached_and_monotone(self):
        cfg = cfg_for("dense")
        eng = disaggregated_lm_engine(cfg, lm.init(cfg, jax.random.key(0)),
                                      n_slots=2, max_len=32)
        eng.serve([Request(prompt=[1, 2], max_new_tokens=2)])
        s1 = eng.stats()
        eng.serve([Request(prompt=[3, 4], max_new_tokens=2)])
        s2 = eng.stats()
        assert s1.completed == 1 and s2.completed == 2
        assert s2.items > s1.items and s2.ticks > s1.ticks
        for k, h in s1.depth.items():
            assert s2.depth[k].count >= h.count
        assert s1.transfer["handoff"].count == 1   # detached snapshot


def _one_handoff(cfg, params, prompt=(1, 2, 3), max_new=4):
    pre = PrefillEngine(cfg, params, n_slots=2, max_len=32)
    pre.submit(Request(prompt=list(prompt), max_new_tokens=max_new))
    (h,) = pre.run_until_idle()
    assert isinstance(h, CacheHandoff)
    return h


class TestHandoffValidation:
    """Fault injection: a decode engine must refuse a handoff it cannot
    decode exactly — no silent garbage decode."""

    def setup_method(self, method):
        self.cfg = cfg_for("dense")
        self.params = lm.init(self.cfg, jax.random.key(0))

    def test_family_mismatch_rejected(self):
        h = _one_handoff(self.cfg, self.params)
        other = cfg_for("ssm")
        dec = DecodeEngine(other, lm.init(other, jax.random.key(0)),
                           n_slots=2, max_len=32)
        with pytest.raises(ValueError, match="family"):
            dec.submit(HandoffRequest(handoff=h))

    def test_max_len_mismatch_rejected(self):
        h = _one_handoff(self.cfg, self.params)
        dec = DecodeEngine(self.cfg, self.params, n_slots=2, max_len=64)
        with pytest.raises(ValueError, match="max_len"):
            dec.submit(HandoffRequest(handoff=h))

    def test_dtype_mismatch_rejected(self):
        h = _one_handoff(self.cfg, self.params)
        h.rows = jax.tree.map(lambda x: x.astype("float16"), h.rows)
        dec = DecodeEngine(self.cfg, self.params, n_slots=2, max_len=32)
        with pytest.raises(ValueError, match="dtype"):
            dec.submit(HandoffRequest(handoff=h))

    def test_shape_mismatch_rejected(self):
        h = _one_handoff(self.cfg, self.params)
        h.rows = jax.tree.map(lambda x: x[..., :-1], h.rows)
        dec = DecodeEngine(self.cfg, self.params, n_slots=2, max_len=32)
        with pytest.raises(ValueError, match="shape"):
            dec.submit(HandoffRequest(handoff=h))

    def test_rejection_leaves_engine_clean(self):
        """A refused handoff changes nothing: the engine still serves."""
        good = _one_handoff(self.cfg, self.params)
        bad = _one_handoff(self.cfg, self.params, prompt=(7, 8))
        bad.rows = jax.tree.map(lambda x: x.astype("float16"), bad.rows)
        dec = DecodeEngine(self.cfg, self.params, n_slots=2, max_len=32)
        with pytest.raises(ValueError):
            dec.submit(HandoffRequest(handoff=bad))
        assert dec.n_pending == 0
        dec.submit(HandoffRequest(handoff=good, rid=good.rid))
        (comp,) = dec.run_until_idle()
        ref = ServeEngine(self.cfg, self.params, n_slots=2, max_len=32)
        assert comp.tokens == ref.generate([[1, 2, 3]], max_new_tokens=4)[0]


class TestFailover:
    """Fault injection: a decode engine killed mid-handoff must cause a
    requeue onto another engine, never a dropped request."""

    def _pair(self, kill_first, transport=None):
        cfg = cfg_for("dense")
        params = lm.init(cfg, jax.random.key(0))
        pre = PrefillEngine(cfg, params, n_slots=2, max_len=32)
        decs = [DecodeEngine(cfg, params, n_slots=2, max_len=32)
                for _ in range(2)]
        if kill_first:
            def boom(request):
                raise RuntimeError("decode engine killed mid-handoff")
            decs[0].submit = boom
        return cfg, params, DisaggregatedEngine(pre, decs,
                                                transport=transport)

    @pytest.mark.parametrize("transport", TRANSPORTS)
    def test_killed_engine_fails_over(self, transport):
        """An engine killed mid-handoff fails over under every transport
        — including the moving ones, whose delivery has already happened
        when the submit dies (the rows re-deliver to the survivor)."""
        cfg, params, eng = self._pair(kill_first=True, transport=transport)
        rid = eng.submit(Request(prompt=[1, 2, 3], max_new_tokens=4))
        comps = eng.run_until_idle()
        assert [c.rid for c in comps] == [rid]      # requeued, not dropped
        assert eng._dead == {eng.decodes[0]}
        ref = ServeEngine(cfg, params, n_slots=2, max_len=32)
        assert comps[0].tokens == ref.generate([[1, 2, 3]],
                                               max_new_tokens=4)[0]

    def test_transport_error_mid_transfer_requeues_and_survives(self):
        """A transport route erroring mid-transfer behaves exactly like
        a killed engine: the target is marked dead, the handoff requeues
        onto a surviving route, tokens stay exact, and the failed
        delivery leaves no partial state (rows re-deliver untouched)."""
        cfg = cfg_for("dense")
        params = lm.init(cfg, jax.random.key(0))
        flaky = FlakyTransport(fail_on={0})         # first delivery dies
        eng = disaggregated_lm_engine(cfg, params, n_slots=2, max_len=32,
                                      n_decode=2, transport=flaky)
        rid = eng.submit(Request(prompt=[1, 2, 3], max_new_tokens=4))
        comps = eng.run_until_idle()
        assert [c.rid for c in comps] == [rid]      # requeued, not dropped
        assert len(eng._dead) == 1                  # the failed route's target
        assert flaky.calls == 2                     # failed + surviving
        ref = ServeEngine(cfg, params, n_slots=2, max_len=32)
        assert comps[0].tokens == ref.generate([[1, 2, 3]],
                                               max_new_tokens=4)[0]
        st = eng.stats()
        assert st.transfer["flaky/total"].count == 1   # only the success

    def test_no_decode_starvation_under_sustained_arrivals(self):
        """A new request arriving every front-end tick must not stop the
        already-resident decodes from progressing (DisaggScheduler
        answers "mixed" when both sides have work — separate engines
        advance together)."""
        cfg = cfg_for("dense")
        params = lm.init(cfg, jax.random.key(0))
        eng = disaggregated_lm_engine(cfg, params, n_slots=2, max_len=48)
        first = eng.submit(Request(prompt=[1, 2, 3], max_new_tokens=3))
        done = []
        for i in range(12):           # arrivals never pause
            eng.submit(Request(prompt=[4 + (i % 3)], max_new_tokens=3))
            eng.tick()
            done += [c.rid for c in eng.poll()]
            if first in done:
                break
        assert first in done, "resident decode starved by prefill arrivals"

    def test_typed_rejection_mid_transfer_requeues_before_raising(self):
        """A ValueError during transfer (heterogeneous pool: one decode
        engine cannot take this handoff) must surface — but the handoff
        goes back on the queue first, never dropped."""
        cfg = cfg_for("dense")
        other = cfg_for("ssm")
        params = lm.init(cfg, jax.random.key(0))
        pre = PrefillEngine(cfg, params, n_slots=2, max_len=32)
        bad = DecodeEngine(other, lm.init(other, jax.random.key(0)),
                           n_slots=2, max_len=32)
        eng = DisaggregatedEngine(pre, [bad])
        eng.submit(Request(prompt=[1, 2, 3], max_new_tokens=4))
        with pytest.raises(ValueError, match="family"):
            eng.run_until_idle()
        assert len(eng._handoffs) == 1              # requeued, not dropped

    def test_all_engines_dead_raises_with_handoff_requeued(self):
        cfg = cfg_for("dense")
        params = lm.init(cfg, jax.random.key(0))
        pre = PrefillEngine(cfg, params, n_slots=2, max_len=32)
        dec = DecodeEngine(cfg, params, n_slots=2, max_len=32)

        def boom(request):
            raise RuntimeError("killed")
        dec.submit = boom
        eng = DisaggregatedEngine(pre, [dec])
        eng.submit(Request(prompt=[1, 2, 3], max_new_tokens=4))
        with pytest.raises(RuntimeError, match="decode engines failed") as ei:
            eng.run_until_idle()
        assert len(eng._handoffs) == 1              # stranded but not lost
        # the engine's own error is chained, not only "dead"
        assert isinstance(ei.value.__cause__, RuntimeError)
        assert str(ei.value.__cause__) == "killed"


def test_disagg_sharded_decode_on_2device_cpu_mesh():
    """Acceptance regression on a 2-device host: disaggregated serving
    with the decode engine's KV caches/recurrent state sharded along the
    slot axis (ShardedScheduler) matches per-request generation for the
    four stateful families (subprocess: the test process is pinned to
    one device)."""
    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax, numpy as np
from jax.sharding import NamedSharding
from repro.models import lm
from repro.models.common import LMConfig, SSMConfig, XLSTMConfig
from repro.launch.mesh import make_mesh
from repro.serving import (Request, ServeEngine, ShardedScheduler,
                           disaggregated_lm_engine)

def tiny(family="dense", **kw):
    base = dict(arch_id="tiny-" + family, family=family, n_layers=2,
                d_model=32, n_heads=4, n_kv_heads=2, d_ff=64, vocab=64,
                remat=False, compute_dtype="float32",
                param_dtype="float32")
    base.update(kw)
    return LMConfig(**base)

CFGS = [
    ("dense", tiny()),
    ("vlm", tiny("vlm", n_layers=3, cross_attn_every=2, n_image_tokens=8)),
    ("ssm", tiny("ssm", d_model=16, n_heads=2, d_ff=0, vocab=32,
                 xlstm=XLSTMConfig(slstm_every=2, chunk_size=8))),
    ("hybrid", tiny("hybrid", d_model=16, n_heads=2, d_ff=32, vocab=32,
                    hybrid_attn_every=2,
                    ssm=SSMConfig(d_state=4, d_conv=4, expand=2,
                                  head_dim=8, n_groups=1, chunk_size=8))),
]
PROMPTS = [[1, 2, 3], [5, 6, 7, 8, 9, 10, 11], [2, 4]]
for name, cfg in CFGS:
    params = lm.init(cfg, jax.random.key(0))
    sched = ShardedScheduler(make_mesh((2,), ("data",)))
    eng = disaggregated_lm_engine(cfg, params, n_slots=2, max_len=32,
                                  n_decode=1, decode_schedulers=[sched])
    leaf = jax.tree.leaves(eng.decodes[0]._caches)[0]
    assert isinstance(leaf.sharding, NamedSharding)
    ref = ServeEngine(cfg, params, n_slots=2, max_len=32)
    reqs = [Request(prompt=p, max_new_tokens=3, rid=i)
            for i, p in enumerate(PROMPTS)]
    comps = {c.rid: c for c in eng.serve(reqs)}
    for i, p in enumerate(PROMPTS):
        want = ref.generate([p], max_new_tokens=3)[0]
        assert comps[i].tokens == want, (name, i, comps[i].tokens, want)
    print(name, "OK")
print("DISAGG_SHARDED_OK")
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=600)
    assert "DISAGG_SHARDED_OK" in r.stdout, r.stdout + r.stderr


def test_transport_failover_sharded_decode_on_2device_cpu_mesh():
    """Killed-mid-handoff coverage for each transport on a REAL 2-device
    multihost topology (subprocess): prefill and both decode engines own
    distinct single-device meshes, the first decode engine dies at
    submit, and every transport must fail the handoff over to the
    engine on the *other* device with tokens staying exact — the rows
    genuinely re-deliver across a device boundary."""
    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
from repro.models import lm
from repro.models.common import LMConfig
from repro.parallel.sharding import disjoint_submeshes
from repro.serving import (DecodeEngine, DisaggregatedEngine, PrefillEngine,
                           Request, ServeEngine, ShardedScheduler)

cfg = LMConfig(arch_id="tiny-dense", family="dense", n_layers=2, d_model=32,
               n_heads=4, n_kv_heads=2, d_ff=64, vocab=64, remat=False,
               compute_dtype="float32", param_dtype="float32")
params = lm.init(cfg, jax.random.key(0))
ref = ServeEngine(cfg, params, n_slots=2, max_len=32)
want = ref.generate([[1, 2, 3]], max_new_tokens=4)[0]
for transport in ["in_process", "host_staged", "device_to_device"]:
    meshes = disjoint_submeshes(2)        # prefill dev0, survivor dev1
    pre = PrefillEngine(cfg, params, n_slots=2, max_len=32,
                        scheduler=ShardedScheduler(meshes[0]))
    decs = [DecodeEngine(cfg, params, n_slots=2, max_len=32,
                         scheduler=ShardedScheduler(meshes[i % 2]))
            for i in range(2)]
    def boom(request):
        raise RuntimeError("decode engine killed mid-handoff")
    decs[0].submit = boom
    eng = DisaggregatedEngine(pre, decs, transport=transport)
    rid = eng.submit(Request(prompt=[1, 2, 3], max_new_tokens=4))
    comps = eng.run_until_idle()
    assert [c.rid for c in comps] == [rid], transport
    assert eng._dead == {decs[0]}, transport
    assert comps[0].tokens == want, (transport, comps[0].tokens, want)
    st = eng.stats()
    assert st.transfer[transport + "/total"].count == 1, transport
    print(transport, "OK")
print("TRANSPORT_FAILOVER_OK")
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=600)
    assert "TRANSPORT_FAILOVER_OK" in r.stdout, r.stdout + r.stderr


def test_image_dispatch_pool():
    """The stateless degenerate form: prefill=None dispatches image
    requests over a pool of CapsuleEngines with the same front-end
    surface, validation-at-submit, and transfer stats."""
    from repro.core import capsnet as cn
    from repro.deploy import FastCapsPipeline
    from repro.serving import CapsuleEngine, ImageRequest

    cfg = cn.CapsNetConfig(conv1_channels=8, caps_types=2,
                           decoder_hidden=(16, 32))
    dep = FastCapsPipeline(cfg).build(seed=0).compile(routing="optimized")
    eng = DisaggregatedEngine(
        None, [CapsuleEngine(dep, batch_size=4) for _ in range(2)])
    rng = np.random.RandomState(0)
    reqs = [ImageRequest(rng.rand(n, 28, 28, 1).astype(np.float32), rid=i)
            for i, n in enumerate([3, 2, 5])]
    comps = {c.rid: c for c in eng.serve(reqs)}
    for r in reqs:
        want = np.asarray(dep.classify(r.images))
        np.testing.assert_array_equal(comps[r.rid].classes, want)
    st = eng.stats()
    assert st.frames == 10 and st.completed == 3
    assert st.transfer["handoff"].count == 3
    assert "prefill" not in st.depth        # no prefill stage, no phantom row
    with pytest.raises(ValueError, match="images must be"):
        eng.submit(ImageRequest(np.zeros((2, 3, 3, 1), np.float32)))
    assert eng.n_pending == 0
