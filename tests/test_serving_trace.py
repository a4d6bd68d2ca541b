"""Phase spans and counters inside the serving loop: ``EngineStats``
``phase_s``, ``queue_wait_s`` and ``admitted`` on an injected clock (toy
engine, CapsuleEngine, preemption, detached snapshots, the disaggregated
sum), the same span names on the host plane of a CPU profiler trace with
CapsuleEngine's spans nested in ``serving.step``, the named CapsNet
program with its stage scopes, and CapsuleEngine's per-run assembly and
scatter with its ``caps.runs`` counter."""

import glob
import re
import types

import jax
import numpy as np
import pytest

from engine_testlib import ToyEngine, ToyRequest
from repro.core import capsnet as cn
from repro.deploy import FastCapsPipeline
from repro.serving import (CapsuleEngine, DisaggregatedEngine, EngineStats,
                           FIFOScheduler, ImageRequest,
                           InterleavingScheduler, PriorityScheduler)
from repro.traffic import VirtualClock

SERVING = ("serving.submit", "serving.schedule", "serving.admit",
           "serving.step", "serving.retire")
CAPS = ("caps.assemble", "caps.device", "caps.scatter")
SCOPES = ("caps.conv1", "caps.primary", "caps.u_hat", "caps.route",
          "caps.length")


class TimedToy(ToyEngine):
    """ToyEngine whose admit and step hooks each move a manual clock by a
    fixed amount, so every phase time is known exactly."""

    ADMIT, STEP = 0.25, 1.0

    def _admit(self, new):
        self._clock.advance(self.ADMIT)
        return super()._admit(new)

    def _step(self, active, n_batch):
        self._clock.advance(self.STEP)
        return super()._step(active, n_batch)


class StepClock:
    """Each read advances the clock by one second."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1.0
        return self.t


@pytest.fixture(scope="module")
def deployed():
    cfg = cn.CapsNetConfig(conv1_channels=16, caps_types=4,
                           decoder_hidden=(32, 64))
    return FastCapsPipeline(cfg).build(seed=0).compile(routing="optimized")


def frames(n, seed=0):
    return np.random.RandomState(seed).rand(n, 28, 28, 1).astype(np.float32)


class TestCounters:
    def test_toy_phases_and_queue_wait_on_a_manual_clock(self):
        clk = VirtualClock()
        eng = TimedToy(capacity=4, clock=clk)
        eng.submit(ToyRequest(n_tasks=2, steps=1))          # at t=0
        clk.advance(3.0)
        eng.submit(ToyRequest(n_tasks=1, steps=1))          # at t=3
        clk.advance(2.0)
        assert eng.tick()                                   # admits at t=5
        st = eng.stats()
        assert st.admitted == 3
        assert st.queue_wait_s == pytest.approx(2 * 5.0 + 2.0)
        assert st.queue_wait_ms == pytest.approx(1e3 * 12.0 / 3)
        assert st.phase_s == pytest.approx({
            "serving.submit": 0.0, "serving.schedule": 0.0,
            "serving.admit": TimedToy.ADMIT, "serving.step": TimedToy.STEP,
            "serving.retire": 0.0})
        assert st.wall_s == pytest.approx(TimedToy.ADMIT + TimedToy.STEP)
        assert not eng.tick()                               # idle
        assert eng.stats().phase_s["serving.admit"] == \
            pytest.approx(TimedToy.ADMIT)

    def test_tasks_still_queued_add_no_wait(self):
        clk = VirtualClock()
        eng = TimedToy(capacity=2, clock=clk)
        eng.submit(ToyRequest(n_tasks=3, steps=1))          # at t=0
        assert eng.stats().queue_wait_s == pytest.approx(0.0)
        clk.advance(2.0)
        eng.tick()                  # two admitted at t=2, one left queued
        st = eng.stats()
        assert st.admitted == 2
        assert st.queue_wait_s == pytest.approx(2 * 2.0)
        clk.advance(1.0)            # t=3.25 after the tick's hooks
        eng.tick()                  # the third admitted at t=4.25
        st = eng.stats()
        assert st.admitted == 3
        assert st.queue_wait_s == pytest.approx(2 * 2.0 + 4.25)

    def test_every_phase_is_timed_on_a_step_clock(self):
        eng = ToyEngine(capacity=2, clock=StepClock())
        eng.serve([ToyRequest(n_tasks=3, steps=2)])
        st = eng.stats()
        assert set(st.phase_s) == set(SERVING)
        assert all(v > 0 for v in st.phase_s.values())
        assert st.wall_s == pytest.approx(st.phase_s["serving.admit"]
                                          + st.phase_s["serving.step"])
        assert st.admitted == 3 and st.queue_wait_s > 0

    def test_capsule_engine_phases_on_a_manual_clock(self, deployed):
        clk = VirtualClock()

        class Timed:
            cfg = deployed.cfg

            def forward(self, x):
                clk.advance(2.0)
                return deployed.forward(x)

        eng = CapsuleEngine(Timed(), batch_size=4, clock=clk)
        eng.submit(ImageRequest(frames(6)))                 # at t=0
        clk.advance(1.0)
        eng.run_until_idle()        # 2 ticks: 4 frames at t=1, 2 at t=3
        st = eng.stats()
        assert st.items == 6 and st.admitted == 6
        assert st.queue_wait_s == pytest.approx(4 * 1.0 + 2 * 3.0)
        assert st.phase_s == pytest.approx({
            "serving.submit": 0.0, "serving.schedule": 0.0,
            "serving.admit": 0.0, "serving.step": 4.0,
            "serving.retire": 0.0, "caps.assemble": 0.0,
            "caps.device": 4.0, "caps.scatter": 0.0})

    def test_capsule_spans_nest_in_the_step_on_a_step_clock(self, deployed):
        eng = CapsuleEngine(deployed, batch_size=4, clock=StepClock())
        eng.serve([ImageRequest(frames(5)), ImageRequest(frames(3, 1))])
        ph = eng.stats().phase_s
        assert set(ph) == set(SERVING) | set(CAPS)
        assert all(v > 0 for v in ph.values())
        assert sum(ph[k] for k in CAPS) < ph["serving.step"]

    def test_requeued_preempted_tasks_are_admitted_again(self):
        clk = VirtualClock()
        eng = ToyEngine(capacity=1, scheduler=PriorityScheduler(),
                        clock=clk)
        eng.submit(ToyRequest(steps=3, priority=5))         # low, t=0
        clk.advance(1.0)
        eng.tick()                                          # low in, t=1
        clk.advance(1.0)
        eng.submit(ToyRequest(steps=1, priority=0))         # high, t=2
        clk.advance(2.0)
        eng.tick()                  # evicts low, admits high at t=4
        clk.advance(2.0)
        eng.tick()                  # low back at t=6, waited from t=0
        st = eng.stats()
        assert st.preempted == 1
        assert st.admitted == 3
        assert st.queue_wait_s == pytest.approx(1.0 + 2.0 + 6.0)

    def test_snapshot_is_detached(self):
        clk = VirtualClock()
        eng = TimedToy(capacity=2, clock=clk)
        eng.submit(ToyRequest(n_tasks=2, steps=2))
        eng.tick()
        snap = eng.stats()
        before = dict(snap.phase_s)
        eng.tick()
        assert snap.phase_s == before
        snap.phase_s["serving.step"] = -1.0
        assert eng.stats().phase_s["serving.step"] == \
            pytest.approx(2 * TimedToy.STEP)

    def test_disaggregated_stats_sum_the_members(self):
        clk = VirtualClock()
        members = [TimedToy(capacity=1, clock=clk) for _ in range(2)]
        pool = DisaggregatedEngine(None, members, clock=clk)
        for i in range(4):
            pool.submit(ToyRequest(n_tasks=1, steps=2))
            clk.advance(0.5 * i)
        pool.run_until_idle()
        agg = pool.stats()
        parts = [m.stats() for m in members]
        assert agg.admitted == sum(p.admitted for p in parts) == 4
        assert agg.queue_wait_s == pytest.approx(
            sum(p.queue_wait_s for p in parts))
        assert agg.queue_wait_s > 0
        assert agg.phase_s == pytest.approx(
            {k: sum(p.phase_s.get(k, 0.0) for p in parts) for k in SERVING})
        assert agg.phase_s["serving.step"] > 0


def _host_events(trace_dir):
    from jax.profiler import ProfileData

    path = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    assert path, "the profiler wrote no trace"
    pd = ProfileData.from_file(path[0])
    out = []
    for plane in pd.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                out.append((line.name, e.name, e.start_ns,
                            e.start_ns + e.duration_ns))
    return out


def test_profiler_trace_holds_every_span_nested(deployed, tmp_path):
    eng = CapsuleEngine(deployed, batch_size=4)
    eng.warmup()
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.serve([ImageRequest(frames(5)), ImageRequest(frames(3, 1))])
    finally:
        jax.profiler.stop_trace()
    events = _host_events(tmp_path)
    names = {n for _, n, _, _ in events}
    assert set(SERVING) | set(CAPS) <= names
    steps = [(ln, a, b) for ln, n, a, b in events if n == "serving.step"]
    assert len(steps) == 2                              # 8 frames, 2 ticks
    for ln, n, a, b in events:
        if n in CAPS:
            assert any(ln == sl and sa <= a and b <= sb
                       for sl, sa, sb in steps), n


def test_forward_is_a_named_program_with_stage_scopes(deployed):
    x = np.zeros((4, 28, 28, 1), np.float32)
    lowered = deployed._forward.lower(deployed.params, x)
    assert lowered.as_text().startswith("module @jit_capsnet_forward")
    hlo = lowered.compile().as_text()
    scopes = set(re.findall(r'op_name="jit\(capsnet_forward\)/([^/"]+)/',
                            hlo))
    assert scopes == set(SCOPES)


def test_no_name_is_one_the_benchmark_keys_on(deployed):
    """The benchmark finds the routing kernel by ``fused_routing`` in an
    op's text and opens its trace window on ``bench.tick`` spans: no span
    or scope of the program may be mistaken for either."""
    eng = CapsuleEngine(deployed, batch_size=4)
    eng.serve([ImageRequest(frames(2))])
    for name in set(eng.stats().phase_s) | set(SCOPES):
        assert "fused_routing" not in name and name != "bench.tick"


class RecordingForward:
    """A numpy stand-in for the deployed forward: keeps a copy of every
    batch it is given and returns lengths that are a fixed function of
    each row alone, so a per-frame reference can recompute them."""

    cfg = types.SimpleNamespace(image_hw=28, in_channels=1, n_classes=10)

    def __init__(self):
        self.batches = []

    def forward(self, x):
        self.batches.append(np.array(x))
        return row_lengths(np.asarray(x))


def row_lengths(x):
    flat = x.reshape(len(x), -1)
    return (flat[:, 1:11] + 0.5 * flat[:, 11:21]).astype(np.float32)


def tagged_frames(tag, n):
    """``n`` random frames whose first pixel reads ``1000 * tag + k``."""
    x = frames(n, seed=tag)
    x[:, 0, 0, 0] = 1000 * tag + np.arange(n)
    return x


def fifo_steps(sizes, capacity):
    """Slot layouts of FIFO ticks over whole requests (name, n frames)."""
    order = [(name, k) for name, n in sizes for k in range(n)]
    return [order[i:i + capacity] for i in range(0, len(order), capacity)]


class LastFirst(FIFOScheduler):
    """Admits the newest queued task first: every request lands in
    descending frame order, one-frame runs side by side."""

    def select(self, queue):
        return len(queue) - 1


# name: (n frames, priority, stream); the script submits them in order,
# "tick" ticks once; the engine then runs until idle.  `steps` are the
# slot layouts of the ticks that step, as (request, frame index).
LAYOUTS = {
    "whole": dict(
        capacity=8, scheduler=lambda: None,
        requests={"a": (4, 0, False), "b": (4, 0, True),
                  "c": (3, 0, False)},
        script=["a", "b", "c"],
        steps=fifo_steps([("a", 4), ("b", 4), ("c", 3)], 8),
        runs=3),
    "ragged": dict(
        capacity=8, scheduler=lambda: None,
        requests={"a": (1, 0, True), "b": (3, 0, False),
                  "c": (64, 0, True)},
        script=["a", "b", "c"],
        steps=fifo_steps([("a", 1), ("b", 3), ("c", 64)], 8),
        runs=3 + 8),
    # a prefill tick seats a0..a2 and idles; the urgent h takes the free
    # slot 3 and evicts a0 from slot 0, so h is split around a1, a2
    "preempt": dict(
        capacity=4,
        scheduler=lambda: PriorityScheduler(InterleavingScheduler()),
        requests={"a": (3, 5, True), "h": (2, 0, False)},
        script=["a", "tick", "h"],
        steps=[[("h", 0), ("a", 1), ("a", 2), ("h", 1)], [("a", 0)]],
        runs=4),
    "reversed": dict(
        capacity=4, scheduler=LastFirst,
        requests={"a": (3, 0, True), "b": (2, 0, False)},
        script=["a", "b"],
        steps=[[("b", 1), ("b", 0), ("a", 2), ("a", 1)], [("a", 0)]],
        runs=5),
}


class TestFrameRuns:
    """CapsuleEngine moves frames as runs of consecutive frames of one
    request: the batch, the lengths, the stream and the run counter read
    as a per-frame loop would give them, whatever the slot layout."""

    @pytest.mark.parametrize("layout", sorted(LAYOUTS))
    def test_runs_match_a_per_frame_reference(self, layout):
        spec = LAYOUTS[layout]
        fwd = RecordingForward()
        eng = CapsuleEngine(fwd, batch_size=spec["capacity"],
                            scheduler=spec["scheduler"]())
        images = {name: tagged_frames(tag, spec["requests"][name][0])
                  for tag, name in enumerate(spec["requests"], 1)}
        rids = {}
        for step in spec["script"]:
            if step == "tick":
                eng.tick()
                continue
            _, prio, stream = spec["requests"][step]
            req = ImageRequest(images[step].copy(), stream=stream)
            req.priority = prio
            rids[step] = eng.submit(req)
        comps = {c.rid: c for c in eng.run_until_idle()}
        events = eng.poll(stream=True)

        # batch rows: the frames in slot order, the padding zero
        assert len(fwd.batches) == len(spec["steps"])
        for batch, slots in zip(fwd.batches, spec["steps"]):
            assert batch.shape[0] == spec["capacity"]
            want = np.stack([images[name][k] for name, k in slots])
            np.testing.assert_array_equal(batch[:len(slots)], want)
            assert not batch[len(slots):].any()

        # completions: a per-frame loop over each request's frames
        for name, rid in rids.items():
            ref = np.stack([row_lengths(images[name][k:k + 1])[0]
                            for k in range(len(images[name]))])
            np.testing.assert_array_equal(comps[rid].lengths, ref)
            np.testing.assert_array_equal(comps[rid].classes,
                                          np.argmax(ref, -1))

        # stream: one event per frame of a streaming request, in slot
        # order tick by tick, each rid's seq counting up, then its done
        seq = {rid: 0 for rid in rids.values()}
        want = []
        for slots in spec["steps"]:
            for name, k in slots:
                if spec["requests"][name][2]:
                    rid = rids[name]
                    cls = int(np.argmax(row_lengths(images[name][k:k + 1])))
                    want.append((rid, seq[rid], (k, cls)))
                    seq[rid] += 1
        assert [(e.rid, e.seq, e.item) for e in events if not e.done] == want
        for name, rid in rids.items():
            mine = [e for e in events if e.rid == rid]
            if not spec["requests"][name][2]:
                assert mine == []
                continue
            assert mine[-1].done and mine[-1].seq == len(images[name])
            assert mine[-1].completion is comps[rid]
            assert all(type(c) is int for e in mine[:-1] for c in e.item)

        assert eng.stats().counts == {"caps.runs": spec["runs"]}
        assert eng.stats().items == sum(map(len, spec["steps"]))

    def test_counts_snapshot_is_detached(self):
        eng = CapsuleEngine(RecordingForward(), batch_size=4)
        eng.serve([ImageRequest(tagged_frames(1, 2)),
                   ImageRequest(tagged_frames(2, 2))])
        snap = eng.stats()
        assert snap.counts == {"caps.runs": 2}
        snap.counts["caps.runs"] = -1
        eng.serve([ImageRequest(tagged_frames(3, 3))])
        assert eng.stats().counts == {"caps.runs": 3}
        assert snap.counts == {"caps.runs": -1}

    def test_other_engines_count_nothing(self):
        eng = ToyEngine(capacity=2)
        eng.serve([ToyRequest(n_tasks=3, steps=2)])
        assert eng.stats().counts == {}
        assert EngineStats().counts == {}
