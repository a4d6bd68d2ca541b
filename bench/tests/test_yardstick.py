"""The yardstick's arithmetic: tails, the trace reduction, the work
counts, the generator, the peak table, and the no-chip exit."""

import json
import math
import os
import subprocess
import sys
import types

import pytest

from conftest import BENCH, ROOT


# -- tails ------------------------------------------------------------------

def test_percentile_is_over_all_samples_nearest_rank():
    import tails

    xs = list(range(1, 101))                 # 1..100
    assert tails.percentile(xs, 95) == 95
    assert tails.percentile(xs, 100) == 100
    assert tails.percentile([5.0], 95) == 5.0
    # not a median of chunk tails: one chunk all slow, one all fast
    chunks = [[1.0] * 50, [10.0] * 50]
    assert tails.percentile(chunks[0] + chunks[1], 95) == 10.0


def test_failures_count_as_misses():
    import tails

    xs = [1.0] * 94 + [math.nan] * 6
    assert tails.percentile(xs, 95) == math.inf
    assert tails.percentile([1.0] * 96 + [math.nan] * 4, 95) == 1.0
    with pytest.raises(ValueError):
        tails.percentile([], 95)


def test_window_edges_are_half_open():
    import tails

    assert tails.in_window(1.0, 1.0, 2.0)
    assert not tails.in_window(2.0, 1.0, 2.0)
    assert not tails.in_window(0.999, 1.0, 2.0)


# -- readers ------------------------------------------------------------------

def _run(reqs, t_open=0.0, t_close=10.0):
    return types.SimpleNamespace(
        reqs=reqs, t_open=t_open, t_close=t_close,
        window_s=t_close - t_open,
        in_window=lambda t: t_open <= t < t_close,
        due_in_window=lambda: [r for r in reqs if t_open <= r.due < t_close])


def _reader(name):
    import harness

    return harness.load_module(os.path.join(BENCH, "metrics", f"{name}.py"),
                               f"test_metric_{name}")


def test_caps_fps_counts_frames_finished_in_the_window():
    import harness

    R = harness.Req
    reqs = [R(0, {"frames": 64}, due=-1.0, done=0.5),     # due before
            R(1, {"frames": 64}, due=2.0, done=9.99),
            R(2, {"frames": 64}, due=9.0, done=10.0),     # at the close
            R(3, {"frames": 64}, due=9.5)]                # never finished
    assert _reader("caps_fps").read(_run(reqs)) == pytest.approx(12.8)


def test_done_per_s_splits_the_window_by_whole_seconds():
    import harness

    R = harness.Req
    reqs = [R(0, {}, 0.0, done=0.2), R(1, {}, 0.0, done=0.9),
            R(2, {}, 0.0, done=2.5), R(3, {}, 0.0, done=3.0),
            R(4, {}, 0.0)]
    assert harness._done_per_s(reqs, 0.0, 3.0) == {"done_per_s": [2, 0, 1]}


# -- trace reduction -------------------------------------------------------

def _ev(name, start, dur):
    return types.SimpleNamespace(name=name, start_ns=start, duration_ns=dur)


def _line(name, events):
    return types.SimpleNamespace(name=name, events=events)


def _plane(name, lines):
    return types.SimpleNamespace(name=name, lines=lines)


RECORDED = [
    _plane("/host:CPU", [_line("python", [
        _ev("bench.tick", 0, 400), _ev("bench.tick", 600, 400),
        _ev("ExecuteOnDevice", 100, 50), _ev("poll", 1000, 0)])]),
    _plane("/device:TPU:0", [
        _line("XLA Ops", [_ev("fusion.1", 100, 100),
                          _ev("_routing_kernel", 150, 100),   # overlaps
                          _ev("fusion.1", 700, 200),
                          _ev("outside", 2000, 100)]),        # after window
        _line("XLA Modules", [_ev("jit_step", 100, 800)])]),
]


def test_trace_reduction_busy_union_idle_and_kernel_time():
    import trace_reduce

    r = trace_reduce.reduce_planes(RECORDED)
    assert r.window_s == pytest.approx(1000e-9)
    assert r.busy_s == pytest.approx(350e-9)     # [100,250) + [700,900)
    assert r.idle_share == pytest.approx(0.65)
    assert r.kernel("_routing_kernel") == (pytest.approx(100e-9), 1)
    assert r.ops["fusion.1"] == pytest.approx(300e-9)
    assert "outside" not in r.ops and "jit_step" not in r.ops
    labels = [g[0] for g in r.gaps]
    assert r.gaps[0][1] == pytest.approx(450e-9)  # [250, 700)
    assert labels[0] == "between ticks"           # mid 475: no span open
    assert sorted(labels[1:]) == ["bench.tick", "bench.tick"]
    bd = r.breakdown()
    assert bd["device_ops"][0] == ["fusion.1", pytest.approx(300e-9)]
    assert len(bd["idle_gaps"]) == len(r.gaps)


def test_trace_reduction_needs_the_tick_spans():
    import trace_reduce

    with pytest.raises(ValueError):
        trace_reduce.reduce_planes(RECORDED[1:])


# -- work counts -------------------------------------------------------------

def _work(name):
    import harness

    return harness.load_module(os.path.join(BENCH, "work", f"{name}.py"),
                               f"test_work_{name}")


CAPS = json.load(open(os.path.join(BENCH, "configs", "capsnet-mnist.json")))


def test_capsnet_forward_and_routing_work_by_hand():
    w = _work("capsnet_forward")
    r = _work("fused_routing")
    conv1 = 20 * 20 * 103 * (2 * 81 + 2)
    conv2 = 6 * 6 * 56 * (2 * 103 * 81 + 1)
    squash = 252 * (4 * 8 + 4)
    pred = 2 * 252 * 10 * 8 * 16
    per_iter = 5 * 10 * 252 + 2 * 10 * 16 * 252 + 10 * (4 * 16 + 4)
    route = 3 * per_iter + 2 * 2 * 10 * 16 * 252
    assert r.flops(1, 10, 16, 252, 3) == route
    assert w.flops_per_frame(CAPS) == conv1 + conv2 + squash + pred + route
    assert r.nbytes(256, 10, 16, 252) == 4 * 256 * (10 * 16 * 252 + 160
                                                    + 2520)


# -- generator ---------------------------------------------------------------

def test_seeds_share_sizes_and_gaps_in_another_order():
    from traffic import generator

    mix = {"kind": "lm", "arrivals": {"process": "poisson", "rate": 3.0},
           "prompt_len": {"dist": "lognormal", "median": 256, "sigma": 0.8,
                          "min": 32, "max": 1024},
           "output_len": {"dist": "uniform", "min": 64, "max": 1536}}
    a = generator.schedule(mix, 2 ** 31 + 11, 60.0)
    b = generator.schedule(mix, 12, 60.0)
    assert len(a.items) == len(b.items) > 50
    for key in ("prompt_len", "output_len"):
        assert sorted(i.sizes[key] for i in a.items) == \
            sorted(i.sizes[key] for i in b.items)
    assert [i.sizes for i in a.items] != [i.sizes for i in b.items]
    assert generator.schedule(mix, 12, 60.0).items == b.items
    assert all(32 <= i.sizes["prompt_len"] <= 1024 for i in a.items)


def test_mmpp_and_closed_processes():
    from traffic import generator

    mmpp = {"kind": "lm", "arrivals": {"process": "mmpp", "rates": [1, 20],
                                       "dwell_s": [2, 1]},
            "prompt_len": {"dist": "const", "value": 8},
            "output_len": {"dist": "uniform", "min": 1, "max": 4}}
    s = generator.schedule(mmpp, 3, 30.0)
    assert s.items and all(0 <= i.due < 30.0 for i in s.items)
    closed = {"kind": "image", "arrivals": {"process": "closed",
                                            "clients": 8},
              "frames": {"dist": "const", "value": 64}}
    c = generator.schedule(closed, 3, 10.0)
    items = c.closed_items(3)
    assert c.clients == 8 and next(items).sizes == {"frames": 64}


# -- peaks and the chip check -----------------------------------------------

def test_peak_table_knows_v5e_and_refuses_unknown_kinds():
    import peaks

    p = peaks.peaks("TPU v5 lite")
    assert (p["bf16_flops"], p["hbm_bytes_per_s"]) == (197e12, 819e9)
    assert peaks.roofline_s(197e12, 1.0, "TPU v5 lite") == (1.0, "compute")
    with pytest.raises(KeyError):
        peaks.peaks("cpu")


def test_run_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "capsnet-mnist.bulk", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "no TPU" in p.stderr
