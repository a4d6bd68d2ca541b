"""The reader of CapsuleEngine's run counter: frames moved per run, the
window difference of ``items`` over that of ``counts["caps.runs"]``, and
nothing read from a program that keeps no such counter."""

import os
import types

import pytest

from conftest import BENCH


def _reader():
    import harness

    return harness.load_module(
        os.path.join(BENCH, "metrics", "frames_per_run.caps.py"),
        "test_metric_frames_per_run.caps")


def _stats_run(a, b):
    return types.SimpleNamespace(stats_open=a, stats_close=b)


def test_frames_per_run_takes_the_window_difference():
    from repro.serving import EngineStats

    a = EngineStats(items=100, counts={"caps.runs": 3})
    b = EngineStats(items=612, counts={"caps.runs": 11})
    assert _reader().read(_stats_run(a, b)) == pytest.approx(512 / 8)
    # the counter first appears inside the window
    a = EngineStats(items=100)
    assert _reader().read(_stats_run(a, b)) == pytest.approx(512 / 11)


def test_frames_per_run_finds_nothing_without_the_counter():
    """The parent program's EngineStats has no ``counts``; an engine that
    keeps counts but moved no runs has no ``"caps.runs"``: the reader
    leaves the metric out and does not raise."""
    from repro.serving import EngineStats

    reader = _reader()
    a = types.SimpleNamespace(items=100, ticks=2, wall_s=1.0)
    b = types.SimpleNamespace(items=612, ticks=4, wall_s=2.0)
    assert reader.read(_stats_run(a, b)) is None
    a = EngineStats(items=100, counts={"pages.alloc": 1})
    b = EngineStats(items=612, counts={"pages.alloc": 2})
    assert reader.read(_stats_run(a, b)) is None
    idle = EngineStats(items=100, counts={"caps.runs": 3})
    assert reader.read(_stats_run(idle, idle)) is None
