"""The readers of the serving tick's phase times and queue-wait counters:
window differences of two ``EngineStats`` snapshots, and nothing read
from a program that keeps no such counters."""

import os
import types

import pytest

from conftest import BENCH

NAMES = ["serving_us_per_frame.caps", "step_us_per_frame.caps",
         "queue_wait_ms.caps"]


def _reader(name):
    import harness

    return harness.load_module(os.path.join(BENCH, "metrics", f"{name}.py"),
                               f"test_phase_metric_{name}")


def _stats_run(a, b):
    return types.SimpleNamespace(stats_open=a, stats_close=b)


def _phase_snapshots():
    from repro.serving import EngineStats

    a = EngineStats(items=100, admitted=120, queue_wait_s=0.5, phase_s={
        "serving.submit": 0.1, "serving.schedule": 0.2,
        "serving.admit": 0.0, "serving.step": 1.0, "serving.retire": 0.1,
        "caps.assemble": 0.2, "caps.device": 0.5, "caps.scatter": 0.2})
    b = EngineStats(items=612, admitted=632, queue_wait_s=3.06, phase_s={
        "serving.submit": 0.2, "serving.schedule": 0.4,
        "serving.admit": 0.001, "serving.step": 2.0, "serving.retire": 0.3,
        "caps.assemble": 0.4, "caps.device": 1.1, "caps.scatter": 0.4})
    return a, b


@pytest.mark.parametrize("name,value", [
    # (0.1 + 0.2 + 0.001 + 1.0 + 0.2 - 0.6) s over 512 frames
    ("serving_us_per_frame.caps", 1e6 * 0.901 / 512),
    ("step_us_per_frame.caps", 1e6 * 0.6 / 512),
    ("queue_wait_ms.caps", 1e3 * 2.56 / 512),
])
def test_phase_readers_take_window_differences(name, value):
    assert _reader(name).read(_stats_run(*_phase_snapshots())) == \
        pytest.approx(value)


@pytest.mark.parametrize("name", NAMES)
def test_phase_readers_find_nothing_in_a_program_without_them(name):
    """The parent program's EngineStats has no phase times or queue-wait
    counters: the readers leave their metric out and do not raise."""
    a = types.SimpleNamespace(items=100, ticks=2, wall_s=1.0)
    b = types.SimpleNamespace(items=612, ticks=4, wall_s=2.0)
    assert _reader(name).read(_stats_run(a, b)) is None
    idle = _phase_snapshots()[0]
    assert _reader(name).read(_stats_run(idle, idle)) is None
