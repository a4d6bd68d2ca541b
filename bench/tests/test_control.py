"""The controls at the committed cell's own sizes and limit: the plain
reference computed one step below the stated precision, put in the
program's place, reads above the limit in ``capsnet-mnist.json``.

Here the reference runs on the CPU, where the stated default precision is
float32, so a control also carries the bfloat16 rounding of the convs
that the TPU's one pass already has: these readings bound the chip's
from above.  The chip's own readings, which set the limit, are taken with
``bench/control.py``.
"""

import json
import os

import numpy as np
import pytest

from conftest import BENCH

FRAMES = 64


@pytest.fixture(scope="module")
def capsnet():
    import harness
    from traffic import generator

    sz = json.load(open(os.path.join(BENCH, "configs",
                                     "capsnet-mnist.json")))
    adapter = harness.load_module(os.path.join(BENCH, "models",
                                               "capsnet.py"), "t_capsnet")
    ref = adapter._ref()
    seed = 2 ** 31 + 5
    frames = generator.rng(seed, "payload").random(
        (FRAMES, sz["image_hw"], sz["image_hw"], sz["in_channels"]),
        np.float32)
    weights = ref.make_weights(sz, seed)
    dep = adapter.Deployment.__new__(adapter.Deployment)
    dep.sz, dep.ref = sz, ref
    want = dep.reference(weights, frames)
    return adapter, dep, weights, frames, want


@pytest.mark.parametrize("precision", ["bfloat16", "int8", "routing_bf16"])
def test_control_fails_the_cells_limit(capsnet, precision):
    adapter, dep, weights, frames, want = capsnet
    got = adapter.s_norm_gaps(dep.reference(weights, frames, precision),
                              want)
    assert any(v > dep.sz["limits"][k] for k, v in got.items()), got


def test_stated_reference_reads_itself_as_zero(capsnet):
    adapter, dep, weights, frames, want = capsnet
    assert adapter.s_norm_gaps(want, want) == {"s_norm_gap": 0.0,
                                               "s_norm_rms": 0.0}
    assert all(v > 0 for v in dep.sz["limits"].values())


def test_control_script_judges_controls_and_faults(tmp_path):
    """``control.py``'s readings at the tiny cell: the program's runs are
    correct, the bfloat16 control and a swapped-frames run are not."""
    import io

    import control
    from test_cells import TINY_CAPSNET, TINY_MIX, _new_cell_root

    root = _new_cell_root(tmp_path, TINY_CAPSNET, "tiny_stream", TINY_MIX,
                          "capsnet-tiny.stream")
    rows, broken, summary = control.readings(
        root, "capsnet-tiny.stream", [2 ** 31 + 3], 1.0, ["bfloat16"],
        ["swap_frames"], chip_check=False, log=io.StringIO())
    assert rows[0]["correct"] is True
    assert rows[0]["control_correct"] == {"bfloat16": False}
    assert broken[0]["correct"] is False
    limits = TINY_CAPSNET["limits"]
    assert all(summary["lower"][k] <= v for k, v in limits.items())
    assert any(summary["upper"]["bfloat16"][k] > v
               for k, v in limits.items())
