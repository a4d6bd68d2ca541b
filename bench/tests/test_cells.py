"""The harness end to end on the CPU, at a tiny size, with the chip check
skipped: a cell added from new files alone runs, and breaking the timed
path underneath turns ``correct`` false."""

import io
import json
import os
import shutil

import pytest

from conftest import BENCH

TINY_CAPSNET = {
    "name": "capsnet-tiny", "model": "capsnet",
    "image_hw": 12, "in_channels": 1, "n_classes": 4, "conv1_channels": 6,
    "conv1_kernel": 5, "caps_types": 2, "caps_dim": 4, "caps_kernel": 3,
    "caps_stride": 2, "digit_dim": 8, "routing_iters": 3,
    "n_primary_caps": 18, "routing": "pallas", "softmax": "taylor",
    "param_dtype": "float32", "matmul_precision": "default",
    "serve": {"batch_size": 8, "scheduler": "fifo", "kernel_tune": False},
    "limits": {"s_norm_gap": 1e-3, "s_norm_rms": 1e-4},
}
TINY_MIX = {"kind": "image", "arrivals": {"process": "poisson", "rate": 40.0,
                                          "ramp_s": 0.2},
            "frames": {"dist": "uniform", "min": 1, "max": 4}}

# A new end-to-end metric the new cell brings as a file of its own.
P95_READER = '''
import tails


def read(run):
    return tails.percentile([1e3 * (r.done - r.due)
                             for r in run.due_in_window()], 95)
'''


def _new_cell_root(tmp_path, config, mix_name, mix, cell):
    """A checkout holding the benchmark's files plus one new cell, added
    as new files and new entries only."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    with open(os.path.join(BENCH, "..", "BENCHMARK.json")) as f:
        bench = json.load(f)
    cfg_file = f"bench/configs/{config['name']}.json"
    (root / cfg_file).write_text(json.dumps(config))
    (root / "bench" / "traffic" / f"{mix_name}.json").write_text(
        json.dumps(mix))
    bench["configs"].append({"name": config["name"], "source": "test",
                             "file": cfg_file, "reduced": [], "why": "test"})
    bench["workloads"].append({"name": cell, "config": config["name"],
                               "traffic": mix_name, "chips": 1,
                               "why": "test"})
    (root / "bench" / "metrics" / "tiny_p95_ms.py").write_text(P95_READER)
    bench["end_to_end"].append(
        {"name": "tiny_p95_ms", "unit": "ms", "better": "lower",
         "bound": 0.25, "source": "host_clock", "workloads": [cell]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(root)


def _run(root, cell, patch=None, seed=2 ** 31 + 7):
    import harness

    out, err = io.StringIO(), io.StringIO()
    res = harness.run_cell(root, cell, seed, 1.0, False, chip_check=False,
                           patch=patch, out=out, err=err)
    lines = out.getvalue().strip().splitlines()
    assert json.loads(lines[-1]) == json.loads(json.dumps(res))
    return res, err.getvalue()


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return _new_cell_root(tmp_path_factory.mktemp("cells"), TINY_CAPSNET,
                          "tiny_stream", TINY_MIX, "capsnet-tiny.stream")


def test_new_cell_from_new_files_runs_correct(tiny_root):
    res, err = _run(tiny_root, "capsnet-tiny.stream")
    assert res["correct"] is True, (res, err)
    assert res["attempted"] > 10 and res["failed"] == 0
    assert set(res["metrics"]) == {"tiny_p95_ms", "setup_s"}
    assert res["metrics"]["tiny_p95_ms"]["value"] > 0
    assert list(res)[-1] == "compared"
    assert "compared s_norm_gap" in err


@pytest.mark.parametrize("fault", ["alter_answer", "swap_frames"])
def test_broken_answer_is_not_correct(tiny_root, fault):
    """A capsule length altered where the forward produces it, or two
    frames of a batch given each other's answers."""
    import harness

    adapter = harness.load_cell(tiny_root, "capsnet-tiny.stream").adapter()
    res, err = _run(tiny_root, "capsnet-tiny.stream",
                    patch=adapter.FAULTS[fault])
    assert res["correct"] is False, res
    assert res["compared"]["s_norm_gap"]["value"] > 1e-3
