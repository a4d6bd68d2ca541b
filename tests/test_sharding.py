"""Sharding policy unit tests + a small-mesh dry-run in a subprocess."""

import json
import os
import subprocess
import sys

import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.parallel import sharding as sh


MESH_2D = {"data": 16, "model": 16}
MESH_3D = {"pod": 2, "data": 16, "model": 16}


class TestShapeAwareSpec:
    def test_basic_mapping(self):
        spec = sh.shape_aware_spec(("batch", "seq", None),
                                   (256, 4096, 1024), sh.DEFAULT_RULES,
                                   MESH_2D)
        assert spec == P("data")

    def test_multi_axis_batch(self):
        spec = sh.shape_aware_spec(("batch", None), (256, 8),
                                   sh.DEFAULT_RULES, MESH_3D)
        assert spec == P(("pod", "data"))

    def test_indivisible_dim_replicates(self):
        # batch=1 (long_500k): data freed, claimed by kv_seq
        spec = sh.shape_aware_spec(
            ("layers", "batch", "kv_seq", "kv_heads", "kv_head_dim"),
            (6, 1, 524288, 32, 64), sh.DEFAULT_RULES, MESH_2D)
        assert spec == P(None, None, "data", "model")

    def test_gqa_kv_heads_fallback_to_head_dim(self):
        # kv_heads=8 < model=16 -> kv_head_dim claims model
        spec = sh.shape_aware_spec(
            ("layers", "batch", "kv_seq", "kv_heads", "kv_head_dim"),
            (88, 128, 32768, 8, 128), sh.DEFAULT_RULES, MESH_2D)
        assert spec == P(None, "data", None, None, "model")

    def test_axis_claimed_once(self):
        spec = sh.shape_aware_spec(("mlp", "heads"), (64, 64),
                                   sh.DEFAULT_RULES, MESH_2D)
        # both map to model; first dim wins
        assert spec == P("model")

    def test_partial_axis_tuple(self):
        # batch=48: divisible by pod(2) but not pod*data(32) -> longest
        # valid prefix ("pod",) survives (partial sharding beats none)
        spec = sh.shape_aware_spec(("batch",), (48,), sh.DEFAULT_RULES,
                                   MESH_3D)
        assert spec == P("pod")

    def test_hubert_vocab_replicates(self):
        spec = sh.shape_aware_spec(("vocab", "embed"), (504, 1280),
                                   sh.DEFAULT_RULES, MESH_2D)
        assert spec == P(None, "data")

    def test_xlstm_no_tp_policy(self):
        """§Perf H-A1: small-d_model archs run pure DP + FSDP — no model-
        axis sharding on weights; batch claims (data, model)."""
        rules = sh.rules_for_arch("xlstm-1.3b")
        spec = sh.shape_aware_spec(
            ("mlstm_inner", "heads", "head_dim_v"), (4096, 4, 1024),
            rules, MESH_2D)
        assert spec == P()
        # train batch claims both axes (256 = 16 x 16)
        spec = sh.shape_aware_spec(("batch", "seq", None),
                                   (256, 4096, 2048), rules, MESH_2D)
        assert spec == P(("data", "model"))
        # weights stay FSDP over data
        spec = sh.shape_aware_spec(("embed", "mlstm_up"), (2048, 8192),
                                   rules, MESH_2D)
        assert spec == P("data")

    def test_deepseek_keeps_ep(self):
        rules = sh.rules_for_arch("deepseek-moe-16b")
        spec = sh.shape_aware_spec(("expert", "embed", "expert_mlp"),
                                   (64, 2048, 1408), rules, MESH_2D)
        assert spec == P("model", "data")


class TestShardingsFor:
    def test_tree_with_nones(self):
        mesh = jax.make_mesh((1, 1), ("data", "model"))
        structs = {"w": jax.ShapeDtypeStruct((16, 8), "float32"),
                   "b": jax.ShapeDtypeStruct((8,), "float32")}
        axes = {"w": ("embed", "mlp"), "b": None}
        out = sh.shardings_for(structs, axes, sh.DEFAULT_RULES, mesh)
        # mesh axes of size 1 still map (harmless no-op placement)
        assert out["w"].spec == P("data", "model")
        assert out["b"].spec == P()


@pytest.mark.slow
def test_small_mesh_dryrun_subprocess(tmp_path):
    """End-to-end dry-run on an 8-virtual-device mesh in a subprocess
    (the 512-device production dry-run is exercised by launch/dryrun.py)."""
    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from repro import configs as cfg_lib
from repro.launch.mesh import make_mesh
from repro.models import lm
from repro.optim import adamw
from repro.parallel import sharding as shard_lib

cfg = cfg_lib.reduced(cfg_lib.get_config("qwen3-1.7b"))
mesh = make_mesh((2, 4), ("data", "model"))
rules = shard_lib.rules_for_arch(cfg.arch_id)
params = lm.param_structs(cfg)
opt = jax.eval_shape(adamw.init_state, params)
batch = {"tokens": jax.ShapeDtypeStruct((8, 32), jnp.int32),
         "labels": jax.ShapeDtypeStruct((8, 32), jnp.int32)}
p_sh = shard_lib.shardings_for(params, lm.specs(cfg), rules, mesh)
o_sh = {"m": p_sh, "v": p_sh,
        "step": shard_lib.shardings_for(opt["step"], None, rules, mesh)}
b_sh = shard_lib.shardings_for(
    batch, {"tokens": ("batch", "seq"), "labels": ("batch", "seq")},
    rules, mesh)
ocfg = adamw.AdamWConfig()

def step(p, o, b):
    (l, m), g = jax.value_and_grad(
        lambda p_: lm.loss_fn(p_, cfg, b), has_aux=True)(p)
    return adamw.apply_updates(p, g, o, ocfg)[:2]

with jax.set_mesh(mesh):
    compiled = jax.jit(step, in_shardings=(p_sh, o_sh, b_sh),
                       out_shardings=(p_sh, o_sh)).lower(
        params, opt, batch).compile()
ca = compiled.cost_analysis()
assert ca["flops"] > 0
print("SUBPROCESS_DRYRUN_OK", ca["flops"])
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=600)
    assert "SUBPROCESS_DRYRUN_OK" in r.stdout, r.stdout + r.stderr
