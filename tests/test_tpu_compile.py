"""The main path's Pallas kernels compile for a TPU v5e chip, at real widths.

Interpret mode (every other kernel test) cannot see the chip's tiling
rules, its scoped-VMEM limit or the ops Mosaic cannot lower.  These tests
hand the kernels to the TPU compiler for a described — not attached —
``v5e:2x2`` topology (one of its chips) and assert that each compiled
program holds the kernel as a ``tpu_custom_call``.  Nothing runs.

Shapes: the capsnet-mnist routing block unpruned (1152 capsules, exact
softmax) and pruned (252, Taylor softmax); qwen3-1.7b decode (16 query
heads over 8 KV heads of 128, bf16 cache, dense / int8 / paged) and its
151936-token vocabulary for sampling; flash attention at d_head = 128.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.registry import registry

H, K, D, VOCAB = 16, 8, 128, 151936        # qwen3-1.7b heads / vocab
SLOTS, MAX_LEN, PAGE = 4, 1024, 16


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip can be written to the persistent
    # cache but never read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # capslint: disable=exception-hygiene
        jax.config.update("jax_enable_compilation_cache", prev)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile_text(one_chip, name, arrays, static=None, **config):
    """AOT-compile the registry entry ``name`` with ``interpret=False``;
    ``arrays`` maps argument names to (shape, dtype)."""
    fn = registry.get(name).build()
    static = dict(static or {})

    def call(**kw):
        return fn(**kw, **static, **config, interpret=False)

    structs = {k: jax.ShapeDtypeStruct(s, jnp.dtype(dt), sharding=one_chip)
               for k, (s, dt) in arrays.items()}
    return jax.jit(call).lower(**structs).compile().as_text()


@pytest.mark.parametrize("shape,softmax", [
    ((8, 10, 16, 1152), "exact"),        # unpruned MNIST CapsNet
    ((8, 10, 16, 252), "taylor"),        # FastCaps-pruned deployment
], ids=["unpruned-1152-exact", "pruned-252-taylor"])
def test_fused_routing_compiles(one_chip, shape, softmax):
    text = _compile_text(
        one_chip, "fused_routing", {"u_hat": (shape, "float32")},
        static={"n_iters": 3, "softmax_mode": softmax},
        **registry.default_config("fused_routing",
                                  jax.ShapeDtypeStruct(shape, jnp.float32)))
    assert "tpu_custom_call" in text


def _decode_arrays(variant):
    arrays = {"q": ((SLOTS, 1, H, D), "bfloat16"),
              "kv_valid_len": ((SLOTS,), "int32")}
    if variant == "paged":
        n_pages = SLOTS * MAX_LEN // PAGE
        arrays["k"] = arrays["v"] = ((n_pages, PAGE, K, D), "bfloat16")
        arrays["tables"] = ((SLOTS, MAX_LEN // PAGE), "int32")
    elif variant == "int8":
        arrays["k"] = arrays["v"] = ((SLOTS, MAX_LEN, K, D), "int8")
        arrays["ks"] = arrays["vs"] = ((SLOTS, MAX_LEN), "float32")
    else:
        arrays["k"] = arrays["v"] = ((SLOTS, MAX_LEN, K, D), "bfloat16")
    return arrays


@pytest.mark.parametrize("variant", ["dense", "int8", "paged"])
def test_decode_attention_compiles(one_chip, variant):
    text = _compile_text(one_chip, "decode_attention",
                         _decode_arrays(variant), kv_block=512,
                         slot_block=1, page_size=PAGE)
    assert "tpu_custom_call" in text


def test_fused_sampling_compiles(one_chip):
    rows = {n: ((SLOTS,), dt) for n, dt in [
        ("temperature", "float32"), ("seeds", "int32"), ("pos", "int32"),
        ("top_k", "int32"), ("top_p", "float32")]}
    text = _compile_text(one_chip, "fused_sampling",
                         {"logits": ((SLOTS, VOCAB), "float32"), **rows},
                         row_block=8)
    assert "tpu_custom_call" in text


def test_flash_attention_compiles(one_chip):
    q = ((1, 512, H, D), "bfloat16")
    kv = ((1, 512, K, D), "bfloat16")
    text = _compile_text(one_chip, "flash_attention",
                         {"q": q, "k": kv, "v": kv},
                         static={"causal": True}, q_block=512, kv_block=512)
    assert "tpu_custom_call" in text
