"""Registry-driven kernel tests.

One parameterized ref-vs-pallas parity harness covers every registered
kernel x its canonical example cases (odd/ragged shapes, softmax modes,
dtypes) — the per-kernel ad-hoc sweeps this file used to carry are now
rows in each :class:`repro.kernels.KernelSpec`'s ``example_cases``, so a
newly registered kernel is parity-tested for free.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import kernels
from repro.kernels.registry import registry

PARITY_CASES = [
    pytest.param(name, i, id=f"{name}-case{i}")
    for name in registry.names()
    for i in range(len(registry.get(name).example_cases))
]


def _leaves(x):
    return list(x) if isinstance(x, (tuple, list)) else [x]


class TestRegistryParity:
    @pytest.mark.parametrize("name,case_idx", PARITY_CASES)
    def test_pallas_matches_reference(self, name, case_idx):
        spec = registry.get(name)
        if not spec.is_available():
            pytest.skip(f"{name}: pallas unavailable")
        case = spec.example_cases[case_idx]
        args, kwargs = spec.make_example(case)
        got = registry.call(name, *args, tune=False, **kwargs)
        want = spec.ref_call(*args, **kwargs)
        for g, w in zip(_leaves(got), _leaves(want)):
            np.testing.assert_allclose(
                np.asarray(g, np.float32), np.asarray(w, np.float32),
                atol=case.get("atol", 1e-5))

    @pytest.mark.parametrize("name", registry.names())
    def test_default_config_is_deterministic_and_legal(self, name):
        spec = registry.get(name)
        args, kwargs = spec.make_example(spec.example_cases[0])
        c1 = registry.default_config(name, *args, **kwargs)
        c2 = registry.default_config(name, *args, **kwargs)
        assert c1 == c2
        # every tuned knob was legalized into the declared space's type
        for k in spec.tuned:
            assert k in c1

    def test_kernel_inventory_pinned(self):
        assert registry.names() == ["decode_attention", "flash_attention",
                                    "flash_attention_dequant",
                                    "fused_routing", "fused_sampling",
                                    "taylor_softmax"]


class TestDefaultBlockSelection:
    def test_routing_odd_batch_gets_largest_divisor(self):
        """The old halving-from-8 collapsed odd batches to batch_block=1;
        the shared tuner default picks the largest divisor instead."""
        u = jnp.zeros((9, 5, 4, 8))
        cfg = registry.default_config("fused_routing", u)
        assert cfg["batch_block"] == 3
        u = jnp.zeros((12, 5, 4, 8))
        assert registry.default_config("fused_routing", u)["batch_block"] == 6

    def test_flash_blocks_divide_sequence(self):
        q = jnp.zeros((1, 192, 4, 32))
        k = jnp.zeros((1, 320, 2, 32))
        cfg = registry.default_config("flash_attention", q, k, k)
        assert 192 % cfg["q_block"] == 0
        assert 320 % cfg["kv_block"] == 0


class TestDispatchModes:
    def test_explicit_config_override_invariance(self):
        """Output does not depend on the block-size config (the tunable
        axes are numerics-preserving by construction)."""
        q = jax.random.normal(jax.random.key(0), (1, 256, 4, 32))
        k = jax.random.normal(jax.random.key(1), (1, 256, 2, 32))
        v = jax.random.normal(jax.random.key(2), (1, 256, 2, 32))
        base = kernels.flash_attention(q, k, v, causal=True)
        for qb, kb in [(32, 32), (64, 128), (128, 64), (256, 256)]:
            o = kernels.flash_attention(q, k, v, causal=True,
                                        q_block=qb, kv_block=kb)
            np.testing.assert_allclose(np.asarray(o), np.asarray(base),
                                       atol=1e-6)

    def test_routing_batch_block_invariance(self):
        u = jax.random.normal(jax.random.key(0), (8, 10, 16, 24)) * 0.2
        v1, c1 = kernels.fused_routing(u, batch_block=8)
        v2, c2 = kernels.fused_routing(u, batch_block=2)
        np.testing.assert_allclose(np.asarray(v1), np.asarray(v2),
                                   atol=1e-6)
        np.testing.assert_allclose(np.asarray(c1), np.asarray(c2),
                                   atol=1e-6)

    def test_taylor_softmax_close_to_exact(self):
        x = jax.random.normal(jax.random.key(1), (32, 128)) * 8
        o_k = kernels.taylor_softmax(x)
        assert float(jnp.max(jnp.abs(o_k - jax.nn.softmax(x, -1)))) < 5e-3

    def test_unknown_kernel_raises(self):
        with pytest.raises(ValueError, match="unknown kernel"):
            registry.get("nope")

    def test_unavailable_kernel_raises_instead_of_reference(self):
        """Dispatch never swaps in the jnp oracle: a kernel whose probe
        says unavailable raises, and its reference is never called."""
        import dataclasses

        from repro.kernels.registry import KernelRegistry

        def oracle(*a, **k):
            raise AssertionError("reference must not run")

        reg = KernelRegistry()
        reg.register(dataclasses.replace(
            registry.get("taylor_softmax"), reference=lambda: oracle,
            is_available=lambda: False))
        with pytest.raises(RuntimeError, match="'taylor_softmax' is "
                                               "unavailable"):
            reg.call("taylor_softmax", jnp.zeros((8, 16)), tune=False)
