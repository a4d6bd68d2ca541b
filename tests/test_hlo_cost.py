"""Trip-count-weighted HLO cost analysis: validated against analytics."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch import hlo_analysis, hlo_cost


def compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


class TestWeightedCost:
    def test_plain_matmul_flops(self):
        a = jax.ShapeDtypeStruct((64, 128), jnp.float32)
        b = jax.ShapeDtypeStruct((128, 32), jnp.float32)
        txt = compiled_text(lambda a, b: a @ b, a, b)
        c = hlo_cost.weighted_cost(txt)
        expect = 2 * 64 * 32 * 128
        assert abs(c.flops - expect) / expect < 0.05

    def test_scan_multiplies_by_trip_count(self):
        """A matmul inside a 10-step scan must cost ~10x the single one."""
        a = jax.ShapeDtypeStruct((64, 64), jnp.float32)

        def once(a):
            return a @ a

        def scanned(a):
            def body(c, _):
                return c @ a, None
            out, _ = jax.lax.scan(body, a, None, length=10)
            return out

        c1 = hlo_cost.weighted_cost(compiled_text(once, a))
        c10 = hlo_cost.weighted_cost(compiled_text(scanned, a))
        ratio = c10.flops / max(c1.flops, 1)
        assert 8.0 < ratio < 12.0, ratio

    def test_nested_scan_multiplies(self):
        a = jax.ShapeDtypeStruct((32, 32), jnp.float32)

        def nested(a):
            def outer(c, _):
                def inner(ci, _):
                    return ci @ a, None
                c2, _ = jax.lax.scan(inner, c, None, length=4)
                return c2, None
            out, _ = jax.lax.scan(outer, a, None, length=3)
            return out

        def once(a):
            return a @ a

        c1 = hlo_cost.weighted_cost(compiled_text(once, a))
        cn = hlo_cost.weighted_cost(compiled_text(nested, a))
        ratio = cn.flops / max(c1.flops, 1)
        assert 9.0 < ratio < 15.0, ratio        # 12 matmuls total

    def test_transcendentals_counted(self):
        x = jax.ShapeDtypeStruct((1000,), jnp.float32)
        txt = compiled_text(lambda x: jnp.exp(x), x)
        c = hlo_cost.weighted_cost(txt)
        assert c.transcendentals >= 1000

    def test_conv_flops(self):
        img = jax.ShapeDtypeStruct((1, 28, 28, 1), jnp.float32)
        ker = jax.ShapeDtypeStruct((16, 1, 9, 9), jnp.float32)

        def conv(x, w):
            return jax.lax.conv_general_dilated(
                x, w, (1, 1), "VALID",
                dimension_numbers=("NHWC", "OIHW", "NHWC"))

        c = hlo_cost.weighted_cost(compiled_text(conv, img, ker))
        expect = 2 * (20 * 20 * 16) * (9 * 9 * 1)
        assert abs(c.flops - expect) / expect < 0.1, c.flops


class TestCollectiveParse:
    def test_collective_stats_from_sharded_module(self):
        """A psum over a 1-device mesh still emits an all-reduce op."""
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((1,), ("x",))

        def f(a):
            return jax.lax.with_sharding_constraint(
                a, jax.sharding.NamedSharding(
                    mesh, jax.sharding.PartitionSpec())).sum()

        # craft a module with an explicit all-reduce via shard_map psum
        from jax.sharding import PartitionSpec as P

        def g(a):
            return jax.shard_map(
                lambda x: jax.lax.psum(x, "x"), mesh=mesh,
                in_specs=P("x"), out_specs=P())(a)

        txt = jax.jit(g).lower(
            jax.ShapeDtypeStruct((8, 4), jnp.float32)).compile().as_text()
        stats = hlo_analysis.collective_stats(txt)
        assert stats.count_by_kind.get("all-reduce", 0) >= 1
        assert stats.bytes_by_kind["all-reduce"] == 8 * 4 * 4


class TestOpCensus:
    def test_census_counts(self):
        a = jax.ShapeDtypeStruct((64, 64), jnp.float32)
        txt = compiled_text(lambda a: jnp.tanh(a @ a) + a, a)
        census = dict(hlo_analysis.op_census(txt, top=50))
        assert sum(census.values()) > 0
