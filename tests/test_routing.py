"""Dynamic routing: variant agreement, simplex property, kernel oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.core import routing
from repro import kernels
from repro.kernels.routing import ref as rref


def u_hat(seed, b=2, i=24, j=10, d=16, scale=0.2):
    """Prediction vectors in the routing layout (B, J, D, I)."""
    return jax.random.normal(jax.random.key(seed), (b, j, d, i)) * scale


class TestVariantAgreement:
    def test_optimized_matches_reference_exact(self):
        uh = u_hat(0)
        v_r, c_r = routing.route_reference(uh)
        v_o, c_o = routing.route_optimized(uh, softmax_mode="exact")
        np.testing.assert_allclose(np.asarray(v_r), np.asarray(v_o),
                                   atol=1e-6)
        np.testing.assert_allclose(np.asarray(c_r), np.asarray(c_o),
                                   atol=1e-6)

    def test_taylor_close_to_exact(self):
        """Paper: Eq. 2 softmax does not drop accuracy in routing."""
        uh = u_hat(1)
        v_r, _ = routing.route_reference(uh)
        v_t, _ = routing.route_optimized(uh, softmax_mode="taylor")
        assert float(jnp.max(jnp.abs(v_r - v_t))) < 1e-3

    def test_div_exp_log_mode(self):
        uh = u_hat(2)
        v_a, _ = routing.route_optimized(uh, use_div_exp_log=True)
        v_b, _ = routing.route_optimized(uh, use_div_exp_log=False)
        assert float(jnp.max(jnp.abs(v_a - v_b))) < 1e-4

    def test_pallas_matches_reference(self):
        uh = u_hat(3, b=4)
        v_p, c_p = routing.route_pallas(uh, softmax_mode="exact")
        v_r, c_r = rref.fused_routing_ref(uh, softmax_mode="exact")
        np.testing.assert_allclose(np.asarray(v_p), np.asarray(v_r),
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(c_p), np.asarray(c_r),
                                   atol=1e-5)


class TestRoutingProperties:
    @settings(max_examples=20, deadline=None)
    @given(st.integers(0, 2 ** 16), st.integers(1, 3))
    def test_coupling_simplex(self, seed, iters):
        """c_ij is a distribution over parents j (softmax output)."""
        uh = u_hat(seed, b=1, i=8, j=5, d=4)
        _, c = routing.route_reference(uh, n_iters=iters)
        np.testing.assert_allclose(np.asarray(jnp.sum(c, 1)), 1.0,
                                   atol=1e-5)
        assert float(jnp.min(c)) >= 0.0

    def test_agreement_sharpens_couplings(self):
        """More routing iterations concentrate c on agreeing parents:
        max_j c_ij is non-decreasing in iterations (on average)."""
        uh = u_hat(7, b=4, i=32, j=10, d=16, scale=1.0)
        _, c1 = routing.route_reference(uh, n_iters=1)
        _, c3 = routing.route_reference(uh, n_iters=3)
        m1 = float(jnp.mean(jnp.max(c1, axis=1)))
        m3 = float(jnp.mean(jnp.max(c3, axis=1)))
        assert m3 >= m1

    def test_uniform_couplings_at_first_iteration(self):
        uh = u_hat(8, j=10)
        _, c = routing.route_reference(uh, n_iters=1)
        np.testing.assert_allclose(np.asarray(c), 0.1, atol=1e-6)

    def test_output_norm_below_one(self):
        uh = u_hat(9, scale=5.0)
        v, _ = routing.route_reference(uh)
        assert float(jnp.max(jnp.linalg.norm(v, axis=-1))) < 1.0


class TestKernelSweep:
    @pytest.mark.parametrize("b,i,j,d", [
        (1, 8, 2, 4), (2, 36, 10, 16), (8, 252, 10, 16), (3, 17, 5, 8)])
    @pytest.mark.parametrize("mode", ["exact", "taylor"])
    def test_kernel_vs_oracle(self, b, i, j, d, mode):
        uh = u_hat(b * 1000 + i, b=b, i=i, j=j, d=d)
        v_k, c_k = kernels.fused_routing(uh, softmax_mode=mode)
        v_r, c_r = rref.fused_routing_ref(uh, softmax_mode=mode)
        np.testing.assert_allclose(np.asarray(v_k), np.asarray(v_r),
                                   atol=2e-5)
        np.testing.assert_allclose(np.asarray(c_k), np.asarray(c_r),
                                   atol=2e-5)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_kernel_dtypes(self, dtype):
        uh = u_hat(11, b=4).astype(dtype)
        v_k, _ = kernels.fused_routing(uh)
        v_r, _ = rref.fused_routing_ref(uh)
        tol = 1e-5 if dtype == jnp.float32 else 5e-2
        np.testing.assert_allclose(
            np.asarray(v_k, np.float32), np.asarray(v_r, np.float32),
            atol=tol)

    def test_flops_model(self):
        f = routing.routing_flops(1, 1152, 10, 16, 3)
        assert f > 0
        # FC+agreement dominate: 4*B*I*J*D per iter x 3
        assert f > 3 * 4 * 1152 * 10 * 16
