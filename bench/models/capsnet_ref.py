"""Plain reference of the FastCaps CapsNet deployment, and the weights.

Weights: made from the seed on the device in one jitted call, float32,
in the deployed (compacted) layout: conv1 ``(C1, 1, 9, 9)``, the primary
capsule conv ``(types * 8, C1, 9, 9)``, DigitCaps ``(N_in, 10, 8, 16)``.
The program is handed these; the reference makes them again itself.

Forward (Sabour et al. 2017, with the deployment's Eq. 2 softmax):

    h  = relu(conv1(x) + b1)                       9x9, stride 1, VALID
    p  = conv2(h) + b2                             9x9, stride 2, VALID
    u  = squash(p as (type, y, x) capsules of 8)   Eq. 1
    uh[b, j, :, i] = u[b, i, :] @ W[i, j]
    b_ij = 0; 3 times: c = softmax_taylor(b over j); s_j = sum_i c_ij uh_ij;
        v_j = squash(s_j); b_ij += <uh_ij, v_j> (not after the last)
    lengths = |v_j|

at the precisions the configuration states: the two convs and the
capsule transform at the default matmul precision, which on the TPU is
one bfloat16 pass (operands rounded to bfloat16, products summed in
float32) and float32 elsewhere; every other array float32, and the
routing's contractions float32 at ``Precision.HIGHEST``.

``softmax_taylor`` is FastCaps Eq. 2 (exp expanded at a = 0.5) with the
range reduction the deployment states: exp(x) = p(x / 32) ** 32, x
clipped to [-32, 32].

``precision`` selects a control, one step below what is stated:
``"bfloat16"`` holds every array in bfloat16, the routing included;
``"int8"`` rounds the operands of each conv and of the capsule transform
to int8 with symmetric per-row scales; ``"routing_bf16"`` and
``"routing_high"`` leave the rest as stated and run the routing's
contractions in one bfloat16 pass or at ``Precision.HIGH`` (three
passes).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

E_A = 1.6487212707001282                # e ** 0.5
COEFFS = (0.60653, 0.60659, 0.30260, 0.10347, 0.02118, 0.00833)
HI = jax.lax.Precision.HIGHEST


def shapes(sz: dict) -> dict:
    k1, k2 = sz["conv1_kernel"], sz["caps_kernel"]
    c1, c2 = sz["conv1_channels"], sz["caps_types"] * sz["caps_dim"]
    hw2 = ((sz["image_hw"] - k1 + 1) - k2) // sz["caps_stride"] + 1
    n_in = sz["caps_types"] * hw2 * hw2
    return {"conv1": {"w": (c1, sz["in_channels"], k1, k1), "b": (c1,)},
            "conv2": {"w": (c2, c1, k2, k2), "b": (c2,)},
            "digit": {"w": (n_in, sz["n_classes"], sz["caps_dim"],
                            sz["digit_dim"])}}


def _fan_in(path, shape):
    if path[-1] == "b":
        return None
    if path[0] == "digit":
        return shape[2]
    return shape[1] * shape[2] * shape[3]


@functools.partial(jax.jit, static_argnums=(0,))
def _make(shape_items, key):
    leaves = []
    for i, (path, shape) in enumerate(shape_items):
        k = jax.random.fold_in(key, i)
        fi = _fan_in(path, shape)
        std = 0.1 if fi is None else fi ** -0.5
        leaves.append(std * jax.random.normal(k, shape, jnp.float32))
    return leaves


def make_weights(sz: dict, seed: int) -> dict:
    """Float32 weights of the deployment, on the default device."""
    sh = shapes(sz)
    items = tuple(((a, b), tuple(s)) for a in sorted(sh)
                  for b, s in sorted(sh[a].items()))
    leaves = _make(items, jax.random.key(seed % (2 ** 31 - 1)))
    out: dict = {}
    for ((a, b), _), leaf in zip(items, leaves):
        out.setdefault(a, {})[b] = leaf
    return out


CONTROLS = ("bfloat16", "int8", "routing_bf16", "routing_high")


def stated_operands(sz: dict):
    """Operand type of the convs and the capsule transform that the
    configuration's matmul precision gives on this backend."""
    if sz["matmul_precision"] == "default" and jax.default_backend() == "tpu":
        return jnp.bfloat16
    return jnp.float32


def _rows(x, axis):
    """Round ``x`` to int8 with symmetric per-row scales over ``axis``."""
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0,
                    1e-30)
    return jnp.round(x / s).clip(-127, 127) * s


def _operands(x, w, sz, precision, x_axis, w_axis):
    """A conv's or the transform's operands and the type of its result."""
    if precision == "bfloat16":
        return x, w, None
    if precision == "int8":
        return _rows(x, x_axis), _rows(w, w_axis), jnp.float32
    dt = stated_operands(sz)
    return x.astype(dt), w.astype(dt), jnp.float32


def _conv(x, w, b, stride, sz, precision):
    x, w, out = _operands(x, w, sz, precision, (1, 2, 3), (1, 2, 3))
    y = jax.lax.conv_general_dilated(
        x, w, (stride, stride), "VALID",
        dimension_numbers=("NHWC", "OIHW", "NHWC"), precision=HI,
        preferred_element_type=out)
    return y + b


def _route(spec, a, b, precision):
    """One of the routing's contractions."""
    if precision == "routing_bf16":
        return jnp.einsum(spec, a.astype(jnp.bfloat16),
                          b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    p = jax.lax.Precision.HIGH if precision == "routing_high" else HI
    return jnp.einsum(spec, a, b, precision=p)


def _squash(s):
    sq = jnp.sum(s * s, axis=-1, keepdims=True)
    return sq / (1.0 + sq) * s / jnp.sqrt(sq + 1e-9)


def _exp_taylor(x):
    x = jnp.clip(x, -32.0, 32.0) / 32.0
    p = COEFFS[5]
    for c in reversed(COEFFS[:5]):
        p = c + x * p
    y = E_A * p
    for _ in range(5):
        y = y * y
    return y


def forward(w: dict, images, sz: dict, precision: str = "stated"):
    """images (B, 28, 28, 1) -> class capsule lengths (B, 10), float32."""
    dt = jnp.bfloat16 if precision == "bfloat16" else jnp.float32
    w = jax.tree.map(lambda a: a.astype(dt), w)
    x = images.astype(dt)
    h = jax.nn.relu(_conv(x, w["conv1"]["w"], w["conv1"]["b"], 1, sz,
                          precision))
    p = _conv(h, w["conv2"]["w"], w["conv2"]["b"], sz["caps_stride"], sz,
              precision)
    bsz, hw = p.shape[0], p.shape[1]
    u = p.reshape(bsz, hw, hw, sz["caps_types"], sz["caps_dim"])
    u = _squash(u.transpose(0, 3, 1, 2, 4).reshape(bsz, -1, sz["caps_dim"]))
    u, wd, out = _operands(u, w["digit"]["w"], sz, precision, (2,), (2,))
    uh = jnp.einsum("bid,ijde->bjei", u, wd, precision=HI,
                    preferred_element_type=out)
    logits = jnp.zeros((bsz, uh.shape[1], uh.shape[3]), dt)
    v = None
    for it in range(sz["routing_iters"]):
        z = logits - jnp.max(logits, axis=1, keepdims=True)
        e = _exp_taylor(z)
        c = e / jnp.sum(e, axis=1, keepdims=True)
        v = _squash(_route("bji,bjdi->bjd", c, uh, precision))
        if it < sz["routing_iters"] - 1:
            logits = logits + _route("bjdi,bjd->bji", uh, v, precision)
    return jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32)), axis=-1))


forward_jit = jax.jit(forward, static_argnums=(2, 3))
