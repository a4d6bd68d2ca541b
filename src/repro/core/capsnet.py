"""Full-fledged CapsNet (Sabour et al. [4], paper Fig. 3) in pure JAX.

Architecture (MNIST shapes):
    Conv1        9x9 conv, 1 -> 256 ch, stride 1, ReLU       -> (B, 20, 20, 256)
    PrimaryCaps  9x9 conv, 256 -> n_caps_types*caps_dim ch,
                 stride 2, reshape to capsules, squash       -> (B, 1152, 8)
    DigitCaps    per-(i, j) linear maps u_hat = W_ij u_i,
                 dynamic routing (core/routing.py)           -> (B, 10, 16)
    Decoder      FC 160 -> 512 -> 1024 -> 784, sigmoid (reconstruction reg.)

Loss: margin loss (Sabour Eq. 4) + 0.0005 * MSE reconstruction.

Pruning integration (paper Fig. 6): conv weights are stored OIHW so
``core/lakp`` can score/mask kernels directly.  ``compact()`` physically
removes capsule *types* whose conv2 channels were fully pruned — 1152 -> 252
capsules on MNIST in the paper — shrinking the routing weight W from
(1152, 10, 8, 16) to (252, 10, 8, 16): the 1280x routing-parameter reduction.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import approx_math
from repro.deploy.registry import RoutingSpec, resolve as resolve_routing
from repro.models.common import ParamDef, fanin_init, init_params, param_specs


@dataclasses.dataclass(frozen=True)
class CapsNetConfig:
    arch_id: str = "capsnet-mnist"
    image_hw: int = 28
    in_channels: int = 1
    n_classes: int = 10
    conv1_channels: int = 256
    conv1_kernel: int = 9
    caps_types: int = 32          # PrimaryCaps capsule types
    caps_dim: int = 8             # PrimaryCaps capsule dimension
    caps_kernel: int = 9
    caps_stride: int = 2
    digit_dim: int = 16           # DigitCaps dimension
    routing_iters: int = 3
    # Typed routing spec (repro.deploy); None means the reference variant.
    routing: Optional[RoutingSpec] = None
    decoder_hidden: Tuple[int, int] = (512, 1024)
    recon_weight: float = 0.0005
    param_dtype: str = "float32"
    # margin loss constants (Sabour Eq. 4)
    m_plus: float = 0.9
    m_minus: float = 0.1
    lambda_down: float = 0.5

    @property
    def conv1_out_hw(self) -> int:
        return self.image_hw - self.conv1_kernel + 1

    @property
    def caps_out_hw(self) -> int:
        return (self.conv1_out_hw - self.caps_kernel) // self.caps_stride + 1

    @property
    def n_primary_caps(self) -> int:
        return self.caps_types * self.caps_out_hw ** 2

    @property
    def primary_conv_channels(self) -> int:
        return self.caps_types * self.caps_dim

    def pdtype(self):
        return jnp.dtype(self.param_dtype)

    def routing_spec(self) -> RoutingSpec:
        """The effective RoutingSpec (reference routing when unset)."""
        if self.routing is not None:
            return self.routing
        return RoutingSpec.reference()


# ---------------------------------------------------------------------------
# Parameter declaration
# ---------------------------------------------------------------------------


def capsnet_defs(cfg: CapsNetConfig) -> Dict[str, Any]:
    k1, k2 = cfg.conv1_kernel, cfg.caps_kernel
    c1 = cfg.conv1_channels
    c2 = cfg.primary_conv_channels
    n_in, n_out = cfg.n_primary_caps, cfg.n_classes
    d_in, d_out = cfg.caps_dim, cfg.digit_dim
    img = cfg.image_hw ** 2 * cfg.in_channels
    h1, h2 = cfg.decoder_hidden
    return {
        # OIHW conv weights (LAKP scores kernels on this layout directly)
        "conv1": {
            "w": ParamDef((c1, cfg.in_channels, k1, k1),
                          ("conv_out", "conv_in", None, None),
                          fanin_init(cfg.in_channels * k1 * k1)),
            "b": ParamDef((c1,), ("conv_out",),
                          lambda k, s, d: jnp.zeros(s, d)),
        },
        "conv2": {
            "w": ParamDef((c2, c1, k2, k2), ("conv_out", "conv_in", None, None),
                          fanin_init(c1 * k2 * k2)),
            "b": ParamDef((c2,), ("conv_out",),
                          lambda k, s, d: jnp.zeros(s, d)),
        },
        # DigitCaps transform: u_hat[b,j,:,i] = u[b,i,:] @ W[i,j]
        "digit": {
            "w": ParamDef((n_in, n_out, d_in, d_out),
                          ("caps_in", "caps_out", None, None),
                          fanin_init(d_in)),
        },
        "decoder": {
            "w1": ParamDef((n_out * d_out, h1), (None, "mlp"), fanin_init()),
            "b1": ParamDef((h1,), ("mlp",), lambda k, s, d: jnp.zeros(s, d)),
            "w2": ParamDef((h1, h2), ("mlp", None), fanin_init()),
            "b2": ParamDef((h2,), (None,), lambda k, s, d: jnp.zeros(s, d)),
            "w3": ParamDef((h2, img), (None, None), fanin_init()),
            "b3": ParamDef((img,), (None,), lambda k, s, d: jnp.zeros(s, d)),
        },
    }


def init(cfg: CapsNetConfig, key: jax.Array) -> Dict[str, Any]:
    return init_params(capsnet_defs(cfg), key, cfg.pdtype())


def specs(cfg: CapsNetConfig) -> Dict[str, Any]:
    return param_specs(capsnet_defs(cfg))


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _conv2d(x: jax.Array, w_oihw: jax.Array, b: jax.Array, stride: int
            ) -> jax.Array:
    """NHWC x OIHW -> NHWC, VALID padding."""
    y = jax.lax.conv_general_dilated(
        x, w_oihw, window_strides=(stride, stride), padding="VALID",
        dimension_numbers=("NHWC", "OIHW", "NHWC"),
    )
    return y + b


def primary_capsules(params: Dict[str, Any], cfg: CapsNetConfig,
                     images: jax.Array) -> jax.Array:
    """images (B, H, W, C) -> squashed primary capsules (B, N_in, caps_dim)."""
    with jax.named_scope("caps.conv1"):
        h = jax.nn.relu(_conv2d(images, params["conv1"]["w"],
                                params["conv1"]["b"], 1))
    with jax.named_scope("caps.primary"):
        h = _conv2d(h, params["conv2"]["w"], params["conv2"]["b"],
                    cfg.caps_stride)                  # (B, 6, 6, types*dim)
        b = h.shape[0]
        hw = cfg.caps_out_hw
        # channel layout: (types, dim); capsule index = (type, y, x)
        h = h.reshape(b, hw, hw, h.shape[-1] // cfg.caps_dim, cfg.caps_dim)
        h = h.transpose(0, 3, 1, 2, 4).reshape(b, -1, cfg.caps_dim)
        return approx_math.squash(h, axis=-1)


def predictions(params: Dict[str, Any], u: jax.Array) -> jax.Array:
    """u (B, N_in, d_in) x W (N_in, N_out, d_in, d_out) -> u_hat
    (B, N_out, d_out, N_in): the routing layout, input capsules minor."""
    return jnp.einsum("bid,ijde->bjei", u, params["digit"]["w"])


def digit_capsules(params: Dict[str, Any], cfg: CapsNetConfig,
                   u: jax.Array) -> Tuple[jax.Array, jax.Array]:
    with jax.named_scope("caps.u_hat"):
        u_hat = predictions(params, u)
    route_fn = resolve_routing(cfg.routing_spec())
    with jax.named_scope("caps.route"):
        return route_fn(u_hat, n_iters=cfg.routing_iters)


def decode(params: Dict[str, Any], cfg: CapsNetConfig, v: jax.Array,
           labels: jax.Array) -> jax.Array:
    """Reconstruction decoder; masks all but the true class's capsule."""
    d = params["decoder"]
    mask = jax.nn.one_hot(labels, cfg.n_classes, dtype=v.dtype)  # (B, J)
    x = (v * mask[:, :, None]).reshape(v.shape[0], -1)
    x = jax.nn.relu(x @ d["w1"] + d["b1"])
    x = jax.nn.relu(x @ d["w2"] + d["b2"])
    return jax.nn.sigmoid(x @ d["w3"] + d["b3"])


def forward(params: Dict[str, Any], cfg: CapsNetConfig, images: jax.Array
            ) -> Tuple[jax.Array, jax.Array]:
    """images -> (class capsule lengths (B, n_classes), capsules v).

    The stages run under named scopes, ``caps.conv1``, ``caps.primary``,
    ``caps.u_hat``, ``caps.route`` and ``caps.length``, which the
    compiled program keeps in each op's ``op_name`` metadata."""
    u = primary_capsules(params, cfg, images)
    v, _ = digit_capsules(params, cfg, u)
    with jax.named_scope("caps.length"):
        lengths = jnp.linalg.norm(v.astype(jnp.float32), axis=-1)
    return lengths, v


# ---------------------------------------------------------------------------
# Losses / metrics
# ---------------------------------------------------------------------------


def margin_loss(lengths: jax.Array, labels: jax.Array, cfg: CapsNetConfig
                ) -> jax.Array:
    t = jax.nn.one_hot(labels, cfg.n_classes, dtype=jnp.float32)
    pos = jnp.square(jnp.maximum(0.0, cfg.m_plus - lengths))
    neg = jnp.square(jnp.maximum(0.0, lengths - cfg.m_minus))
    per_class = t * pos + cfg.lambda_down * (1.0 - t) * neg
    return jnp.mean(jnp.sum(per_class, axis=-1))


def loss_fn(params: Dict[str, Any], cfg: CapsNetConfig,
            images: jax.Array, labels: jax.Array
            ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    u = primary_capsules(params, cfg, images)
    v, _ = digit_capsules(params, cfg, u)
    lengths = jnp.linalg.norm(v.astype(jnp.float32) + 1e-12, axis=-1)
    l_margin = margin_loss(lengths, labels, cfg)
    recon = decode(params, cfg, v, labels)
    flat = images.reshape(images.shape[0], -1).astype(jnp.float32)
    l_recon = jnp.mean(jnp.sum(jnp.square(recon - flat), axis=-1))
    loss = l_margin + cfg.recon_weight * l_recon
    acc = jnp.mean((jnp.argmax(lengths, -1) == labels).astype(jnp.float32))
    return loss, {"loss": loss, "margin": l_margin,
                  "recon": l_recon, "acc": acc}


# ---------------------------------------------------------------------------
# Pruning integration (paper Fig. 6 pipeline)
# ---------------------------------------------------------------------------


def conv_chain(params: Dict[str, Any]) -> list:
    """The prunable conv chain, with DigitCaps W as conv2's look-ahead
    neighbour: W (N_in, N_out, d_in, d_out) folds to a dense
    (conv2-out-channel, class*dim) matrix so LAKP can see how much each
    PrimaryCaps channel matters downstream."""
    w_digit = params["digit"]["w"]
    n_in, n_out, d_in, d_out = w_digit.shape
    # each conv2 output channel = one (type, dim) pair; capsule i uses
    # channels type(i)*d_in ... +d_in.  Aggregate |W| onto (types*d_in, ...)
    # by summing over spatial positions of each type.
    return [params["conv1"]["w"], params["conv2"]["w"], w_digit]


def digit_w_as_dense(w_digit: jax.Array, caps_types: int, caps_dim: int,
                     hw: int) -> jax.Array:
    """(N_in, N_out, d_in, d_out) -> (types*caps_dim [conv2 out ch], rest).

    Capsule i = (type t, spatial p); its d_in inputs are conv2 channels
    t*caps_dim..+caps_dim.  Summing |W| over spatial positions gives the
    dense next-layer weight LAKP expects: rows = conv2 out channels.
    """
    n_in, n_out, d_in, d_out = w_digit.shape
    w = jnp.abs(w_digit).reshape(caps_types, hw * hw, n_out, d_in, d_out)
    w = jnp.sum(w, axis=1)                        # (types, n_out, d_in, d_out)
    w = w.transpose(0, 2, 1, 3).reshape(caps_types * d_in, n_out * d_out)
    return w


def lakp_masks(params: Dict[str, Any], cfg: CapsNetConfig,
               sparsity_conv1: float, sparsity_conv2: float,
               method: str = "lakp", norm: str = "l1",
               type_keep: Optional[int] = None):
    """Score + mask the two conv layers (the paper prunes Conv1 and the
    PrimaryCaps conv).  Returns (mask1, mask2).

    ``type_keep``: the paper's "interconnection study" step (Fig. 6) —
    after kernel masking, whole capsule *types* are eliminated down to the
    ``type_keep`` highest-scored ones (paper: 32 -> 7 on MNIST, 32 -> 12 on
    F-MNIST), zeroing every kernel of the dropped types."""
    from repro.core import lakp as lakp_lib

    w1, w2 = params["conv1"]["w"], params["conv2"]["w"]
    w_next = digit_w_as_dense(params["digit"]["w"], cfg.caps_types,
                              cfg.caps_dim, cfg.caps_out_hw)
    if method == "lakp":
        # w_next is (conv2_out_ch, n_out*d_out) == dense (in, out) layout
        s1 = lakp_lib.lakp_kernel_scores(w1, None, w2, norm=norm)
        s2 = lakp_lib.lakp_kernel_scores(w2, w1, w_next, norm=norm)
    elif method == "kp":
        s1, s2 = lakp_lib.kp_scores(w1), lakp_lib.kp_scores(w2)
    else:
        raise ValueError(method)
    m1 = lakp_lib.mask_from_scores(s1, sparsity_conv1)
    m2 = lakp_lib.mask_from_scores(s2, sparsity_conv2)
    if type_keep is not None and type_keep < cfg.caps_types:
        m2 = eliminate_capsule_types(s2 * m2, cfg, type_keep)
    return m1, m2


def eliminate_capsule_types(masked_scores2: jax.Array, cfg: CapsNetConfig,
                            keep: int) -> jax.Array:
    """Keep only the ``keep`` capsule types with the highest surviving
    kernel score; zero all kernels of the other types (and keep the
    surviving-kernel mask within kept types)."""
    o, i = masked_scores2.shape
    per_type = masked_scores2.reshape(cfg.caps_types, cfg.caps_dim, i)
    type_scores = jnp.sum(per_type, axis=(1, 2))            # (types,)
    order = jnp.argsort(-type_scores)
    keep_idx = order[:keep]
    type_mask = jnp.zeros((cfg.caps_types,)).at[keep_idx].set(1.0)
    ch_mask = jnp.repeat(type_mask, cfg.caps_dim)           # (O,)
    return (masked_scores2 > 0).astype(jnp.float32) * ch_mask[:, None]


def apply_masks(params: Dict[str, Any], masks) -> Dict[str, Any]:
    from repro.core import lakp as lakp_lib

    m1, m2 = masks
    out = jax.tree.map(lambda x: x, params)  # shallow copy
    out["conv1"] = dict(params["conv1"])
    out["conv2"] = dict(params["conv2"])
    out["conv1"]["w"] = lakp_lib.apply_kernel_mask(params["conv1"]["w"], m1)
    out["conv2"]["w"] = lakp_lib.apply_kernel_mask(params["conv2"]["w"], m2)
    return out


def compact(params: Dict[str, Any], cfg: CapsNetConfig, masks
            ) -> Tuple[Dict[str, Any], CapsNetConfig, Dict[str, jax.Array]]:
    """Physically remove pruned structures (paper §III-C index memory, TPU
    compaction analogue — DESIGN.md §2).

    * conv1: output channels with no surviving kernel are removed (and the
      corresponding conv2 input channels).
    * conv2: capsule *types* whose all caps_dim channels lost every kernel
      are removed — this is the 1152 -> 252 capsule elimination — and the
      DigitCaps weight rows for those capsules are removed.

    Returns (compacted params, updated config, surviving index vectors).
    """
    m1, m2 = masks
    w1, b1 = params["conv1"]["w"], params["conv1"]["b"]
    w2, b2 = params["conv2"]["w"], params["conv2"]["b"]
    wd = params["digit"]["w"]

    alive1 = jnp.nonzero(jnp.any(m1 > 0, axis=1))[0]          # conv1 out ch
    w1c = w1[alive1]
    b1c = b1[alive1]
    w2c = w2[:, alive1]                                       # conv2 in ch
    m2c = m2                                                  # (O2, I2) rows keep

    # capsule types: group conv2 out channels by caps_dim
    alive_ch = jnp.any(m2c > 0, axis=1)                       # (O2,)
    types_alive = jnp.any(
        alive_ch.reshape(cfg.caps_types, cfg.caps_dim), axis=1)
    type_idx = jnp.nonzero(types_alive)[0]                    # surviving types
    ch_idx = (type_idx[:, None] * cfg.caps_dim
              + jnp.arange(cfg.caps_dim)[None, :]).reshape(-1)
    w2c = w2c[ch_idx]
    b2c = b2[ch_idx]

    # DigitCaps rows: capsule i = (type, spatial); keep surviving types
    hw2 = cfg.caps_out_hw ** 2
    wd_t = wd.reshape(cfg.caps_types, hw2, cfg.n_classes, cfg.caps_dim,
                      cfg.digit_dim)
    wd_c = wd_t[type_idx].reshape(-1, cfg.n_classes, cfg.caps_dim,
                                  cfg.digit_dim)

    new_cfg = dataclasses.replace(
        cfg,
        conv1_channels=int(alive1.shape[0]),
        caps_types=int(type_idx.shape[0]),
    )
    out = {
        "conv1": {"w": w1c, "b": b1c},
        "conv2": {"w": w2c, "b": b2c},
        "digit": {"w": wd_c},
        "decoder": params["decoder"],
    }
    index = {"conv1_out": alive1, "caps_types": type_idx}
    return out, new_cfg, index


def param_count(params: Dict[str, Any]) -> int:
    return sum(int(x.size) for x in jax.tree.leaves(params))
