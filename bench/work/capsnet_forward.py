"""Forward FLOPs of one frame through the deployed CapsNet, from its
sizes (the ``facts`` of a capsnet cell): conv1, the primary-capsule conv,
the capsule predictions u_hat, and routing (``fused_routing.py``).
Bias adds, ReLU and the primary squash are counted too."""

import importlib.util
import os


def _routing():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fused_routing.py")
    spec = importlib.util.spec_from_file_location("work_fused_routing", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def flops_per_frame(f: dict) -> int:
    k1, k2 = f["conv1_kernel"], f["caps_kernel"]
    hw1 = f["image_hw"] - k1 + 1
    hw2 = (hw1 - k2) // f["caps_stride"] + 1
    c1, c2 = f["conv1_channels"], f["caps_types"] * f["caps_dim"]
    n_in = f["caps_types"] * hw2 * hw2
    j, d, dd = f["n_classes"], f["caps_dim"], f["digit_dim"]
    conv1 = hw1 * hw1 * c1 * (2 * f["in_channels"] * k1 * k1 + 2)
    conv2 = hw2 * hw2 * c2 * (2 * c1 * k2 * k2 + 1)
    squash = n_in * (4 * d + 4)
    pred = 2 * n_in * j * d * dd
    route = _routing().flops(1, j, dd, n_in, f["routing_iters"])
    return conv1 + conv2 + squash + pred + route
