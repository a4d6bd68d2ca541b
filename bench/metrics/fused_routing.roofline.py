"""Roofline share of the fused routing kernel in the traced slice: the
least time its calls could take (bench/work/fused_routing.py over the
compiled batch, float32 u_hat) over their device time in the trace.
Each call is bound by memory at these sizes; see PERF.md."""

import peaks

KERNEL = "fused_routing"


def read(run):
    if run.trace is None:
        return None
    seconds, calls = run.trace.kernel(KERNEL)
    if calls == 0 or seconds <= 0:
        return None
    f = run.facts
    hw2 = ((f["image_hw"] - f["conv1_kernel"] + 1) - f["caps_kernel"]) \
        // f["caps_stride"] + 1
    shape = (f["batch"], f["n_classes"], f["digit_dim"],
             f["caps_types"] * hw2 * hw2)
    w = run.work("fused_routing")
    least, _ = peaks.roofline_s(w.flops(*shape, f["routing_iters"]),
                                w.nbytes(*shape), run.device_kind)
    return 100.0 * calls * least / seconds
