"""Model step utilisation: forward FLOPs of the frames served in the
window (bench/work/capsnet_forward.py) over the summed host span of the
window's ticks, as a share of the chip's bf16 peak."""


def read(run):
    ticks = run.window_ticks()
    span = sum(t.t1 - t.t0 for t in ticks)
    frames = run.stats_close.items - run.stats_open.items
    if span <= 0 or frames <= 0:
        return None
    flops = run.work("capsnet_forward").flops_per_frame(run.facts) * frames
    return 100.0 * flops / span / run.peaks()["bf16_flops"]
