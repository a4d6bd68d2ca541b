"""Frames of the requests completed inside the window, per second of
window."""


def read(run):
    frames = sum(r.sizes["frames"] for r in run.reqs if run.in_window(r.done))
    return frames / run.window_s
