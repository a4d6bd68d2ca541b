"""Share of the traced window with no operation on the device."""


def read(run):
    if run.trace is None or run.trace.window_s <= 0:
        return None
    return 100.0 * run.trace.idle_share
