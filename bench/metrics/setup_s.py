"""Set-up: process start to the window's opening (weights, engine,
compiles or cache loads, warm-up, and the ramp of an open-loop mix)."""


def read(run):
    return run.setup_s
