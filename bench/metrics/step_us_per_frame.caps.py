"""The model step per frame served in the window: the engine's
``caps.device`` span (placement, the jitted forward, the blocking
readback), in microseconds.  ``EngineStats.phase_s`` differences over
the window; ``None`` where the program keeps no phase times."""

SPAN = "caps.device"


def read(run):
    a = getattr(run.stats_open, "phase_s", None)
    b = getattr(run.stats_close, "phase_s", None)
    frames = run.stats_close.items - run.stats_open.items
    if a is None or b is None or SPAN not in b or frames <= 0:
        return None
    return 1e6 * (b[SPAN] - a.get(SPAN, 0.0)) / frames
