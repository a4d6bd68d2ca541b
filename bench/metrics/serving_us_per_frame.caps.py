"""Host time of the serving layer per frame served in the window: the
engine's phase spans (submit, schedule, admit, step, retire) less the
device forward inside the step (``caps.device``), in microseconds.
``EngineStats.phase_s`` differences over the window; ``None`` where the
program keeps no phase times."""

SERVING = ("serving.submit", "serving.schedule", "serving.admit",
           "serving.step", "serving.retire")


def read(run):
    a = getattr(run.stats_open, "phase_s", None)
    b = getattr(run.stats_close, "phase_s", None)
    frames = run.stats_close.items - run.stats_open.items
    if a is None or b is None or frames <= 0:
        return None

    def spent(name):
        return b.get(name, 0.0) - a.get(name, 0.0)

    host = sum(spent(n) for n in SERVING) - spent("caps.device")
    return 1e6 * host / frames
