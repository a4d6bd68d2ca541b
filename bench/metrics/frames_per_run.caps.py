"""Frames CapsuleEngine moved per run in the window: consecutive batch
rows holding consecutive frames of one request, copied in and back as
one slice each (``EngineStats`` ``items`` over ``counts["caps.runs"]``,
window differences); ``None`` where the program keeps no such counter."""


def read(run):
    a = getattr(run.stats_open, "counts", None)
    b = getattr(run.stats_close, "counts", None)
    if a is None or b is None or "caps.runs" not in b:
        return None
    runs = b["caps.runs"] - a.get("caps.runs", 0)
    if runs <= 0:
        return None
    return (run.stats_close.items - run.stats_open.items) / runs
