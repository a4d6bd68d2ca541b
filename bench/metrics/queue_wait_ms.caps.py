"""Mean wait of a frame in the engine's queue, submit to admission, over
the frames admitted in the window, in milliseconds (``EngineStats``
``queue_wait_s`` over ``admitted``); ``None`` where the program keeps no
such counters."""


def read(run):
    a, b = run.stats_open, run.stats_close
    if not hasattr(a, "queue_wait_s") or not hasattr(b, "queue_wait_s"):
        return None
    n = b.admitted - a.admitted
    if n <= 0:
        return None
    return 1e3 * (b.queue_wait_s - a.queue_wait_s) / n
