"""Where JAX's persistent compilation cache lives, for every entry point.

``enable_compile_cache()`` is called once by each program's main path
(``launch/serve.py``, ``launch/train.py``, ``benchmarks``, ``chip_smoke.py``);
importing this module changes nothing.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads the variable itself and
  this module sets no directory.
* unset: the cache goes to ``<checkout>/.jax_cache`` — one fixed path, so
  a second run of the same checkout finds what the first one compiled.
* a TPU process that sees more than one chip: the cache is off.  With
  JAX 0.9.0 / libtpu 0.0.34 on a four-chip v5e host, programs over a
  two-chip submesh (multihost disaggregation) ran correctly when freshly
  compiled and halted the chip ("program continuator has halted") when
  the same executables were loaded back from the cache.

Every compile is written, however short (the JAX default skips compiles
under a second, which is most Pallas kernels).  :func:`cache_events`
counts the cache's hits and misses in this process, and the seconds spent
in backend compiles (a hit costs only its read).
"""

from __future__ import annotations

import collections
import os
from typing import Dict, Optional

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")

_EVENTS = {"/jax/compilation_cache/cache_hits": "hits",
           "/jax/compilation_cache/cache_misses": "misses",
           "/jax/compilation_cache/compile_requests_use_cache": "requests"}
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_counts: Dict[str, float] = collections.Counter()
_listening = False


def _on_event(event: str, **kwargs) -> None:
    name = _EVENTS.get(event)
    if name is not None:
        _counts[name] += 1


def _on_duration(event: str, duration_secs: float, **kwargs) -> None:
    if event == _COMPILE_EVENT:
        _counts["compile_s"] += duration_secs


def enable_compile_cache() -> Optional[str]:
    """Turn the persistent compilation cache on and return its directory,
    or turn it off and return None on a TPU host with several chips."""
    global _listening
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    if not _listening:
        jax.monitoring.register_event_listener(_on_event)
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _listening = True
    devices = jax.devices()
    if devices[0].platform == "tpu" and len(devices) > 1:
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        return None
    path = os.environ.get(ENV)
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def cache_events() -> Dict[str, float]:
    """Cache requests, hits and misses, and backend compile seconds,
    counted in this process since :func:`enable_compile_cache`."""
    return {k: _counts[k]
            for k in ("requests", "hits", "misses", "compile_s")}
