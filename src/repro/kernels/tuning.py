"""Measured block-size autotuning for the kernel registry.

FastCaps' methodology is a *design-space search* over kernel
configurations (Fig. 1/8: simplified nonlinearities, reordered loops,
parallelization factors chosen per target).  This module is the search
half of that story for the Pallas kernels: every
:class:`repro.kernels.KernelSpec` declares a tunable block-size space,
and the tuner measures the candidates on the live backend and remembers
the winner.

Three pieces:

* **Deterministic defaults** (``tune=False``, the CI path) — config
  resolution never measures anything: the spec's base config is
  legalized against the concrete shapes (``largest_divisor`` replaces
  the old per-kernel halving loops, so e.g. an odd batch of 9 gets
  ``batch_block=3`` instead of degrading to 1).
* **The measured tuner** (:func:`autotune`) — times every legalized
  candidate config of a kernel on example inputs (median wall-clock,
  compile excluded) and returns the winner plus the full timing table.
  The base config is always a candidate, so the tuned choice is never
  slower than the old hard-coded blocks on the measuring machine.
* **The on-disk cache** (:class:`TuneCache`) — winners are stored as
  JSON keyed by ``(kernel, backend, shape-bucket, dtype)`` under
  ``~/.cache/repro-kernels`` (override with ``REPRO_KERNEL_CACHE_DIR``),
  so tuning survives processes and CI runs can cache the artifact.
  Shapes are bucketed to powers of two: one tuning run covers the whole
  bucket, keeping the cache small and lookups O(1).

Whether dispatch *consults* the tuner is a scoped policy, not a global:
``with tuning(True): ...`` (thread-local) or the ``REPRO_KERNEL_TUNE=1``
environment variable.  Inside a ``jax.jit`` trace the arguments are
tracers, so dispatch can only *read* the cache (shape buckets are known
at trace time); filling it requires concrete arrays — that is what
bind-time pretuning in ``repro.serving`` and the
``python -m repro.kernels.tuning`` CLI are for.
"""

from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

CACHE_ENV = "REPRO_KERNEL_CACHE_DIR"
TUNE_ENV = "REPRO_KERNEL_TUNE"
CACHE_VERSION = 1


# ---------------------------------------------------------------------------
# Deterministic config helpers (shared by every spec's legalizer)
# ---------------------------------------------------------------------------


def largest_divisor(n: int, cap: int) -> int:
    """Largest divisor of ``n`` that is <= ``cap`` (>= 1).

    This is the shared block-size default: the whole dimension is covered
    by equal full blocks, and an odd size degrades gracefully (n=9, cap=8
    -> 3) instead of collapsing to 1 the way halving-from-8 did.

    Raises :class:`ValueError` on ``n <= 0`` or ``cap <= 0`` — a zero-size
    dimension or a zero/negative block request is always a caller bug
    (empty example case, config typo), and silently returning 1 used to
    hide it until the kernel produced garbage grids.
    """
    n, cap = int(n), int(cap)
    if n <= 0:
        raise ValueError(f"largest_divisor: dimension must be positive, "
                         f"got n={n}")
    if cap <= 0:
        raise ValueError(f"largest_divisor: block cap must be positive, "
                         f"got cap={cap} (for dimension n={n})")
    for d in range(min(n, cap), 0, -1):
        if n % d == 0:
            return d
    return 1


def next_pow2(n: int) -> int:
    """Smallest power of two >= n (bucket key for cache shapes).  Named
    distinctly from ``serving.schedulers.pow2_bucket(n, cap)``, which
    clamps — confusing the two picks the wrong bucket."""
    b = 1
    while b < int(n):
        b *= 2
    return b


def shape_bucket(shapes: Iterable[Tuple[int, ...]]) -> str:
    """Cache-key string for a tuple of array shapes, pow2-bucketed per dim
    (``(9, 252, 10, 16)`` -> ``"16x256x16x16"``)."""
    return ",".join("x".join(str(next_pow2(d)) for d in s) or "scalar"
                    for s in shapes)


def config_label(config: Dict[str, Any]) -> str:
    """Canonical label for a config in timing tables and reports
    (``{"q_block": 64, "kv_block": 128}`` -> ``"kv_block=128,q_block=64"``).
    The single source of the format — :func:`autotune` keys its timing
    table with it, and benches/tests must index with it, never rebuild
    the string by hand."""
    return ",".join(f"{k}={config[k]}" for k in sorted(config))


# ---------------------------------------------------------------------------
# Tuning policy (scoped, thread-local)
# ---------------------------------------------------------------------------

_POLICY = threading.local()


def tune_enabled() -> bool:
    """Whether dispatch should consult the tuner cache (scope > env)."""
    scoped = getattr(_POLICY, "tune", None)
    if scoped is not None:
        return scoped
    return (os.environ.get(TUNE_ENV, "").strip().lower()
            not in ("", "0", "false", "off", "no"))


@contextlib.contextmanager
def tuning(enabled: bool = True):
    """Scope in which registry dispatch prefers tuned configs.

    Thread-local, so one serving engine can bind tuned executables while
    another thread stays on deterministic defaults.
    """
    prev = getattr(_POLICY, "tune", None)
    _POLICY.tune = bool(enabled)
    try:
        yield
    finally:
        _POLICY.tune = prev


# ---------------------------------------------------------------------------
# On-disk cache
# ---------------------------------------------------------------------------


class TuneCache:
    """JSON-backed winner cache keyed ``kernel|backend|bucket|dtype``.

    The file is read lazily once and written atomically (per-writer tmp
    + rename, with a merge of the on-disk entries first), so multiple
    processes sharing one cache dir can write concurrently without ever
    publishing corrupt JSON or erasing each other's keys; an unwritable
    cache dir degrades to memory-only.  Entries store the winning config
    plus the measured timing table for reporting::

        {"version": 1,
         "entries": {"fused_routing|cpu|32x16x16x256|float32":
                     {"config": {"batch_block": 8},
                      "timings": {"batch_block=8": 0.0012, ...}}}}
    """

    def __init__(self, path: Optional[str] = None):
        if path is None:
            root = os.environ.get(CACHE_ENV) or os.path.join(
                os.path.expanduser("~"), ".cache", "repro-kernels")
            path = os.path.join(root, "autotune.json")
        self.path = path
        self._entries: Optional[Dict[str, Dict[str, Any]]] = (
            None)                                      # guarded-by: _lock
        self._written: set = set()                     # guarded-by: _lock
        #   ^ the keys THIS instance put (the merge-on-write overlay set)
        self._lock = threading.Lock()

    @staticmethod
    def key(kernel: str, backend: str, bucket: str, dtype: str) -> str:
        return f"{kernel}|{backend}|{bucket}|{dtype}"

    def _load_locked(self) -> Dict[str, Dict[str, Any]]:
        """Lazy read of the on-disk cache; ``_locked`` = caller holds
        ``self._lock`` (every public entry point takes it first)."""
        if self._entries is None:
            entries: Dict[str, Dict[str, Any]] = {}
            try:
                with open(self.path) as f:
                    blob = json.load(f)
                if blob.get("version") == CACHE_VERSION:
                    entries = dict(blob.get("entries", {}))
            except (OSError, ValueError):
                pass
            self._entries = entries
        return self._entries

    def get(self, key: str) -> Optional[Dict[str, Any]]:
        with self._lock:
            entry = self._load_locked().get(key)
            return dict(entry["config"]) if entry else None

    def entry(self, key: str) -> Optional[Dict[str, Any]]:
        with self._lock:
            e = self._load_locked().get(key)
            return json.loads(json.dumps(e)) if e else None

    def put(self, key: str, config: Dict[str, Any],
            timings: Optional[Dict[str, float]] = None) -> None:
        with self._lock:
            entries = self._load_locked()
            entries[key] = {"config": dict(config),
                            "timings": dict(timings or {})}
            self._written.add(key)
            try:
                os.makedirs(os.path.dirname(self.path), exist_ok=True)
                # Concurrent writers (two serving processes sharing one
                # REPRO_KERNEL_CACHE_DIR) must never corrupt the file or
                # erase each other's keys:
                #   * an exclusive flock on a sidecar lock file brackets
                #     the whole read-merge-replace, so no other writer's
                #     publish can land inside our window (platforms
                #     without fcntl skip the lock: writes stay corruption
                #     -free via the rename, a racing key may be lost);
                #   * merge-on-write — re-read the file under the lock
                #     and overlay ONLY the keys this instance itself
                #     wrote, so entries another process published since
                #     our lazy load survive (overlaying the whole stale
                #     in-memory snapshot would silently revert them);
                #   * a per-writer tmp name — a shared `.tmp` would let
                #     two processes interleave writes into one file and
                #     os.replace() would then publish the garbage;
                #   * atomic rename — readers only ever see a complete
                #     JSON document.
                with self._file_lock():
                    merged: Dict[str, Dict[str, Any]] = {}
                    try:
                        with open(self.path) as f:
                            blob = json.load(f)
                        if blob.get("version") == CACHE_VERSION:
                            merged.update(blob.get("entries", {}))
                    except (OSError, ValueError):
                        pass
                    merged.update({k: entries[k] for k in self._written
                                   if k in entries})
                    self._entries = merged
                    tmp = (f"{self.path}.{os.getpid()}."
                           f"{threading.get_ident()}.tmp")
                    with open(tmp, "w") as f:
                        json.dump({"version": CACHE_VERSION,
                                   "entries": merged},
                                  f, indent=1, sort_keys=True)
                    os.replace(tmp, self.path)
            except OSError:
                pass                      # memory-only fallback

    @contextlib.contextmanager
    def _file_lock(self):
        """Exclusive cross-process lock around read-merge-replace (a
        sidecar ``.lock`` file, never the data file itself — locking the
        file we os.replace would lock a dead inode)."""
        try:
            import fcntl
        except ImportError:               # non-POSIX: best-effort, no lock
            yield
            return
        with open(self.path + ".lock", "w") as lf:
            fcntl.flock(lf, fcntl.LOCK_EX)
            try:
                yield
            finally:
                fcntl.flock(lf, fcntl.LOCK_UN)

    def clear_memory(self) -> None:
        """Drop the in-memory view (tests: re-read after env changes)."""
        with self._lock:
            self._entries = None


_default_cache = TuneCache()


def default_cache() -> TuneCache:
    """Process-wide cache; re-targets if REPRO_KERNEL_CACHE_DIR changed."""
    global _default_cache
    root = os.environ.get(CACHE_ENV) or os.path.join(
        os.path.expanduser("~"), ".cache", "repro-kernels")
    expect = os.path.join(root, "autotune.json")
    if _default_cache.path != expect:
        _default_cache = TuneCache(expect)
    return _default_cache


# ---------------------------------------------------------------------------
# Measurement
# ---------------------------------------------------------------------------


def _time_call(fn: Callable[[], Any], warmup: int = 1, iters: int = 3
               ) -> float:
    import jax

    for _ in range(warmup):
        jax.block_until_ready(fn())
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


def candidate_configs(spec, *args, **kwargs) -> List[Dict[str, Any]]:
    """Legalized, deduplicated candidate configs for ``spec`` on these
    shapes: the cartesian product of the tuned axes of ``spec.space``,
    with the (legalized) base config guaranteed present and first."""
    import itertools

    base = spec.legalize(dict(spec.base_config), *args, **kwargs)
    seen, out = set(), []

    def push(cfg):
        key = tuple(sorted(cfg.items()))
        if key not in seen:
            seen.add(key)
            out.append(cfg)

    push(base)
    axes = [(k, spec.space[k]) for k in spec.tuned]
    for combo in itertools.product(*(vals for _, vals in axes)):
        cand = dict(spec.base_config)
        cand.update({k: v for (k, _), v in zip(axes, combo)})
        push(spec.legalize(cand, *args, **kwargs))
    return out


def autotune(spec, args: tuple, kwargs: Optional[dict] = None,
             cache: Optional[TuneCache] = None, warmup: int = 1,
             iters: int = 3) -> Tuple[Dict[str, Any], Dict[str, float]]:
    """Measure every candidate config of ``spec`` on concrete ``args``.

    Returns ``(best_config, timings)`` where ``timings`` maps a compact
    config label to median seconds; the winner is stored in ``cache``
    (the default on-disk cache when None) under the shape-bucket key, so
    later dispatches — including trace-time dispatch inside ``jax.jit``
    — pick it up.
    """
    kwargs = dict(kwargs or {})
    cache = cache or default_cache()
    key = cache_key_for(spec, args)
    impl = spec.build()
    interpret = needs_interpret()
    best_cfg, best_t = None, float("inf")
    timings: Dict[str, float] = {}
    for cfg in candidate_configs(spec, *args, **kwargs):
        label = config_label(cfg)
        t = _time_call(
            lambda cfg=cfg: impl(*args, interpret=interpret,
                                 **kwargs, **cfg),
            warmup=warmup, iters=iters)
        timings[label] = t
        if t < best_t:
            best_cfg, best_t = cfg, t
    assert best_cfg is not None
    cache.put(key, best_cfg, timings)
    return best_cfg, timings


def cache_key_for(spec, args: tuple) -> str:
    """(kernel, backend, shape-bucket, dtype) key for these arguments."""
    import jax
    import numpy as np

    shapes = [tuple(getattr(a, "shape", ())) for a in args
              if hasattr(a, "shape")]
    first = next((a for a in args if hasattr(a, "dtype")), None)
    dtype = str(np.dtype(first.dtype)) if first is not None else "none"
    return TuneCache.key(spec.name, jax.default_backend(),
                         shape_bucket(shapes), dtype)


def needs_interpret() -> bool:
    """THE backend capability probe for every Pallas kernel: compiled
    natively only on TPU; every other backend (cpu, gpu) runs the Pallas
    interpreter.  This is the single place that probes — wrappers and
    registries import it, never re-derive it."""
    import jax

    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# CLI: selfcheck (tune=False parity on the interpret path) and pretune
# ---------------------------------------------------------------------------


def _selfcheck() -> int:
    """tune=False dispatch of every registered kernel on this backend's
    interpret path, checked against the jnp reference.  CI runs this to
    pin the deterministic default path."""
    import numpy as np

    from repro.kernels.registry import registry

    failures = []
    for name in registry.names():
        spec = registry.get(name)
        if not spec.is_available():
            print(f"[selfcheck] {name}: SKIP (unavailable)")
            continue
        for i, case in enumerate(spec.example_cases):
            args, kwargs = spec.make_example(case)
            # tune=False is passed explicitly: under ``python -m`` this
            # module also exists as __main__, so a tuning() scope set
            # here would toggle the wrong module's thread-local
            got = registry.call(name, *args, tune=False, **kwargs)
            want = spec.ref_call(*args, **kwargs)
            ok = True
            for g, w in zip(_leaves(got), _leaves(want)):
                if not np.allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32),
                                   atol=case.get("atol", 1e-5)):
                    ok = False
            status = "ok" if ok else "FAIL"
            cfg = registry.default_config(name, *args, **kwargs)
            print(f"[selfcheck] {name} case#{i} {cfg}: {status}")
            if not ok:
                failures.append((name, i))
    if failures:
        print(f"[selfcheck] FAILED: {failures}")
        return 1
    print("[selfcheck] all kernels dispatch with tune=False: OK")
    return 0


def _leaves(x):
    return list(x) if isinstance(x, (tuple, list)) else [x]


def _pretune(names: List[str], warmup: int, iters: int,
             force: bool = False) -> int:
    from repro.kernels.registry import registry

    cache = default_cache()
    for name in names:
        spec = registry.get(name)
        if not spec.is_available():
            print(f"[pretune] {name}: SKIP (unavailable)")
            continue
        for case in spec.example_cases:
            args, kwargs = spec.make_example(case)
            key = cache_key_for(spec, args)
            if not force and cache.get(key) is not None:
                print(f"[pretune] {name} {key}: cached")
                continue
            best, _ = autotune(spec, args, kwargs, cache=cache,
                               warmup=warmup, iters=iters)
            print(f"[pretune] {name} {key} -> {best}")
    print(f"[pretune] cache: {cache.path}")
    return 0


def main(argv=None) -> int:
    import argparse

    from repro.kernels.registry import registry

    ap = argparse.ArgumentParser(
        description="Kernel autotuner: selfcheck / pretune the cache")
    ap.add_argument("--selfcheck", action="store_true",
                    help="tune=False parity of every kernel vs reference")
    ap.add_argument("--pretune", default=None,
                    help="autotune one kernel by name, or 'all'")
    ap.add_argument("--force", action="store_true",
                    help="re-measure even when the cache has an entry")
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--warmup", type=int, default=1)
    args = ap.parse_args(argv)
    rc = 0
    if args.selfcheck:
        rc |= _selfcheck()
    if args.pretune:
        names = (registry.names() if args.pretune == "all"
                 else [args.pretune])
        rc |= _pretune(names, args.warmup, args.iters, force=args.force)
    if not args.selfcheck and not args.pretune:
        ap.print_help()
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
