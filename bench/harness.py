"""The benchmark harness: one cell, one seed, one process.

Everything that belongs to one configuration, traffic mix or metric is a
file of its own, found by the name ``BENCHMARK.json`` gives it:

* ``bench/configs/<config>.json``: the configuration as it is run.  Its
  ``model`` key names ``bench/models/<model>.py``, a ``Deployment``
  class that makes the weights from the seed, builds the engine through
  the program's normal entry points, warms the cell's shapes, turns
  schedule items into requests, and checks what the timed path produced
  against the plain reference ``bench/models/<model>_ref.py``.
* ``bench/traffic/<mix>.json``: the mix, read by ``traffic/generator.py``.
* ``bench/metrics/<metric>.py``: ``read(run) -> float | None`` for one
  metric, end to end or per layer.  ``None`` leaves the metric out.
* ``bench/work/<kernel or step>.py``: FLOPs and bytes from shapes.

The run: check the chip, turn the compile cache on, set up (weights,
engine, warm-up, and a ramp of load for open-loop mixes), measure for
``--seconds`` with the host clock, drain what was due, read the peak
memory, free the program's state, check correctness, print one JSON line.
With ``--trace 1`` a slice of the window is traced with the profiler and
the per-layer metrics are printed instead of the end-to-end ones.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import time
from typing import Any, Callable, Dict, List, Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
DRAIN_S = 60.0        # how long past the close a due request may finish
TRACE_MAX_S = 5.0     # length of the traced slice of the window


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with everything it names."""

    root: str
    workload: dict
    config: dict                  # the BENCHMARK.json config entry
    sizes: dict                   # bench/configs/<config>.json
    mix: dict                     # bench/traffic/<mix>.json
    end_to_end: List[dict]        # metrics this cell reports, --trace 0
    per_layer: List[dict]         # metrics this cell reports, --trace 1

    @property
    def name(self) -> str:
        return self.workload["name"]

    @property
    def chips(self) -> int:
        return int(self.workload["chips"])

    def path(self, *parts: str) -> str:
        return os.path.join(self.root, "bench", *parts)

    def serve(self) -> dict:
        """Engine settings: the configuration's, then the mix's."""
        return {**self.sizes.get("serve", {}), **self.mix.get("serve", {})}

    def adapter(self):
        name = self.sizes["model"]
        return load_module(self.path("models", f"{name}.py"),
                           f"bench_model_{name}")

    def reader(self, metric: str):
        return load_module(self.path("metrics", f"{metric}.py"),
                           f"bench_metric_{metric}")


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root: str, workload: str) -> Cell:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    config = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(os.path.join(root, config["file"])) as f:
        sizes = json.load(f)
    from traffic import generator

    mix = generator.load(
        os.path.join(root, "bench", "traffic", f"{w['traffic']}.json"))
    return Cell(root=root, workload=w, config=config, sizes=sizes, mix=mix,
                end_to_end=[m for m in bench["end_to_end"]
                            if _reports(m, workload)],
                per_layer=[m for m in bench["per_layer"]
                           if _reports(m, workload)])


def require_chip(chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found "
                     f"{len(devices)}")
    return devices[:chips]


# ---------------------------------------------------------------------------
# records of one run
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Req:
    """One request as the client saw it, on the host clock."""

    index: int
    sizes: Dict[str, int]
    due: float
    submitted: float = math.nan
    rid: int = -1
    stamps: List[float] = dataclasses.field(default_factory=list)
    done: float = math.nan        # completion stamp; NaN = never finished


@dataclasses.dataclass
class Tick:
    t0: float
    t1: float
    items: int = 0                # stream items stamped after this tick


@dataclasses.dataclass
class Run:
    """What the metric readers see."""

    cell: Cell
    seed: int
    seconds: float
    t_open: float
    t_close: float
    setup_s: float
    reqs: List[Req]
    ticks: List[Tick]
    stats_open: Any               # EngineStats at the window's opening
    stats_close: Any              # ... and at its close
    facts: Dict[str, Any]         # model facts the work functions read
    device_kind: str
    trace: Any = None             # trace_reduce.Reduction (--trace 1)

    @property
    def window_s(self) -> float:
        return self.t_close - self.t_open

    def in_window(self, t: float) -> bool:
        return self.t_open <= t < self.t_close

    def due_in_window(self) -> List[Req]:
        return [r for r in self.reqs if self.in_window(r.due)]

    def window_ticks(self) -> List[Tick]:
        return [t for t in self.ticks
                if t.t0 >= self.t_open and t.t1 <= self.t_close]

    def work(self, name: str):
        return load_module(self.cell.path("work", f"{name}.py"),
                           f"bench_work_{name}")

    def peaks(self) -> dict:
        import peaks

        return peaks.peaks(self.device_kind)


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------


class Client:
    """Single-threaded client and ticker: submits what is due, ticks the
    engine, stamps what each tick produced."""

    def __init__(self, dep, sched, seed: int, clock=time.perf_counter):
        self.dep = dep
        self.engine = dep.engine
        self.clock = clock
        self.reqs: List[Req] = []
        self.ticks: List[Tick] = []
        self.by_rid: Dict[int, Req] = {}
        self.closed = sched.process == "closed"
        self.stream = sched.closed_items(seed) if self.closed else None
        self.pending = list(sched.items)
        self.next = 0
        self.idle_clients = sched.clients
        self.annotate = None      # TraceAnnotation while tracing

    def _submit(self, item, due: float) -> None:
        req = Req(index=item.index, sizes=item.sizes, due=due)
        request = self.dep.request(item)
        req.submitted = self.clock()
        req.rid = self.engine.submit(request)
        self.by_rid[req.rid] = req
        self.reqs.append(req)

    def submit_due(self, origin: float, until: float) -> None:
        now = self.clock()
        if self.closed:
            while self.idle_clients and now < until:
                self._submit(next(self.stream), now)
                self.idle_clients -= 1
            return
        while self.next < len(self.pending):
            item = self.pending[self.next]
            due = origin + item.due
            if due > now or due >= until:
                break
            self._submit(item, due)
            self.next += 1

    def tick(self) -> bool:
        t0 = self.clock()
        if self.annotate is not None:
            with self.annotate("bench.tick"):
                busy = self.engine.tick()
        else:
            busy = self.engine.tick()
        t1 = self.clock()
        if not busy:
            return False
        tick = Tick(t0, t1)
        self.ticks.append(tick)
        for ev in self.engine.poll(stream=self.dep.stream):
            req = self.by_rid[ev.rid]
            if self.dep.stream:
                if ev.done:
                    req.done = t1
                    self.dep.on_done(req, ev.completion)
                else:
                    tick.items += 1
                    req.stamps.append(t1)
            else:
                req.done = t1
                self.dep.on_done(req, ev)
            if not math.isnan(req.done) and self.closed:
                self.idle_clients += 1
        return True

    def run(self, origin: float, until: float) -> None:
        """Serve arrivals due before ``until`` (offsets from ``origin``)
        until the clock reaches ``until``."""
        while True:
            now = self.clock()
            if now >= until:
                return
            self.submit_due(origin, until)
            busy = self.tick()
            if not busy and not self.closed:
                nxt = (origin + self.pending[self.next].due
                       if self.next < len(self.pending) else until)
                time.sleep(max(0.0, min(nxt, until) - self.clock()))

    def drain(self, origin: float, close: float, limit: float) -> None:
        """After the close: submit what was due in the window and tick
        until every submitted request finished, or until ``limit``."""
        self.submit_due(origin, close)
        self.closed_clients_stop()
        while self.clock() < limit:
            if not self.tick() and self.engine.n_pending == 0:
                return

    def closed_clients_stop(self) -> None:
        self.idle_clients = 0
        self.closed = False       # no client sends again
        self.pending, self.next = [], 0


def _lag_line(reqs: List[Req], t_open: float, t_close: float) -> dict:
    import tails

    lags = [1e3 * (r.submitted - r.due) for r in reqs
            if t_open <= r.due < t_close]
    if not lags:
        return {"generator_lag_ms": None}
    return {"generator_lag_ms": {"p50": tails.percentile(lags, 50),
                                 "p99": tails.percentile(lags, 99),
                                 "max": max(lags), "n": len(lags)}}


def _done_per_s(reqs: List[Req], t_open: float, t_close: float) -> dict:
    """Requests finished in each whole second of the window: shows
    whether a run's rate dips inside the window or differs throughout."""
    n = max(int(round(t_close - t_open)), 1)
    counts = [0] * n
    for r in reqs:
        k = int((r.done - t_open) // 1.0) if not math.isnan(r.done) else -1
        if 0 <= k < n and r.done < t_close:
            counts[k] += 1
    return {"done_per_s": counts}


def run_cell(root: str, workload: str, seed: int, seconds: float,
             trace: bool, *, t_start: Optional[float] = None,
             chip_check: bool = True, patch: Optional[Callable] = None,
             out=sys.stdout, err=sys.stderr) -> dict:
    """Run one cell once and return the result line's object (printed as
    the last line of ``out``).  ``patch(dep)`` may alter the deployment
    after set-up; the tests use it to break the timed path."""
    t_start = time.perf_counter() if t_start is None else t_start
    cell = load_cell(root, workload)
    import jax

    devices = require_chip(cell.chips) if chip_check else jax.devices()
    from repro.launch.compile_cache import cache_events, enable_compile_cache

    cache_dir = enable_compile_cache()
    from traffic import generator

    sched = generator.schedule(cell.mix, seed, seconds)
    dep = cell.adapter().Deployment(cell, seed, sched)
    dep.warm()
    if patch is not None:
        patch(dep)
    client = Client(dep, sched, seed)
    ramp = float(cell.mix["arrivals"].get("ramp_s", 0.0))
    origin = time.perf_counter() + ramp
    if ramp:
        client.run(origin, origin)            # load before the window
    gc.collect()
    gc.freeze()               # set-up's objects are not scanned again
    t_open = time.perf_counter()
    stats_open = dep.engine.stats()
    events_open = cache_events()
    t_close = t_open + seconds
    reduction = None
    trace_dir = os.path.join(root, ".bench_trace", cell.name)
    if trace:
        _traced_window(client, origin, t_open, t_close, trace_dir)
    else:
        client.run(origin, t_close)
    stats_close = dep.engine.stats()
    events_close = cache_events()
    client.drain(origin, t_close, t_close + DRAIN_S)
    if trace:
        import trace_reduce

        reduction = trace_reduce.reduce_trace(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
    mem = [d.memory_stats() or {} for d in devices]
    peak = max(m.get("peak_bytes_in_use", 0) for m in mem)
    run = Run(cell=cell, seed=seed, seconds=seconds, t_open=t_open,
              t_close=t_close, setup_s=t_open - t_start, reqs=client.reqs,
              ticks=client.ticks, stats_open=stats_open,
              stats_close=stats_close, facts=dep.facts(),
              device_kind=devices[0].device_kind, trace=reduction)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = cell.reader(m["name"]).read(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    info = {"cache_dir": cache_dir,
            "compiles_in_window": events_close["requests"]
            - events_open["requests"],
            "compile_s_in_window": events_close["compile_s"]
            - events_open["compile_s"],
            "ticks_in_window": len(run.window_ticks()),
            "peak_bytes": peak,
            "bytes_limit": min(m.get("bytes_limit", 0) for m in mem),
            **_lag_line(client.reqs, t_open, t_close),
            **_done_per_s(client.reqs, t_open, t_close),
            **dep.info(run)}
    print(json.dumps(info), file=out, flush=True)
    due = run.due_in_window()
    failed = sum(1 for r in due if math.isnan(r.done))
    dep.release()
    del client
    gc.unfreeze()
    gc.collect()
    checks = dep.check(run, generator.rng(seed, "sample"))
    for c in checks:
        limit = c["limit"] if c.get("judged", True) else "not set"
        print(f"compared {c['name']}: {c['value']!r} limit {limit!r}",
              file=err, flush=True)
    correct = failed == 0 and all(
        c.get("judged", True) and c["value"] <= c["limit"] for c in checks)
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": bool(correct), "attempted": len(due),
              "failed": failed, "metrics": metrics, "device": device}
    if reduction is not None:
        device["busy_s"] = reduction.busy_s
        device["window_s"] = reduction.window_s
        result["breakdown"] = reduction.breakdown()
    result["compared"] = {
        c["name"]: {"value": c["value"],
                    "limit": c["limit"] if c.get("judged", True) else None}
        for c in checks}
    print(json.dumps(result), file=out, flush=True)
    return result


def _traced_window(client: Client, origin: float, t_open: float,
                   t_close: float, trace_dir: str) -> None:
    """Run the window, profiling a slice of it that starts a third of the
    way in."""
    import jax

    span = min(TRACE_MAX_S, (t_close - t_open) / 3.0)
    t_a = t_open + (t_close - t_open) / 3.0
    client.run(origin, t_a)
    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    client.annotate = jax.profiler.TraceAnnotation
    try:
        client.run(origin, t_a + span)
    finally:
        client.annotate = None
        jax.profiler.stop_trace()
    client.run(origin, t_close)


def main(argv=None, t_start: Optional[float] = None) -> int:
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    root = os.path.dirname(BENCH)
    try:
        run_cell(root, args.workload, args.seed, args.seconds,
                 bool(args.trace), t_start=t_start)
    except NoChip as e:
        print(f"bench: {e}; nothing was run", file=sys.stderr)
        return 1
    return 0
