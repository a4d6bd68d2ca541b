"""Reduce one profiler trace (``.xplane.pb``) to device busy time, kernel
time and idle gaps.

* Device operations are the events of the ``XLA Ops`` line of each
  ``/device:TPU:<n>`` plane.  Busy time is the union of their intervals
  inside the window, so overlapping or nested events count once.
* The window runs from the start of the first host span named
  ``span`` (the harness wraps each engine tick in one) to the end of the
  last, on the trace's own clock.
* An idle gap is a stretch of the window in which no operation runs on
  the device.  Each is labelled by the innermost host event open at its
  middle (the harness's own tick spans, or the runtime's dispatch
  events), or "between ticks" where none is (the harness polling,
  submitting or waiting for arrivals), so the longest gaps say what the
  host was doing meanwhile.  Only the ``top_gaps`` longest are labelled.
* Ops are keyed by their HLO instruction name (the trace names each by
  its whole HLO text).  Kernel time is the summed device duration of the
  ops whose HLO text, or one of whose string stats, contains a kernel's
  name (the registry entry, e.g. ``fused_routing``).
"""

from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"


@dataclasses.dataclass
class Reduction:
    window_s: float               # traced window
    busy_s: float                 # union of device-op time, mean over chips
    n_chips: int
    ops: Dict[str, float]         # device op name -> summed seconds
    op_counts: Dict[str, int]
    gaps: List[Tuple[str, float]]  # (host label, seconds): the longest
    n_spans: int
    op_text: Dict[str, str] = dataclasses.field(default_factory=dict)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s if self.window_s > 0 else 0.0

    def kernel(self, body: str) -> Tuple[float, int]:
        """Summed device seconds and event count of ops naming ``body``."""
        hit = [k for k in self.ops
               if body in k or body in self.op_text.get(k, "")]
        return (sum(self.ops[k] for k in hit),
                sum(self.op_counts[k] for k in hit))

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.ops.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in self.gaps[:top]]}


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merge intervals into disjoint, sorted ones."""
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """Stretches of [lo, hi) not covered by the disjoint sorted ``busy``."""
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if t < hi:
        out.append((t, hi))
    return out


def _events(line):
    for e in line.events:
        yield e.name, float(e.start_ns), float(e.start_ns + e.duration_ns)


def short(name: str) -> str:
    """An op's HLO instruction name: the TPU trace names each op by its
    whole HLO text, ``%fusion.7 = f32[...] fusion(...)``."""
    return name.split(" = ", 1)[0]


def _text(event) -> str:
    """The string stats of an event (long name, HLO op), joined."""
    return " ".join(str(v) for _, v in getattr(event, "stats", ())
                    if isinstance(v, str))


def reduce_planes(planes, span: str = "bench.tick",
                  top_gaps: int = 10) -> Reduction:
    """Reduce planes shaped like ``ProfileData.planes`` (objects with
    ``name`` and ``lines``; lines with ``name`` and ``events``; events
    with ``name``, ``start_ns`` and ``duration_ns``)."""
    host = [p for p in planes if p.name == HOST_PLANE]
    host_events = [ev for p in host for ln in p.lines for ev in _events(ln)]
    spans = [(a, b) for n, a, b in host_events if n == span]
    if not spans:
        raise ValueError(f"no host span {span!r} in the trace")
    lo, hi = min(a for a, _ in spans), max(b for _, b in spans)
    devices = [p for p in planes if p.name.startswith(DEVICE_PREFIX)]
    ops: Dict[str, float] = {}
    counts: Dict[str, int] = {}
    busy_total = 0.0
    merged_first: Optional[List[Interval]] = None
    text: Dict[str, str] = {}
    for p in devices:
        lines = [ln for ln in p.lines if ln.name == OPS_LINE]
        evs = [ev for ln in lines for ev in _events(ln)]
        for ln in lines:
            for e in ln.events:
                k = short(e.name)
                if k not in text:
                    text[k] = e.name + " " + _text(e)
        for n, a, b in evs:
            if b > lo and a < hi:
                k = short(n)
                ops[k] = ops.get(k, 0.0) + (min(b, hi) - max(a, lo)) * 1e-9
                counts[k] = counts.get(k, 0) + 1
        merged = union(clip([(a, b) for _, a, b in evs], lo, hi))
        busy_total += sum(b - a for a, b in merged)
        if merged_first is None:
            merged_first = merged
    n_chips = len(devices)
    idle = sorted(gaps(merged_first or [], lo, hi), key=lambda g: g[0] - g[1])
    labelled = []
    for a, b in idle[:top_gaps]:
        mid = 0.5 * (a + b)
        around = [(e - s, n) for n, s, e in host_events
                  if s <= mid < e and e > s]
        label = min(around)[1] if around else "between ticks"
        labelled.append((label, (b - a) * 1e-9))
    return Reduction(window_s=(hi - lo) * 1e-9,
                     busy_s=busy_total * 1e-9 / max(n_chips, 1),
                     n_chips=n_chips, ops=ops, op_counts=counts,
                     gaps=labelled, n_spans=len(spans), op_text=text)


def reduce_trace(trace_dir: str, span: str = "bench.tick") -> Reduction:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(find_xplane(trace_dir))
    return reduce_planes(list(pd.planes), span)
