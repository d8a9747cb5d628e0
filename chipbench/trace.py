"""Reduction of a JAX profiler trace (``.xplane.pb``) to what the metric
readers read: device busy time as the union of operation intervals, device
time by operation and by program, and the idle gaps with the host span
that overlaps each.

Device planes are the ``/device:TPU:<n>`` planes.  On each, the
``XLA Ops`` line holds one event per operation run (named by its HLO
instruction, e.g. ``frontier_scores_pallas.11`` for a Pallas kernel) and
the ``XLA Modules`` line one event per program run (``jit__knn_cohort(...)``).
Device and host events share the trace's clock.  Everything is clipped to
the window.
"""
from __future__ import annotations

import collections
import dataclasses

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def _planes(path):
    from jax.profiler import ProfileData
    return ProfileData.from_file(str(path)).planes


def find_mark(path, name: str) -> float | None:
    """Trace-clock start (ns) of the first host event called ``name``."""
    for plane in _planes(path):
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == name:
                        return float(ev.start_ns)
    return None


def short_name(name: str) -> str:
    """An op event is named by its whole HLO line ("%fusion.3 = f32[...]
    fusion(...), ..."): keep the instruction's name ("fusion.3")."""
    head = name.split(" = ", 1)[0]
    return head[1:] if head.startswith("%") else head


def _union(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


@dataclasses.dataclass
class Reduced:
    """One window of a trace, on the trace's clock (ns)."""
    start_ns: float
    end_ns: float
    n_chips: int = 0
    busy_ns: float = 0.0          # union of op intervals, mean over chips
    ops: dict = dataclasses.field(default_factory=dict)       # name -> ns
    modules: dict = dataclasses.field(default_factory=dict)   # name -> ns
    gaps: list = dataclasses.field(default_factory=list)      # [a, b] chip 0

    @property
    def window_ns(self) -> float:
        return self.end_ns - self.start_ns

    def op_ns(self, *needles: str) -> float:
        return sum(v for k, v in self.ops.items()
                   if any(n in k for n in needles))

    def module_ns(self, *needles: str) -> float:
        return sum(v for k, v in self.modules.items()
                   if any(n in k for n in needles))


def reduce(path, start_ns: float, seconds: float) -> Reduced:
    end_ns = start_ns + seconds * 1e9
    red = Reduced(start_ns, end_ns)
    ops = collections.Counter()
    modules = collections.Counter()
    busy = []
    for plane in _planes(path):
        if not (plane.name.startswith("/device:TPU:")
                and plane.name[len("/device:TPU:"):].isdigit()):
            continue
        lines = {ln.name: ln for ln in plane.lines}
        red.n_chips += 1
        intervals = []
        for line_name, counter in ((OPS_LINE, ops), (MODULES_LINE, modules)):
            line = lines.get(line_name)
            if line is None:
                continue
            for ev in line.events:
                a = max(float(ev.start_ns), start_ns)
                b = min(float(ev.start_ns) + float(ev.duration_ns), end_ns)
                if b <= a:
                    continue
                counter[short_name(ev.name)] += b - a
                if line_name == OPS_LINE:
                    intervals.append((a, b))
        u = _union(intervals)
        busy.append(sum(b - a for a, b in u))
        if red.n_chips == 1:
            edges = [start_ns] + [x for ab in u for x in ab] + [end_ns]
            red.gaps = [[edges[i], edges[i + 1]]
                        for i in range(0, len(edges), 2)
                        if edges[i + 1] > edges[i]]
    red.ops, red.modules = dict(ops), dict(modules)
    red.busy_ns = sum(busy) / len(busy) if busy else 0.0
    return red


def _depths(spans: list[dict]) -> list[int]:
    parent = {s.get("span_id"): s.get("parent_id") for s in spans}
    out = []
    for s in spans:
        d, p = 0, s.get("parent_id")
        while p is not None and p in parent and d < 64:
            d, p = d + 1, parent[p]
        out.append(d)
    return out


def name_gaps(red: Reduced, spans: list[dict], t0_mono: float,
              top: int = 10) -> list[list]:
    """The ``top`` longest idle gaps, each named by what the host was doing
    in it: the deepest span that covers at least half of the gap (a ticket
    span covers its whole wait, the cohort's child spans say more), else
    the span covering most of it, or "no span"."""
    def mono(ns):
        return t0_mono + (ns - red.start_ns) / 1e9

    depth = _depths(spans)
    out = []
    for a, b in sorted(red.gaps, key=lambda g: g[0] - g[1])[:top]:
        ma, mb = mono(a), mono(b)
        best, key = "no span", None
        for s, d in zip(spans, depth):
            if s.get("t_end") is None:
                continue
            cover = min(mb, s["t_end"]) - max(ma, s["t_start"])
            if cover <= 0:
                continue
            k = (cover >= 0.5 * (mb - ma), d, cover,
                 -(s["t_end"] - s["t_start"]))
            if key is None or k > key:
                best, key = s["name"], k
        out.append([best, (b - a) / 1e9])
    return out


def top_ops(red: Reduced, top: int = 10) -> list[list]:
    """The device operations that took most time in the window, in s."""
    return [[k, v / 1e9] for k, v in
            sorted(red.ops.items(), key=lambda kv: -kv[1])[:top]]
