#!/usr/bin/env python3
"""Device time and device idle attributed to what the program was doing,
from a JAX profiler trace (``.xplane.pb``) and the program's own compiled
text, with no clock but the trace's.

* ``idle_by_span``: every idle stretch of the window on chip 0, totalled
  per host thread by the deepest program span open on that thread over it
  (``"no span"`` for the rest).  The program mirrors its obs spans onto
  the trace's host plane as profiler annotations, one line per thread.
* ``scope_ns``: device time of the operations a named scope holds
  (``jax.named_scope``).  A TPU trace names an operation by its HLO
  instruction and carries no op metadata, so the scope is found in the
  ``metadata={op_name=...}`` of the program's compiled text
  (``program_ops``, ``scope_ops``), and each traced program is matched
  to the compiled variant whose instructions it ran.

Run as a script, it prints the ``idle_by_span`` and ``span_totals`` of
the last traced run of a cell (``chipbench/run.py --trace 1`` keeps its
trace under ``.cache/trace/``)::

    python3 chipbench/attribution.py --workload clustered-20d-linf.search
"""
from __future__ import annotations

import re
import sys
from pathlib import Path

if __name__ == "__main__":
    ROOT = Path(__file__).resolve().parents[1]
    sys.path[:] = [p for p in sys.path if Path(p or ".").resolve()
                   != ROOT / "chipbench"]
    sys.path.insert(0, str(ROOT))

from chipbench import trace  # noqa: E402

NO_SPAN = "no span"
# obs span names: dotted lower-case words ("frontend.cohort").  The
# runtime's own host events are CamelCase or carry "::", HLO operations
# run on the host end in a number ("fusion.12"), and the benchmark's own
# marks start with "chipbench."
SPAN_NAME = re.compile(r"^(?!chipbench\.)[a-z][a-z0-9_]*(\.[a-z][a-z0-9_]*)+$")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([^\s(]+)\s.*\{\s*$")
_INSTR = re.compile(r"^\s+(?:ROOT\s+)?%?([^\s=]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bfusion\(.*calls=%?([^\s,]+)")


def _host_threads(path, start_ns: float, end_ns: float) -> dict:
    """Program spans on each host thread, clipped to the window:
    ``{label: [(start, end, name), ...]}``.  A line is named by the
    thread's name and its outermost span names (threads of one process
    share a name)."""
    out = {}
    for plane in trace._planes(path):
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            spans = []
            for ev in line.events:
                a = float(ev.start_ns)
                b = a + float(ev.duration_ns)
                if b > a and b > start_ns and a < end_ns \
                        and SPAN_NAME.match(ev.name):
                    spans.append((a, b, ev.name))
            if not spans:
                continue
            spans.sort(key=lambda s: (s[0], -s[1]))
            outer, reach = set(), -1.0
            for a, b, name in spans:
                if a >= reach:
                    outer.add(name)
                reach = max(reach, b)
            label = f"{line.name}[{','.join(sorted(outer))}]"
            n, base = 2, label
            while label in out:
                label, n = f"{base}#{n}", n + 1
            out[label] = spans
    return out


def innermost(spans) -> list:
    """Disjoint ``(start, end, name)`` stretches, each named by the
    deepest of ``spans`` open over it; the spans of one thread nest.
    ``spans`` is sorted by start, the longer first at a tie."""
    out, stack, t = [], [], None

    def upto(x):
        nonlocal t
        while stack and stack[-1][0] <= x:
            end, name = stack.pop()
            if end > t:
                out.append((t, end, name))
                t = end
        if stack and x > t:
            out.append((t, x, stack[-1][1]))
        t = max(t, x)

    for a, b, name in spans:
        if t is None:
            t = a
        upto(a)
        stack.append((b, name))
    if stack:
        upto(max(end for end, _ in stack))
    return out


def _overlap(gaps, segments) -> dict:
    """Seconds of ``gaps`` under each segment's name, the rest under
    ``NO_SPAN``; both lists sorted and disjoint."""
    tot, j = {}, 0
    for a, b in gaps:
        covered = 0.0
        while j < len(segments) and segments[j][1] <= a:
            j += 1
        k = j
        while k < len(segments) and segments[k][0] < b:
            s, e, name = segments[k]
            d = min(b, e) - max(a, s)
            if d > 0:
                tot[name] = tot.get(name, 0.0) + d / 1e9
                covered += d
            k += 1
        if b - a > covered:
            tot[NO_SPAN] = tot.get(NO_SPAN, 0.0) + (b - a - covered) / 1e9
    return dict(sorted(tot.items(), key=lambda kv: -kv[1]))


def idle_by_span(path, red) -> dict:
    """``{thread: {span: idle s}}`` over ``red``'s window (a
    ``trace.reduce`` of the same trace): each thread's values sum to the
    window's idle time on chip 0.  Threads that opened no program span
    are left out."""
    threads = _host_threads(path, red.start_ns, red.end_ns)
    return {label: _overlap(red.gaps, innermost(spans))
            for label, spans in threads.items()}


def span_totals(path, red) -> dict:
    """``{span: [count, s]}`` of the program spans that start in ``red``'s
    window, over every thread: where the host's time went."""
    out = {}
    for spans in _host_threads(path, red.start_ns, red.end_ns).values():
        for a, b, name in spans:
            if a >= red.start_ns:
                c = out.setdefault(name, [0, 0.0])
                c[0] += 1
                c[1] += (b - a) / 1e9
    return dict(sorted(out.items()))


# ------------------------------------------------------------ named scopes
def program_ops(hlo_text: str) -> dict:
    """``{instruction: op_name}`` of the instructions a compiled program
    runs as operations of its own: those outside fused computations
    (which run inside their fusion's operation)."""
    fused = set(_CALLS.findall(hlo_text))
    out, comp = {}, None
    for ln in hlo_text.splitlines():
        if ln and not ln[0].isspace():
            m = _COMPUTATION.match(ln)
            comp = m.group(1) if m else None
            continue
        m = _INSTR.match(ln)
        if m and comp is not None and comp not in fused:
            op = _OP_NAME.search(ln)
            out[m.group(1)] = op.group(1) if op else ""
    return out


def scope_ops(ops: dict, scope: str) -> set:
    """The instructions of ``program_ops`` whose op_name runs through
    ``scope``."""
    return {name for name, op in ops.items() if scope in op.split("/")}


def scope_ns(path, start_ns: float, seconds: float, program: str,
             variants) -> float:
    """Device time (ns, the union of their intervals, mean over chips) of
    the operations under a scope, in the window.  ``variants`` holds one
    ``(program_ops, scope's instructions)`` pair for each compiled variant
    of the programs whose trace name holds ``program``.  Each traced
    program takes the variant that names most of the operations it ran,
    the one with fewer instructions at a tie (instruction names are
    numbered, so a larger variant's names hold a smaller one's)."""
    end_ns = start_ns + seconds * 1e9
    per_chip = []
    for plane in trace._planes(path):
        if not (plane.name.startswith("/device:TPU:")
                and plane.name[len("/device:TPU:"):].isdigit()):
            continue
        lines = {ln.name: ln for ln in plane.lines}
        if trace.OPS_LINE not in lines or trace.MODULES_LINE not in lines:
            continue
        mods = sorted((float(e.start_ns), float(e.start_ns + e.duration_ns),
                       e.name) for e in lines[trace.MODULES_LINE].events
                      if program in e.name)
        ops = sorted((float(e.start_ns), float(e.start_ns + e.duration_ns),
                      trace.short_name(e.name))
                     for e in lines[trace.OPS_LINE].events)
        by_mod, j = {}, 0
        for ma, mb, mname in mods:
            while j < len(ops) and ops[j][0] < ma:
                j += 1
            k = j
            while k < len(ops) and ops[k][0] < mb:
                by_mod.setdefault(mname, []).append(ops[k])
                k += 1
        iv = []
        for run_ops in by_mod.values():
            seen = {n for _, _, n in run_ops}
            ops_of, want = max(variants, key=lambda v: (
                len(seen & v[0].keys()), -len(v[0])))
            if seen & ops_of.keys():
                iv += [(max(a, start_ns), min(b, end_ns))
                       for a, b, n in run_ops
                       if n in want and min(b, end_ns) > max(a, start_ns)]
        per_chip.append(sum(b - a for a, b in trace._union(iv)))
    return sum(per_chip) / len(per_chip) if per_chip else 0.0


def descent_texts(tree, *, rows: int, k: int, max_frontier: int) -> list:
    """Compiled text of the cohort descent (both level-stats variants) at
    a cohort's shapes, as ``smtree.knn`` dispatches it."""
    import jax
    import jax.numpy as jnp
    from repro.core import smtree
    out = []
    for level_stats in (False, True):
        low = smtree._knn_cohort.lower(
            tree, jnp.zeros((rows, tree.dim), jnp.float32),
            jnp.float32(smtree._INF), k=k, F=max_frontier,
            height=int(tree.height), impl=smtree._resolve_impl(None),
            interpret=jax.default_backend() != "tpu",
            level_stats=level_stats,
            prune=smtree._resolve_parent_prune(None))
        out.append(low.compile().as_text())
    return out


def main(argv=None) -> int:
    import argparse
    import json
    from chipbench import harness
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float,
                    help="the window (default: BENCHMARK.json run_seconds)")
    args = ap.parse_args(argv)
    seconds = args.seconds or harness.load_json(
        harness.ROOT / "BENCHMARK.json")["run_seconds"]
    path = next((harness.CACHE / "trace" / args.workload).rglob(
        "*.xplane.pb"), None)
    t0 = path and trace.find_mark(path, harness._Profiler.MARK)
    if t0 is None:
        print(f"no traced run of {args.workload}", file=sys.stderr)
        return 2
    red = trace.reduce(path, t0, seconds)
    print(json.dumps({"idle_s": sum(b - a for a, b in red.gaps) / 1e9,
                      "idle_by_span": idle_by_span(path, red),
                      "span_totals": span_totals(path, red)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
