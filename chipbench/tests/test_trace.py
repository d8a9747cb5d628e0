"""The trace reducer on a small trace recorded on a TPU v5e: a jitted
matmul and an elementwise program, three times each, with a 20 ms host
sleep (``small.spans.json``, on ``time.monotonic``) between them."""
import json

import pytest

from chipbench import trace
from conftest import DATA

PB = DATA / "small.xplane.pb"
SPANS = json.loads((DATA / "small.spans.json").read_text())


@pytest.fixture(scope="module")
def red():
    t0_ns = trace.find_mark(PB, "chipbench.window_start")
    assert t0_ns is not None
    return trace.reduce(PB, t0_ns, SPANS["t1"] - SPANS["t0"])


def test_busy_is_the_union_of_op_intervals(red):
    from jax.profiler import ProfileData
    plane = next(p for p in ProfileData.from_file(str(PB)).planes
                 if p.name == "/device:TPU:0")
    ops = next(ln for ln in plane.lines if ln.name == "XLA Ops")
    iv = sorted((max(e.start_ns, red.start_ns),
                 min(e.start_ns + e.duration_ns, red.end_ns))
                for e in ops.events)
    busy, end = 0.0, red.start_ns
    for a, b in iv:
        if b > max(a, end):
            busy += b - max(a, end)
            end = b
    assert red.n_chips == 1
    assert red.busy_ns == pytest.approx(busy)
    assert 0 < red.busy_ns < red.window_ns
    # idle gaps and busy time tile the window
    gaps = sum(b - a for a, b in red.gaps)
    assert gaps + red.busy_ns == pytest.approx(red.window_ns)


def test_events_by_name(red):
    assert set(red.ops) == {"fusion", "add_reduce_fusion", "copy-start",
                            "copy-done"}
    assert len(red.modules) == 2
    assert all(m.startswith("jit__lambda(") for m in red.modules)
    assert red.module_ns("jit__lambda") == pytest.approx(
        sum(red.modules.values()))
    assert sum(red.ops.values()) >= red.busy_ns
    assert trace.top_ops(red, 2)[0][1] == max(red.ops.values()) / 1e9


def test_idle_gaps_named_by_overlapping_span(red):
    named = trace.name_gaps(red, SPANS["spans"], SPANS["t0"], top=3)
    assert [n for n, _ in named] == ["test.sleep"] * 3
    assert all(0.019 < s < 0.025 for _, s in named)
    assert trace.name_gaps(red, [], SPANS["t0"], top=1)[0][0] == "no span"


def test_short_name():
    assert trace.short_name("%fusion.3 = f32[] fusion(%x), kind=kLoop") \
        == "fusion.3"
    assert trace.short_name("jit_f(123)") == "jit_f(123)"


def test_gap_named_by_deepest_covering_span(red):
    """A root span covering the whole window loses to a child that covers
    the gap."""
    (a, b), = sorted(red.gaps, key=lambda g: g[0] - g[1])[:1]
    mono = lambda ns: SPANS["t0"] + (ns - red.start_ns) / 1e9  # noqa: E731
    spans = [{"name": "root", "span_id": "r", "parent_id": None,
              "t_start": SPANS["t0"] - 1, "t_end": SPANS["t1"] + 1},
             {"name": "child", "span_id": "c", "parent_id": "r",
              "t_start": mono(a) - 0.001, "t_end": mono(b) + 0.001}]
    assert trace.name_gaps(red, spans, SPANS["t0"], top=1)[0][0] == "child"
