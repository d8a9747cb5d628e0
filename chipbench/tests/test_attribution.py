"""Idle time and device time attributed to program spans and named
scopes (``chipbench/attribution.py``), and the per-layer readers that
read the program's new spans and counters, on the synthetic values of
``attribution.json``, the TPU trace ``small.xplane.pb``, and a trace the
test records on the CPU."""
import json
import threading
import types

import pytest

from chipbench import attribution, harness, trace
from conftest import DATA

FX = json.loads((DATA / "attribution.json").read_text())
PB = DATA / "small.xplane.pb"
SPANS = json.loads((DATA / "small.spans.json").read_text())


@pytest.fixture(scope="module")
def red():
    t0_ns = trace.find_mark(PB, "chipbench.window_start")
    return trace.reduce(PB, t0_ns, SPANS["t1"] - SPANS["t0"])


def test_innermost_span_and_idle_by_it():
    fx = FX["idle"]
    spans = [tuple(s) for s in fx["spans"]]
    segs = attribution.innermost(spans)
    assert [list(s) for s in segs] == fx["innermost"]
    got = attribution._overlap([tuple(g) for g in fx["gaps"]], segs)
    assert got == pytest.approx(fx["expect"])
    assert sum(got.values()) == pytest.approx(
        sum(b - a for a, b in fx["gaps"]) / 1e9)


def test_idle_by_span_on_a_recorded_trace(tmp_path):
    """Spans on two threads of a CPU trace; one idle stretch over the
    whole window is split by each thread's deepest span.  The threads
    live at once: a thread started after another has ended may reuse its
    id, and with it its line."""
    import jax
    from jax.profiler import TraceAnnotation
    both = threading.Barrier(2)

    def work(outer, inner):
        both.wait()
        with TraceAnnotation(outer):
            with TraceAnnotation(inner):
                jax.block_until_ready(jax.numpy.ones(4) + 1)
        both.wait()

    with jax.profiler.trace(str(tmp_path)):
        with TraceAnnotation("chipbench.window_start"):
            pass
        threads = [threading.Thread(target=work, args=names)
                   for names in (("test.outer", "test.inner"),
                                 ("test.writer", "test.write_step"))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        with TraceAnnotation("chipbench.window_end"):
            pass
    (pb,) = tmp_path.rglob("*.xplane.pb")
    a = trace.find_mark(pb, "chipbench.window_start")
    b = trace.find_mark(pb, "chipbench.window_end")
    window = trace.Reduced(a, b, gaps=[[a, b]])
    got = attribution.idle_by_span(pb, window)
    (outer,) = [v for k, v in got.items() if k.endswith("[test.outer]")]
    (writer,) = [v for k, v in got.items() if k.endswith("[test.writer]")]
    for per_thread in got.values():
        assert sum(per_thread.values()) == pytest.approx((b - a) / 1e9)
        assert not any(k.startswith("chipbench.") for k in per_thread)
    assert set(outer) == {"test.outer", "test.inner", "no span"}
    assert set(writer) == {"test.writer", "test.write_step", "no span"}
    totals = attribution.span_totals(pb, window)
    assert totals["test.inner"][0] == 1
    assert 0 < totals["test.inner"][1] <= totals["test.outer"][1]


def test_program_ops_leave_out_fused_instructions():
    assert attribution.program_ops(FX["hlo"]["a"]) == FX["hlo"]["a_ops"]
    ops_b = attribution.program_ops(FX["hlo"]["b"])
    assert attribution.scope_ops(ops_b, "descent.compact") == {
        "add_reduce_fusion"}
    assert attribution.scope_ops(ops_b, "descent") == set()


def test_scope_time_matches_each_program_to_its_variant(red):
    hlo = FX["hlo"]
    va, vb = (attribution.program_ops(hlo[k]) for k in ("a", "b"))
    variants = [(v, attribution.scope_ops(v, "descent.compact"))
                for v in (va, vb)]
    secs = SPANS["t1"] - SPANS["t0"]
    assert attribution.scope_ns(PB, red.start_ns, secs, "jit__lambda",
                                variants[:1]) == hlo["scope_ns_a"]
    assert attribution.scope_ns(PB, red.start_ns, secs, "jit__lambda",
                                variants) == hlo["scope_ns_ab"]
    assert attribution.scope_ns(PB, red.start_ns, secs, "_knn_cohort",
                                variants) == 0.0


def test_descent_texts_carry_the_compact_scope():
    import numpy as np
    from repro.core.smtree import bulk_build
    X = np.random.default_rng(0).random((300, 6)).astype(np.float32)
    texts = attribution.descent_texts(bulk_build(X, capacity=8), rows=8,
                                      k=3, max_frontier=64)
    assert len(texts) == 2
    for t in texts:
        assert attribution.scope_ops(attribution.program_ops(t),
                                     "descent.compact")


@pytest.mark.parametrize("name", sorted(FX["run"]["expect"]))
def test_span_and_counter_readers(name):
    fx = FX["run"]
    reader = harness.load_module("metrics", name)
    run = types.SimpleNamespace(spans=fx["spans"], counters=fx["counters"])
    assert reader.read(run) == pytest.approx(fx["expect"][name])
    # a program without the spans and counters: no reading
    assert reader.read(types.SimpleNamespace(spans=[], counters={})) is None


def _compact_reader(monkeypatch, cell):
    monkeypatch.setattr(harness, "cell_spec",
                        lambda *a, **kw: (cell.wl, cell.cfg, cell.traffic))
    return harness.load_module("metrics",
                               "descent.compact_device_us_per_query")


def test_compact_reader(red, monkeypatch, tiny_cells):
    hlo = FX["hlo"]
    reader = _compact_reader(monkeypatch, tiny_cells("tiny.search"))
    monkeypatch.setattr(reader, "PROGRAM", "jit__lambda")
    monkeypatch.setattr(attribution, "descent_texts",
                        lambda tree, **kw: [hlo["a"], hlo["b"]])
    rec = types.SimpleNamespace(
        workload="tiny.search", trace_path=PB,
        seconds=SPANS["t1"] - SPANS["t0"])
    run = types.SimpleNamespace(trace=red, rec=rec,
                                answered=hlo["answered"])
    assert reader.read(run) == pytest.approx(hlo["compact_us_per_query"])
    # a program whose descent has no such scope: no reading
    monkeypatch.setattr(attribution, "descent_texts",
                        lambda tree, **kw: [hlo["a"].replace(
                            "descent.compact", "x")])
    assert reader.read(run) is None


def test_compact_reader_on_a_checked_window(monkeypatch, tiny_cells):
    """After the checks (which free the window's engine), the reader
    compiles the cell's descent and hands both variants' compaction ops
    to the trace reduction."""
    from chipbench import check
    cell = tiny_cells("tiny.search")
    rec = cell.window(5, 0.5)
    check.numbers(cell, rec)
    reader = _compact_reader(monkeypatch, cell)
    seen = []

    def scope_ns(path, start_ns, seconds, program, variants):
        seen.extend(variants)
        return 2e6

    monkeypatch.setattr(attribution, "scope_ns", scope_ns)
    run = types.SimpleNamespace(rec=rec, answered=4,
                                trace=trace.Reduced(0.0, 1e9, n_chips=1))
    assert reader.read(run) == pytest.approx(2e6 / 1e3 / 4)
    assert len(seen) == 2 and all(want for _, want in seen)
