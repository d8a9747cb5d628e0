"""Observability plane unit tests: registry instruments (bounded
histograms, percentile estimates, snapshot flattening), trace spans
(thread-local parenting, connectedness), the flight-recorder ring
(bounded, JSON dumps), the disabled-path no-op contract, and the
planted-``FencedOut`` dump trigger through the real WAL fence hook."""
import json
import threading

import numpy as np
import pytest

from repro import obs
from repro.obs.export import metrics_snapshot, missing_rows
from repro.obs.recorder import FlightRecorder
from repro.obs.registry import Histogram
from repro.stream.wal import FencedOut, WriteAheadLog


@pytest.fixture
def obs_on(monkeypatch, tmp_path):
    """Enabled plane with a clean slate; dumps land in tmp_path."""
    monkeypatch.setenv("REPRO_OBS_DUMP_DIR", str(tmp_path))
    obs.reset()
    obs.enable()
    obs.set_trace_sampling(1)        # trace every root: tests need them all
    yield
    obs.disable()
    obs.set_trace_sampling(obs.TRACE_SAMPLE_EVERY)
    obs.reset()


# ----------------------------------------------------------------- registry

def test_counter_gauge_roundtrip(obs_on):
    obs.counter("t.hits_total").inc()
    obs.counter("t.hits_total").inc(4)
    obs.gauge("t.depth").set(7.0)
    snap = obs.REGISTRY.snapshot()
    assert snap["t.hits_total"] == 5
    assert snap["t.depth"] == 7.0


def test_histogram_percentiles_and_bounds(obs_on):
    h = obs.histogram("t.lat_s", buckets=(1.0, 2.0, 4.0, 8.0))
    h.observe_many([0.5] * 50 + [3.0] * 45 + [100.0] * 5)
    assert h.count == 100
    # p50 lands in the first bucket (<=1.0), p95 in (2,4], p99 overflows
    # to the exact observed max
    assert h.percentile(50) == 1.0
    assert h.percentile(95) == 4.0
    assert h.percentile(99) == 100.0
    full = h.full_snapshot()
    assert full["min"] == 0.5 and full["max"] == 100.0
    # bounded memory: bucket table only, never a sample list
    assert len(h._counts) == 5


def test_histogram_single_sample_clamps_to_max(obs_on):
    h = Histogram("t.one", buckets=(1e-3, 1.0, 1000.0))
    h.observe(2.5)     # alone in the huge (1, 1000] bucket
    assert h.percentile(50) == 2.5   # clamped, not the 1000.0 ceiling


def test_histogram_rejects_bad_buckets():
    with pytest.raises(ValueError):
        Histogram("t.bad", buckets=(2.0, 1.0))
    with pytest.raises(ValueError):
        Histogram("t.empty", buckets=())


def test_registry_kind_mismatch(obs_on):
    obs.counter("t.kind")
    with pytest.raises(TypeError):
        obs.gauge("t.kind")


def test_registry_snapshot_flattens_histograms(obs_on):
    obs.histogram("t.h", buckets=(1.0, 2.0)).observe(0.5)
    snap = obs.REGISTRY.snapshot()
    for k in ("t.h.count", "t.h.sum", "t.h.p50", "t.h.p95", "t.h.p99"):
        assert k in snap
    assert snap["t.h.count"] == 1


def test_counters_are_thread_safe(obs_on):
    c = obs.counter("t.mt_total")

    def work():
        for _ in range(10_000):
            c.inc()

    ts = [threading.Thread(target=work) for _ in range(4)]
    [t.start() for t in ts]
    [t.join() for t in ts]
    assert c.value == 40_000


# -------------------------------------------------------------------- trace

def test_span_nesting_and_connectedness(obs_on):
    with obs.span("root") as root:
        tid = root.trace_id
        with obs.span("child"):
            # thread-local parenting: no explicit ctx plumbing
            with obs.span("grandchild"):
                pass
        s = obs.start_span("sibling", parent=root.ctx)
        s.end()
    records = obs.RECORDER.records()
    spans = obs.assemble_trace(records, tid)
    assert sorted(x["name"] for x in spans) == [
        "child", "grandchild", "root", "sibling"]
    assert obs.trace_connected(records, tid)
    by_name = {x["name"]: x for x in spans}
    assert by_name["child"]["parent_id"] == by_name["root"]["span_id"]
    assert by_name["grandchild"]["parent_id"] == by_name["child"]["span_id"]
    assert all(x["duration_s"] >= 0.0 for x in spans)


def test_cohort_fan_in_via_links(obs_on):
    a = obs.start_span("frontend.query")     # two independent tickets
    b = obs.start_span("frontend.query", parent=None, trace_id=None)
    cohort = obs.start_span("frontend.cohort", parent=a.ctx,
                            links=(b.trace_id,))
    comp = obs.start_span("frontend.device_compute", parent=cohort.ctx)
    comp.end()
    cohort.end()
    a.end()
    b.end()
    records = obs.RECORDER.records()
    # the primary ticket owns the cohort subtree; the linked ticket still
    # reaches the shared cohort span through its link
    got_a = {s["name"] for s in obs.assemble_trace(records, a.trace_id)}
    assert {"frontend.query", "frontend.cohort",
            "frontend.device_compute"} <= got_a
    got_b = {s["name"] for s in obs.assemble_trace(records, b.trace_id)}
    assert {"frontend.query", "frontend.cohort"} <= got_b
    assert obs.trace_connected(records, a.trace_id)
    assert obs.trace_connected(records, b.trace_id)


def test_span_error_attr_on_exception(obs_on):
    with pytest.raises(RuntimeError):
        with obs.span("boom"):
            raise RuntimeError("x")
    (rec,) = obs.RECORDER.spans()
    assert rec["attrs"]["error"] == "RuntimeError"


def test_head_sampling_thins_roots_not_children(obs_on):
    obs.set_trace_sampling(4)
    roots = [obs.start_span("ticket", sampled=True) for _ in range(16)]
    real = [s for s in roots if s is not obs.NULL_SPAN]
    assert len(real) == 4                     # 1 in 4, counter-aligned
    # a child of a traced root is always real, never re-sampled
    child = obs.start_span("child", parent=real[0].ctx, sampled=True)
    assert child is not obs.NULL_SPAN
    assert child.trace_id == real[0].trace_id
    # unsampled roots (no sampled=True) are unaffected by the rate
    assert obs.start_span("mutation") is not obs.NULL_SPAN
    obs.set_trace_sampling(1)
    assert obs.start_span("ticket", sampled=True) is not obs.NULL_SPAN


# ----------------------------------------------------------------- recorder

def test_ring_is_bounded():
    rec = FlightRecorder(capacity=8)
    for i in range(100):
        rec.record_event("e", i=i)
    records = rec.records()
    assert len(records) == 8
    assert [r["attrs"]["i"] for r in records] == list(range(92, 100))
    assert rec.stats()["n_events"] == 100


def test_dump_roundtrip(obs_on, tmp_path):
    obs.record_event("lease.acquired", holder="n0", token=3)
    with obs.span("mutation.apply", n=4):
        pass
    path = obs.RECORDER.dump(reason="manual", metrics=obs.REGISTRY.snapshot())
    with open(path) as f:
        doc = json.load(f)
    assert doc["reason"] == "manual"
    kinds = {r["kind"] for r in doc["records"]}
    assert kinds == {"event", "span"}
    assert obs.RECORDER.last_dump_path == path


def test_record_fault_attaches_exception_and_metrics(obs_on):
    obs.counter("t.pre_total").inc(2)
    path = obs.record_fault("transport.ship_stall",
                            ConnectionError("pump died"), rounds=7)
    with open(path) as f:
        doc = json.load(f)
    assert doc["reason"] == "transport.ship_stall"
    ev = [r for r in doc["records"] if r["kind"] == "event"][-1]
    assert ev["attrs"]["exc_type"] == "ConnectionError"
    assert ev["attrs"]["rounds"] == 7
    assert doc["metrics"]["t.pre_total"] == 2


def test_planted_fenced_out_dumps(obs_on, tmp_path):
    """The real WAL fence hook: a fenced append must raise AND leave a
    flight-recorder dump behind (reason wal.fenced_out)."""
    def fence():
        raise FencedOut("planted: higher token exists")

    wal = WriteAheadLog(str(tmp_path / "wal"))
    ops = np.zeros(2, np.int8)
    xs = np.zeros((2, 3), np.float32)
    oids = np.arange(2, dtype=np.int32)
    wal.append_batch(ops, xs, oids)          # healthy append first
    wal.fence = fence
    with pytest.raises(FencedOut):
        wal.append_batch(ops, xs, oids)
    wal.close()
    path = obs.RECORDER.last_dump_path
    assert path is not None and "wal.fenced_out" in path
    with open(path) as f:
        doc = json.load(f)
    assert doc["reason"] == "wal.fenced_out"
    names = [r["name"] for r in doc["records"] if r["kind"] == "event"]
    assert "wal.fenced_out" in names
    # the healthy append's counters rode into the attached snapshot
    assert doc["metrics"]["wal.appends_total"] == 1


# ------------------------------------------------------------ disabled path

def test_disabled_everything_is_noop(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_OBS_DUMP_DIR", str(tmp_path))
    obs.reset()
    obs.disable()
    obs.counter("t.off_total").inc(5)
    obs.gauge("t.off").set(1.0)
    obs.histogram("t.off_s").observe(0.1)
    assert obs.counter("t.off_total").value == 0
    assert obs.histogram("t.off_s").count == 0
    s = obs.start_span("t.span")
    assert s is obs.NULL_SPAN and s.ctx is None
    with obs.span("t.cm") as inner:
        assert inner is obs.NULL_SPAN
    obs.record_event("t.event")
    assert obs.record_fault("t.fault", RuntimeError("x")) is None
    assert obs.RECORDER.records() == []
    assert obs.RECORDER.last_dump_path is None


def test_direct_instruments_are_always_on():
    """FrontendStats latency lives on a directly-constructed histogram:
    it must keep observing with the plane off (the bench gate reads its
    percentiles)."""
    obs.disable()
    h = Histogram("standalone", buckets=(1.0, 2.0))
    h.observe(0.5)
    assert h.count == 1


# ------------------------------------------------------------------- export

def test_metrics_snapshot_and_missing_rows(obs_on):
    obs.counter("frontend.queries_total").inc(3)
    obs.counter("wal.appends_total").inc(1)
    snap = metrics_snapshot()
    assert snap["enabled"] is True
    assert snap["metrics"]["frontend.queries_total"] == 3
    assert missing_rows(snap, ["frontend.", "wal."]) == []
    assert missing_rows(snap, ["router.", "frontend."]) == ["router."]
    # the snapshot is JSON all the way down
    json.dumps(snap)


# ---------------------------------------------------------------- descent

@pytest.mark.parametrize("dim", [3072, 20])
def test_lane_folded_slots_count_wide_pages_only(obs_on, dim):
    """A cohort over 3,072-dim pages (folded along lanes before the
    kernel's transpose) counts every live slot; over 20-d pages, none."""
    from repro.core.smtree import bulk_build
    from repro.serve.frontend import pinned_knn
    X = np.random.default_rng(dim).random((96, dim)).astype(np.float32)
    tree = bulk_build(X, capacity=8, metric="l2")
    pinned_knn(tree, X[:5], k=3, max_frontier=64)
    snap = obs.REGISTRY.snapshot()
    nodes = snap["descent.nodes_visited_total"]
    assert nodes > 0
    assert snap["descent.lane_folded_slots_total"] == \
        (nodes if dim == 3072 else 0)
