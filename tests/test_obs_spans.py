"""The obs plane on the serving and writer paths: every cohort's
dispatcher-side spans (with or without a sampled ticket), the writer's
apply-path children and host-sync counter, the descent's grid counter,
the spans mirrored into the JAX profiler's trace, and the disabled path
building nothing."""
import threading

import jax
import numpy as np
import pytest

from repro import obs
from repro.core import smtree
from repro.core.smtree import OP_DELETE, OP_INSERT, bulk_build
from repro.obs import trace as obs_trace
from repro.serve.frontend import FrontendConfig, ServeFrontend, pinned_knn
from repro.stream import StreamingEngine

DIM = 4


@pytest.fixture
def obs_on():
    obs.reset()
    obs.enable()
    yield
    obs.disable()
    obs.set_trace_sampling(obs.TRACE_SAMPLE_EVERY)
    obs.reset()


def _points(n=200, seed=0):
    return np.random.default_rng(seed).random((n, DIM)).astype(np.float32)


def _split_and_merge_batch(X):
    """Six inserts into one leaf (a device split) and eight deletes far
    from it (device merges)."""
    ins = X[0] + 0.001 * np.random.default_rng(1).random(
        (6, DIM)).astype(np.float32)
    near = np.argsort(np.max(np.abs(X - X[150]), 1))[:12]
    far = [i for i in near if np.max(np.abs(X[i] - X[0])) > 0.2][:8]
    ops = np.array([OP_INSERT] * 6 + [OP_DELETE] * len(far), np.int32)
    xs = np.concatenate([ins, X[far]]).astype(np.float32)
    oids = np.concatenate([np.arange(1000, 1006), far]).astype(np.int32)
    return ops, xs, oids


def _children(spans, parent):
    return [s for s in spans if s["parent_id"] == parent["span_id"]]


def test_every_cohort_splits_its_device_compute(obs_on):
    obs.set_trace_sampling(8)
    X = _points()
    eng = StreamingEngine(bulk_build(X, capacity=8))
    cfg = FrontendConfig(cohort_width=4, slo_ms=1.0, k=3, max_frontier=256)
    with ServeFrontend(eng, cfg) as fe:
        for q in X[:9]:                     # one query a cohort
            fe.knn(q[None, :], timeout=60)
    spans = obs.RECORDER.spans()
    cohorts = [s for s in spans if s["name"] == "frontend.cohort"]
    assert len(cohorts) == 9
    # at 1 in 8 most cohorts hold no sampled ticket: their span is a root
    assert any(c["parent_id"] is None and not c["links"] for c in cohorts)
    for c in cohorts:
        (comp,) = [s for s in _children(spans, c)
                   if s["name"] == "frontend.device_compute"]
        kids = {s["name"]: s for s in _children(spans, comp)}
        assert set(kids) == {"frontend.dispatch", "frontend.device_wait",
                             "frontend.fetch"}
        assert [s["name"] for s in _children(spans,
                                             kids["frontend.dispatch"])] \
            == ["descent.height_read"]
        # the cohort span covers its children and its bookkeeping
        assert c["t_start"] <= comp["t_start"] <= comp["t_end"] <= c["t_end"]
    assert sum(s["name"] == "frontend.assemble" for s in spans) >= 9


def test_writer_children_and_host_syncs(obs_on):
    X = _points()
    eng = StreamingEngine(bulk_build(X, capacity=8, seed=0))
    res = eng.apply(*_split_and_merge_batch(X))
    assert res.n_split > 0 and res.n_merge > 0
    spans = obs.RECORDER.spans()
    (apply,) = [s for s in spans if s["name"] == "mutation.apply"]
    kids = {s["name"] for s in _children(spans, apply)}
    assert kids == {"mutation.scan", "mutation.split_pass",
                    "mutation.merge_pass", "mutation.status_read",
                    "mutation.escalate", "mutation.headroom"}
    (split,) = [s for s in spans if s["name"] == "mutation.split_pass"]
    assert [s["name"] for s in _children(spans, split)] == \
        ["mutation.split_chunk"] * len(smtree.split_chunks(res.n_split))
    # scan status, one split chunk, one merge chunk, the batcher's status
    # read and the free-ring headroom read
    assert obs.REGISTRY.snapshot()["mutation.host_syncs_total"] == 5


def test_mutation_passes_outside_a_span_emit_no_roots(obs_on):
    X = _points()
    tree = bulk_build(X, capacity=8, seed=0)
    smtree.apply_mutations(tree, *_split_and_merge_batch(X))
    assert obs.RECORDER.spans() == []
    assert obs.REGISTRY.snapshot()["mutation.host_syncs_total"] == 3


def test_grid_counter_counts_live_rows_only(obs_on):
    """A cohort of 5 queries padded to 16 rows: the first dispatch after
    a reset is sampled, and counts 5 queries and their grid."""
    X = _points()
    tree = bulk_build(X, capacity=8)
    cfg = FrontendConfig(cohort_width=16, slo_ms=500.0, k=3,
                         max_frontier=256)
    with ServeFrontend(StreamingEngine(tree), cfg) as fe:
        fe.knn(X[:5], timeout=60)
    snap = obs.REGISTRY.snapshot()
    widths = smtree.level_widths(int(tree.height), tree.capacity, 256)
    assert snap["frontend.cohorts_total"] == 1
    assert snap["descent.queries_total"] == 5
    assert snap["descent.grid_slots_total"] == 5 * sum(widths)
    res = smtree.knn(tree, X[:5], k=3, max_frontier=256)
    assert snap["descent.nodes_visited_total"] == \
        int(np.asarray(res.page_hits).sum())
    assert 0 < snap["descent.nodes_visited_total"] \
        <= snap["descent.grid_slots_total"]
    # outside a cohort every row counts
    pinned_knn(tree, X[:7], k=3, max_frontier=256)
    obs.reset()
    pinned_knn(tree, X[:7], k=3, max_frontier=256)
    assert obs.REGISTRY.snapshot()["descent.queries_total"] == 7


def test_grid_steps_count_live_rows_only(obs_on):
    """The sampled dispatch of a cohort of 5 queries padded to 16 rows adds
    the frontier kernel's grid steps of 5 rows, and the live blocks of
    those 5 rows alone."""
    X = _points()
    tree = bulk_build(X, capacity=8)
    cfg = FrontendConfig(cohort_width=16, slo_ms=500.0, k=3,
                         max_frontier=256)
    with ServeFrontend(StreamingEngine(tree), cfg) as fe:
        fe.knn(X[:5], timeout=60)
    snap = obs.REGISTRY.snapshot()
    steps = smtree.level_grid_steps(int(tree.height), tree.capacity, 256,
                                    tree.dim)
    assert snap["descent.grid_steps_total"] == 5 * sum(steps)
    _, (_, _, blocks) = smtree.knn(tree, X[:5], k=3, max_frontier=256,
                                   level_stats=True)
    assert snap["descent.live_blocks_total"] == int(np.asarray(blocks).sum())
    assert 0 < snap["descent.live_blocks_total"] \
        <= snap["descent.grid_steps_total"]


def test_page_bytes_count_live_slots_of_live_rows(obs_on):
    """Beside the grid counters, the sampled dispatch of a cohort of 5
    rows padded to 16 counts the frontier kernel's page traffic: each
    live slot of the 5 rows, the tile-padded page and its per-entry
    rows."""
    from repro.kernels.frontier import page_bytes
    X = _points()
    tree = bulk_build(X, capacity=8)
    cfg = FrontendConfig(cohort_width=16, slo_ms=500.0, k=3,
                         max_frontier=256)
    with ServeFrontend(StreamingEngine(tree), cfg) as fe:
        fe.knn(X[:5], timeout=60)
    snap = obs.REGISTRY.snapshot()
    assert page_bytes(tree.capacity, tree.dim) == 8 * 128 * 4 + 8 * 128 * 4
    assert snap["descent.page_bytes_total"] == (
        snap["descent.nodes_visited_total"]
        * page_bytes(tree.capacity, tree.dim))
    assert snap["descent.queries_total"] == 5


def test_live_blocks_match_a_count_of_the_frontiers():
    """The level-stats descent's live-block stack equals a numpy count,
    over the frontier of every scoring call, of the blocks of
    ``block_slots`` slots that hold a live id (the leaf level summing its
    chunks)."""
    from repro.kernels import frontier
    X = _points(600, seed=3)
    tree = bulk_build(X, capacity=8)
    height = int(tree.height)
    Q = X[:6] + 0.01
    seen = []
    scores = frontier.frontier_scores

    def recording(fids, *a, **kw):
        jax.debug.callback(lambda f: seen.append(np.asarray(f)), fids,
                           ordered=True)
        return scores(fids, *a, **kw)

    smtree._knn_cohort.clear_cache()
    try:
        frontier.frontier_scores = recording
        _, (_, _, blocks) = smtree.knn(tree, Q, k=3, max_frontier=24,
                                       impl="xla", level_stats=True)
        blocks = np.asarray(blocks)
        jax.effects_barrier()
    finally:
        frontier.frontier_scores = scores
        smtree._knn_cohort.clear_cache()
    widths = smtree.level_widths(height, tree.capacity, 24)
    assert [f.shape[1] for f in seen] == widths[:-1] + [
        wc for _, wc in smtree.leaf_chunks(widths[-1])]
    want = np.zeros((height, len(Q)), np.int64)
    for n, f in enumerate(seen):
        g = frontier.block_slots(f.shape[1], tree.capacity, tree.dim)
        f = np.pad(f, ((0, 0), (0, -f.shape[1] % g)), constant_values=-1)
        want[min(n, height - 1)] += (f.reshape(len(Q), -1, g) >= 0).any(2) \
            .sum(1)
    np.testing.assert_array_equal(blocks, want)
    assert blocks[-1].sum() > 0


def test_kernel_work_gathers_the_counter_inputs():
    from repro.kernels.frontier import page_bytes
    work = smtree.kernel_work(3, 42, 2048, 20)
    assert work == dict(widths=smtree.level_widths(3, 42, 2048),
                        grid_steps=smtree.level_grid_steps(3, 42, 2048, 20),
                        page_bytes=page_bytes(42, 20), lane_stages=0)
    assert smtree.kernel_work(4, 32, 12288, 3072)["lane_stages"] == 3


def test_level_widths():
    assert smtree.level_widths(3, 42, 2048) == [1, 42, 1764]
    assert smtree.level_widths(4, 8, 64) == [1, 8, 64, 64]


def test_spans_mirror_into_the_profiler_trace(obs_on, tmp_path):
    from jax.profiler import ProfileData
    root = obs.start_span("test.cross_thread_root", mirror=False)

    def work():
        with obs.span("test.mirrored", parent=root.ctx):
            with obs.child_span("test.inner"):
                jax.block_until_ready(jax.numpy.ones(8) + 1)
        root.end()                  # ends on another thread: no raise

    with jax.profiler.trace(str(tmp_path)):
        t = threading.Thread(target=work)
        t.start()
        t.join()
    (pb,) = tmp_path.rglob("*.xplane.pb")
    names = {ev.name for p in ProfileData.from_file(str(pb)).planes
             if p.name.startswith("/host:")
             for ln in p.lines for ev in ln.events}
    assert {"test.mirrored", "test.inner"} <= names
    assert "test.cross_thread_root" not in names
    assert {s["name"] for s in obs.RECORDER.spans()} == {
        "test.mirrored", "test.inner", "test.cross_thread_root"}


def test_disabled_path_builds_no_span_and_no_annotation(monkeypatch,
                                                        tmp_path):
    def refuse(*a, **kw):
        raise AssertionError("built with observability off")

    monkeypatch.setattr(obs_trace, "Span", refuse)
    monkeypatch.setattr(obs_trace, "TraceAnnotation", refuse)
    assert not obs.enabled()
    X = _points()
    eng = StreamingEngine(bulk_build(X, capacity=8, seed=0))
    cfg = FrontendConfig(cohort_width=4, slo_ms=1.0, k=3, max_frontier=256)
    with ServeFrontend(eng, cfg) as fe:
        fe.knn(X[:6], timeout=60)
        fe.submit_mutations(*_split_and_merge_batch(X)).result(60)
    assert obs.span("x") is obs.NULL_SPAN
    assert obs.child_span("x") is obs.NULL_SPAN
    assert obs.start_span("x") is obs.NULL_SPAN
