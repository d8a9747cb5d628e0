"""The kNN-LM datastore configuration's pieces of the benchmark, on the CPU
at small sizes: the L2 brute force against numpy, the key generator, the
bfloat16 control against the configuration's limits, a tiny cell of the
same kind through the whole run, and the readers of the page-bytes
counter and the ``descent.bound`` scope on ``knnlm_readers.json``."""
import io
import json
import types
from contextlib import redirect_stdout

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import attribution, check, harness, run, trace
from conftest import DATA, ROOT, TRAFFIC

ref_mod = harness.load_module("reference", "l2")
CFG = json.loads(
    (ROOT / "chipbench" / "configs" / "knnlm-starcoder2-3b-l2.json")
    .read_text())
TINY = json.loads((DATA / "tiny-knnlm.json").read_text())
FX = json.loads((DATA / "knnlm_readers.json").read_text())
PB = DATA / "small.xplane.pb"
SPANS = json.loads((DATA / "small.spans.json").read_text())


def numpy_knn(Q, X, live, k):
    d = np.sqrt(((Q[:, None, :].astype(np.float64) - X[None]) ** 2).sum(-1))
    d[:, ~live] = np.inf
    i = np.argsort(d, axis=1, kind="stable")[:, :k]
    return np.take_along_axis(d, i, 1), i


def test_l2_brute_force_matches_numpy():
    rng = np.random.default_rng(0)
    X = rng.normal(size=(1000, 96)).astype(np.float32)
    Q = rng.normal(size=(150, 96)).astype(np.float32)
    live = rng.random(1000) < 0.7
    ref = ref_mod.Reference(X, chunk=128, q_chunk=64)
    d, i = ref.knn(Q, live, 40)
    nd, ni = numpy_knn(Q, X, live, 40)
    np.testing.assert_allclose(d, nd, rtol=2e-6)
    np.testing.assert_array_equal(i, ni)
    np.testing.assert_allclose(ref.dist(Q, i), nd, rtol=2e-6)
    bad = i.copy()
    bad[:, 0], bad[:, 1] = -1, 1000
    assert np.isinf(ref.dist(Q, bad)[:, :2]).all()
    want = np.sqrt(((Q[:, None].astype(np.float64) - X[None]) ** 2).sum(-1))
    np.testing.assert_allclose(ref_mod.pairwise(Q, X), want, rtol=2e-6)


def test_generator_is_seeded_and_its_queries_are_not_corpus_rows():
    gen = harness.load_module("data", "starcoder2_hidden")
    a = gen.corpus(TINY)
    assert a.shape == (TINY["n"], TINY["dim"]) and a.dtype == np.float32
    again = harness.load_module("data", "starcoder2_hidden").corpus(TINY)
    np.testing.assert_array_equal(a, again)
    other = gen.corpus(dict(TINY, data_seed=TINY["data_seed"] + 1))
    assert not np.array_equal(a, other)
    seed = 2**31 + 5
    q = gen.queries(a, seed, 300, 0, cfg=TINY)
    np.testing.assert_array_equal(q, gen.queries(a, seed, 300, 0, cfg=TINY))
    assert not np.array_equal(q, gen.queries(a, seed, 300, 1, cfg=TINY))
    assert not np.array_equal(q, gen.queries(a, seed + 1, 300, 0, cfg=TINY))
    rows = {r.tobytes() for r in a}
    assert not any(r.tobytes() in rows for r in q)
    assert ref_mod.pairwise(q, a).min() > 0


def _wide_keys():
    """Keys and queries at the configuration's width, metric and k, from
    a one-layer key model (cheap on a CPU)."""
    gen = harness.load_module("data", "starcoder2_hidden")
    cfg = dict(CFG, key_model=dict(CFG["key_model"], n_layers=1, d_ff=3072,
                                   vocab_size=1024),
               sequences=8, seq_len=256, n=2048, query_sequences=1,
               query_skip=64)
    X = gen.corpus(cfg)
    return X, gen.queries(X, 2**31 + 7, 12, 0, cfg=cfg)


def test_bfloat16_control_fails_the_configurations_limits():
    X, Q = _wide_keys()
    k = CFG["k"]
    live = [np.ones(len(X), bool)]
    ref = ref_mod.Reference(X)
    d, i = ref.knn(Q, live[0], k)
    rec = types.SimpleNamespace(failed=np.zeros(len(Q), bool),
                                epoch=np.zeros(len(Q), np.int64),
                                queries=Q, dists=d, ids=i)
    ok, _ = check.compare(check.answer_numbers(rec, ref, live, k),
                          CFG["limits"])
    assert ok
    low = ref_mod.Reference(X, dtype=jnp.bfloat16)
    numbers = check.answer_numbers(rec, ref, live, k, answer=low.knn)
    ok, _ = check.compare(numbers, CFG["limits"])
    assert not ok
    assert numbers["answer_dist_gap"] > 100 * CFG["limits"]["answer_dist_gap"]


def test_tiny_knnlm_cell_is_correct(tiny_spec, monkeypatch):
    """A tiny cell of the configuration's kind (pre-head keys of a tiny
    key model, L2, closed-loop lookups) through the whole run."""
    load = harness.load_module

    def load_module(kind, name):
        mod = load(kind, name)
        if kind == "data" and name == "starcoder2_hidden":
            mod._config = lambda: TINY
        return mod

    monkeypatch.setattr(harness, "load_module", load_module)
    spec = dict(tiny_spec)
    spec["configs"] = tiny_spec["configs"] + [
        {"name": "tiny-knnlm", "source": "test",
         "file": "chipbench/tests/data/tiny-knnlm.json", "reduced": [],
         "why": "test"}]
    wl = {"name": "tiny.lookup", "config": "tiny-knnlm",
          "traffic": "tiny-lookup", "chips": 1, "why": "test"}
    spec["workloads"] = tiny_spec["workloads"] + [wl]
    args = types.SimpleNamespace(workload=wl["name"], seed=2**31 + 11,
                                 seconds=1.0, trace=0)
    buf = io.StringIO()
    with redirect_stdout(buf):
        assert run.run_cell(spec, wl, args, jax.devices()[:1], TRAFFIC) == 0
    res = json.loads(buf.getvalue().strip().splitlines()[-1])
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["checks"]) == {"unanswered", "bad_ids", "answer_dist_gap",
                                  "answer_id_gap"}


def test_hbm_share_reader():
    fx = FX["hbm"]
    reader = harness.load_module("metrics", "frontier.hbm_share")
    red = trace.Reduced(0.0, 1e9, n_chips=1,
                        ops={"frontier_scores_pallas.3": fx["frontier_ns"],
                             "sort.11": 1e6})
    run_ = types.SimpleNamespace(trace=red, answered=fx["answered"],
                                 counters=fx["counters"],
                                 device_kind=fx["device_kind"])
    got = reader.read(run_)
    assert got == pytest.approx(fx["expect"])
    assert 0 < got <= 1.0
    # a program without the counter, or a chip without a peak: no reading
    no_counter = dict(fx["counters"])
    del no_counter["descent.page_bytes_total"]
    assert reader.read(types.SimpleNamespace(
        trace=red, answered=fx["answered"], counters=no_counter,
        device_kind=fx["device_kind"])) is None
    assert reader.read(types.SimpleNamespace(
        trace=red, answered=fx["answered"], counters=fx["counters"],
        device_kind="cpu")) is None


def test_bound_reader(monkeypatch, tiny_cells):
    fx = FX["bound"]
    cell = tiny_cells("tiny.search")
    monkeypatch.setattr(harness, "cell_spec",
                        lambda *a, **kw: (cell.wl, cell.cfg, cell.traffic))
    reader = harness.load_module("metrics",
                                 "descent.bound_device_us_per_query")
    monkeypatch.setattr(reader, "PROGRAM", "jit__lambda")
    shapes = []

    def texts(tree, **kw):
        shapes.append(tree)
        return [fx["a"], fx["b"]]

    monkeypatch.setattr(attribution, "descent_texts", texts)
    secs = SPANS["t1"] - SPANS["t0"]
    red = trace.reduce(PB, trace.find_mark(PB, "chipbench.window_start"),
                       secs)
    rec = types.SimpleNamespace(workload="tiny.search", trace_path=PB,
                                seconds=secs)
    run_ = types.SimpleNamespace(trace=red, rec=rec, answered=fx["answered"])
    assert reader.read(run_) == pytest.approx(fx["expect"])
    # the descent was lowered from the cached index's shapes, not its pages
    tree = shapes[0]
    assert isinstance(tree.vecs, jax.ShapeDtypeStruct)
    assert int(tree.height) == int(cell.tree0.height)
    # a program whose descent has no such scope: no reading
    monkeypatch.setattr(attribution, "descent_texts", lambda tree, **kw: [
        fx["a"].replace("descent.bound", "x")])
    assert reader.read(run_) is None


def test_bound_scope_is_in_the_descent():
    from repro.core.smtree import bulk_build
    X = np.random.default_rng(0).random((300, 6)).astype(np.float32)
    for t in attribution.descent_texts(bulk_build(X, capacity=8), rows=8,
                                       k=20, max_frontier=64):
        assert attribution.scope_ops(attribution.program_ops(t),
                                     "descent.bound")
