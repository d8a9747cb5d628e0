"""Cohort-descent engine: parity across frontier implementations, parity vs
the paper-faithful reference, and adversarial data (ISSUE 2 satellite).

The bitwise tests here are the PR's acceptance parity suite: knn and
range_search results must be identical between ``REPRO_FRONTIER_IMPL=xla``
and ``=pallas`` (interpret mode on CPU), down to stats and tie-broken ids.
"""
import numpy as np
import pytest

from repro.core.engine import SMTreeEngine
from repro.core.metric import pairwise
from repro.data.datagen import clustered, uniform

FIELDS = ("dists", "ids", "page_hits", "dist_evals", "overflow")


def assert_results_equal(a, b, msg=""):
    for f in FIELDS:
        np.testing.assert_array_equal(np.asarray(getattr(a, f)),
                                      np.asarray(getattr(b, f)),
                                      err_msg=f"{msg}:{f}")


def brute_knn_dists(metric, X, Q, k):
    return np.sort(pairwise(metric, Q, X), axis=1)[:, :k]


# --------------------------------------------------------------------------
# xla vs pallas bitwise parity (the acceptance suite)
# --------------------------------------------------------------------------
@pytest.mark.parametrize("metric", ["d_inf", "l2"])
def test_knn_bitwise_xla_vs_pallas(metric):
    X = clustered(1500, dims=8, seed=3)
    eng = SMTreeEngine.build(X, capacity=16, metric=metric)
    Q = uniform(24, dims=8, seed=4)
    for k, F in ((1, 64), (10, 64), (10, 256)):
        a = eng.knn(Q, k=k, max_frontier=F, impl="xla")
        b = eng.knn(Q, k=k, max_frontier=F, impl="pallas")
        assert_results_equal(a, b, f"knn k={k} F={F} {metric}")


@pytest.mark.parametrize("metric", ["d_inf", "l2"])
def test_range_search_bitwise_xla_vs_pallas(metric):
    X = clustered(1500, dims=8, seed=5)
    eng = SMTreeEngine.build(X, capacity=16, metric=metric)
    Q = X[::100].copy()
    for r in (0.0, 0.05, 0.5):
        a = eng.range_search(Q, r, max_results=64, impl="xla")
        b = eng.range_search(Q, r, max_results=64, impl="pallas")
        assert_results_equal(a, b, f"range r={r} {metric}")


def test_env_toggle_routes_impl(monkeypatch):
    X = clustered(600, dims=6, seed=6)
    eng = SMTreeEngine.build(X, capacity=8)
    Q = uniform(8, dims=6, seed=7)
    explicit = eng.knn(Q, k=3, impl="pallas")
    monkeypatch.setenv("REPRO_FRONTIER_IMPL", "pallas")
    via_env = eng.knn(Q, k=3)
    assert_results_equal(explicit, via_env, "env routing")
    monkeypatch.setenv("REPRO_FRONTIER_IMPL", "bogus")
    with pytest.raises(ValueError):
        eng.knn(Q, k=3)


# --------------------------------------------------------------------------
# parent-distance pre-filter (DESIGN.md §17): results bitwise identical with
# pruning on vs off; only dist_evals (evaluations *performed*) may shrink
# --------------------------------------------------------------------------
RESULT_FIELDS = ("dists", "ids", "page_hits", "overflow")


def assert_results_equal_ex_evals(a, b, msg=""):
    for f in RESULT_FIELDS:
        np.testing.assert_array_equal(np.asarray(getattr(a, f)),
                                      np.asarray(getattr(b, f)),
                                      err_msg=f"{msg}:{f}")


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("metric", ["d_inf", "l2", "l1"])
def test_knn_parent_prune_bitwise(metric, impl):
    X = clustered(1500, dims=8, seed=3)
    eng = SMTreeEngine.build(X, capacity=16, metric=metric)
    Q = np.vstack([uniform(16, dims=8, seed=4), X[:16] + 0.003])
    for k, F in ((1, 64), (10, 64), (10, 256)):
        off = eng.knn(Q, k=k, max_frontier=F, impl=impl, parent_prune=False)
        on = eng.knn(Q, k=k, max_frontier=F, impl=impl, parent_prune=True)
        assert_results_equal_ex_evals(off, on, f"knn k={k} F={F} {metric}")
        # the filter only removes work, never adds it
        assert (np.asarray(on.dist_evals) <= np.asarray(off.dist_evals)).all()


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("metric", ["d_inf", "l2", "l1"])
def test_range_search_parent_prune_bitwise(metric, impl):
    X = clustered(1500, dims=8, seed=5)
    eng = SMTreeEngine.build(X, capacity=16, metric=metric)
    Q = X[::100].copy()
    for r in (0.0, 0.05, 0.5):
        off = eng.range_search(Q, r, max_results=64, impl=impl,
                               parent_prune=False)
        on = eng.range_search(Q, r, max_results=64, impl=impl,
                              parent_prune=True)
        assert_results_equal_ex_evals(off, on, f"range r={r} {metric}")
        assert (np.asarray(on.dist_evals) <= np.asarray(off.dist_evals)).all()


def test_parent_prune_env_toggle(monkeypatch):
    X = clustered(600, dims=6, seed=6)
    eng = SMTreeEngine.build(X, capacity=8)
    Q = uniform(8, dims=6, seed=7)
    explicit_off = eng.knn(Q, k=3, impl="xla", parent_prune=False)
    monkeypatch.setenv("REPRO_PARENT_PRUNE", "0")
    via_env = eng.knn(Q, k=3, impl="xla")
    assert_results_equal(explicit_off, via_env, "env off routing")
    monkeypatch.setenv("REPRO_PARENT_PRUNE", "1")
    on_env = eng.knn(Q, k=3, impl="xla")
    assert_results_equal_ex_evals(explicit_off, on_env, "env on routing")
    monkeypatch.setenv("REPRO_PARENT_PRUNE", "yes")
    with pytest.raises(ValueError, match="REPRO_PARENT_PRUNE"):
        eng.knn(Q, k=3, impl="xla")


def _collinear_tree(metric="d_inf"):
    """Planted adversarial geometry: points on a line at exactly-
    representable f32 coordinates.  For collinear same-side points the
    triangle inequality is *tight* — |d(q,p) − d(e,p)| == d(q,e) exactly,
    in f32 too — so the parent filter sits exactly on its boundary for
    every entry: any over-aggressive filtering (a missing pad, a stale
    pdist/radius) drops true neighbors."""
    n, dims = 192, 4
    X = np.zeros((n, dims), np.float32)
    X[:, 0] = np.arange(n, dtype=np.float32) / 64.0
    eng = SMTreeEngine.build(X, capacity=4, metric=metric)
    return eng, X


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_parent_prune_adversarial_collinear(impl):
    eng, X = _collinear_tree()
    # far collinear queries: every frontier entry is same-side, the filter's
    # lower bound equals the true distance bit-for-bit
    q = np.zeros((3, 4), np.float32)
    q[:, 0] = [X[-1, 0] + 8.0, -5.0, X[96, 0]]
    for k in (1, 5, 17):
        off = eng.knn(q, k=k, max_frontier=64, impl=impl, parent_prune=False)
        on = eng.knn(q, k=k, max_frontier=64, impl=impl, parent_prune=True)
        assert_results_equal_ex_evals(off, on, f"collinear k={k}")
        np.testing.assert_allclose(np.asarray(on.dists),
                                   brute_knn_dists("d_inf", X, q, k),
                                   atol=1e-6)


def test_parent_prune_rides_on_pdist_invariant():
    """Corrupting pdist makes the filter wrongly prune — the demonstration
    that pruning correctness rides on the pdist invariant (pinned
    independently by tests/test_pdist_invariant.py), while the unfiltered
    path is immune."""
    import dataclasses

    import jax.numpy as jnp

    from repro.core import smtree
    eng, X = _collinear_tree()
    q = np.zeros((1, 4), np.float32)
    q[0, 0] = X[96, 0]
    want = brute_knn_dists("d_inf", X, q, 5)
    # stale-pdist plant: every entry claims to sit 1000 from its routing
    # object, so |d(q,p) − pdist| dwarfs rq + r and the filter drops
    # everything below the root
    bad = dataclasses.replace(eng.tree,
                              pdist=jnp.full_like(eng.tree.pdist, 1000.0))
    res_off = smtree.knn(bad, q, k=5, max_frontier=64, impl="xla",
                         parent_prune=False)
    np.testing.assert_allclose(np.asarray(res_off.dists), want, atol=1e-6)
    res_on = smtree.knn(bad, q, k=5, max_frontier=64, impl="xla",
                        parent_prune=True)
    assert not np.allclose(np.asarray(res_on.dists), want), \
        "corrupt pdist must break the filtered path (else the filter is dead)"


def test_level_stats_parent_counts():
    """level_stats returns (by_bound, by_parent, live_blocks); parent
    counts are zero at the root level and with the filter off, and
    account exactly for the dist_evals delta.  At internal levels, every
    parent-filtered entry provably fails the d_min bound too (DESIGN.md
    §17), so in the unfiltered trace it shows up as pruned-by-bound
    instead: bb_off == bb_on + bp_on at those levels."""
    from repro.core import smtree
    X = clustered(2000, dims=8, seed=23)
    eng = SMTreeEngine.build(X, capacity=16)
    Q = np.asarray(X[:32] + 0.002, np.float32)
    res_on, (bb_on, bp_on, _) = smtree.knn(
        eng.tree, Q, k=5, max_frontier=64, impl="xla", level_stats=True,
        parent_prune=True)
    res_off, (bb_off, bp_off, _) = smtree.knn(
        eng.tree, Q, k=5, max_frontier=64, impl="xla", level_stats=True,
        parent_prune=False)
    assert np.asarray(bp_off).sum() == 0
    assert np.asarray(bp_on)[0].sum() == 0          # root has no parent
    n_internal = np.asarray(bb_on).shape[0]
    np.testing.assert_array_equal(
        np.asarray(bb_off),
        np.asarray(bb_on) + np.asarray(bp_on)[:n_internal])
    delta = (np.asarray(res_off.dist_evals) - np.asarray(res_on.dist_evals))
    np.testing.assert_array_equal(np.asarray(bp_on).sum(axis=0), delta)
    assert np.asarray(bp_on).sum() > 0              # the filter actually bites


# --------------------------------------------------------------------------
# cohort vs legacy per-query engine (results, not stats — the cohort path's
# min-fill-aware d_max bound prunes tighter, so page_hits legitimately
# differ; distances and ids may not)
# --------------------------------------------------------------------------
@pytest.mark.parametrize("metric", ["d_inf", "l2"])
def test_cohort_matches_perquery_results(metric):
    X = clustered(1200, dims=8, seed=9)
    eng = SMTreeEngine.build(X, capacity=16, metric=metric)
    Q = uniform(16, dims=8, seed=10)
    a = eng.knn(Q, k=8, max_frontier=256, impl="xla")
    p = eng.knn(Q, k=8, max_frontier=256, impl="perquery")
    assert not np.asarray(a.overflow).any()
    assert not np.asarray(p.overflow).any()
    np.testing.assert_array_equal(np.asarray(a.dists), np.asarray(p.dists))
    # ids may tie-break differently only between equal distances; verify
    # every returned id really sits at the reported distance
    D = pairwise(metric, Q, X)
    ids = np.asarray(a.ids)
    dists = np.asarray(a.dists)
    for qi in range(len(Q)):
        for j, (i, d) in enumerate(zip(ids[qi], dists[qi])):
            if i >= 0:
                np.testing.assert_allclose(D[qi, i], d, atol=1e-5)


# --------------------------------------------------------------------------
# adversarial data (vs brute force and the paper-faithful reference)
# --------------------------------------------------------------------------
def test_duplicate_points():
    rng = np.random.default_rng(11)
    base = rng.random((200, 6)).astype(np.float32)
    X = np.repeat(base, 4, axis=0)          # every point appears 4 times
    eng = SMTreeEngine.build(X, capacity=8)
    Q = base[:16] + 0.001
    for impl in ("xla", "pallas", "perquery"):
        res = eng.knn(Q, k=8, max_frontier=512, impl=impl)
        assert not np.asarray(res.overflow).any()
        np.testing.assert_allclose(np.asarray(res.dists),
                                   brute_knn_dists("d_inf", X, Q, 8),
                                   atol=1e-5, err_msg=impl)


def test_all_points_equidistant():
    """One-hot points scaled by c: every pairwise d_inf distance is exactly
    c, and the origin sees every point at distance c — maximal tie stress
    for the d_max bound and top-k tie-breaking."""
    n = dim = 48
    c = 0.7
    X = (np.eye(n, dim) * c).astype(np.float32)
    eng = SMTreeEngine.build(X, capacity=8)
    Q = np.zeros((1, dim), np.float32)
    for impl in ("xla", "pallas", "perquery"):
        res = eng.knn(Q, k=5, max_frontier=512, impl=impl)
        assert not np.asarray(res.overflow).any()
        np.testing.assert_allclose(np.asarray(res.dists), np.full((1, 5), c),
                                   atol=1e-6, err_msg=impl)
        ids = np.asarray(res.ids)[0]
        assert len(set(ids.tolist())) == 5 and (ids >= 0).all()
    # a query at one of the points: itself at 0, the rest at c
    res = eng.knn(X[:1], k=5, max_frontier=512, impl="xla")
    d = np.asarray(res.dists)[0]
    np.testing.assert_allclose(d, [0.0, c, c, c, c], atol=1e-6)


def test_k_exceeds_n_objects():
    X = uniform(10, dims=5, seed=13)
    eng = SMTreeEngine.build(X, capacity=8)
    Q = uniform(4, dims=5, seed=14)
    for impl in ("xla", "pallas", "perquery"):
        res = eng.knn(Q, k=32, max_frontier=64, impl=impl)
        d = np.asarray(res.dists)
        ids = np.asarray(res.ids)
        np.testing.assert_allclose(d[:, :10], brute_knn_dists("d_inf", X, Q, 10),
                                   atol=1e-5, err_msg=impl)
        assert np.isposinf(d[:, 10:]).all()
        assert (ids[:, 10:] == -1).all()
        assert (np.sort(ids[:, :10], axis=1) == np.arange(10)).all()


def test_parity_vs_ref_impl_on_clustered_and_duplicates():
    """Engine (all impls) returns the same kNN distances as the
    paper-faithful reference on clustered data salted with duplicates."""
    from repro.core.ref_impl import SMTree
    X = clustered(900, dims=10, seed=15)
    X = np.vstack([X, X[:60]])               # salt with duplicates
    eng = SMTreeEngine.build(X, capacity=16)
    ref = SMTree(dim=10, capacity=16, n_dims=10)
    for i, x in enumerate(X):
        ref.insert(x, i)
    Q = uniform(8, dims=10, seed=16)
    for impl in ("xla", "pallas", "perquery"):
        res = eng.knn(Q, k=10, max_frontier=512, impl=impl)
        assert not np.asarray(res.overflow).any()
        for qi, q in enumerate(Q):
            want = np.array([d for d, _ in ref.knn_query(q, 10)])
            np.testing.assert_allclose(np.asarray(res.dists)[qi], want,
                                       atol=1e-5, err_msg=impl)


# --------------------------------------------------------------------------
# range_search overflow-flag semantics at exactly max_results
# --------------------------------------------------------------------------
def test_range_overflow_flag_at_exact_capacity():
    """Cluster of exactly m in-radius points: max_results == m sets the
    (conservative) overflow flag, max_results > m does not; the returned id
    set is exact either way and identical across impls."""
    rng = np.random.default_rng(17)
    m = 12
    near = (rng.random((m, 6)) * 0.02).astype(np.float32)         # within 0.1
    far = (rng.random((120, 6)) * 0.5 + 5.0).astype(np.float32)   # way outside
    X = np.vstack([near, far])
    eng = SMTreeEngine.build(X, capacity=8)
    q = np.zeros((1, 6), np.float32)
    want_ids = set(range(m))

    for impl in ("xla", "pallas", "perquery"):
        # exactly max_results matches -> flag set (cannot rule out truncation)
        res = eng.range_search(q, 0.1, max_results=m, max_frontier=256,
                               impl=impl)
        assert bool(np.asarray(res.overflow)[0]), impl
        got = set(int(i) for i in np.asarray(res.ids)[0] if i >= 0)
        assert got == want_ids, impl
        # headroom -> no flag, same ids
        res = eng.range_search(q, 0.1, max_results=m + 1, max_frontier=256,
                               impl=impl)
        assert not bool(np.asarray(res.overflow)[0]), impl
        got = set(int(i) for i in np.asarray(res.ids)[0] if i >= 0)
        assert got == want_ids, impl

    a = eng.range_search(q, 0.1, max_results=m, impl="xla")
    b = eng.range_search(q, 0.1, max_results=m, impl="pallas")
    assert_results_equal(a, b, "range exact-capacity")


def test_small_awkward_builds_keep_min_fill_and_exactness():
    """bulk_build sizes that used to split below min_fill (e.g. 23 points at
    capacity 32 -> 11/12-entry leaves vs floor 13) broke the cohort d_max
    bound's coverage premise, silently dropping neighbors with
    overflow=False.  Non-root nodes must meet min_fill and knn must stay
    exact for every k up to n."""
    rng = np.random.default_rng(21)
    for n in (5, 13, 23, 24, 25, 33, 47):
        # two well-separated clusters: the adversarial case for a bound
        # that overestimates a subtree's coverage
        a = rng.random((n // 2, 4)).astype(np.float32)
        b2 = rng.random((n - n // 2, 4)).astype(np.float32) + 200.0
        X = np.vstack([a, b2])
        eng = SMTreeEngine.build(X, capacity=32)
        eng.validate()
        q = X[:2]
        for k in (1, n // 2 + 1, n):
            for impl in ("xla", "perquery"):
                res = eng.knn(q, k=k, max_frontier=256, impl=impl)
                assert not np.asarray(res.overflow).any()
                np.testing.assert_allclose(
                    np.asarray(res.dists), brute_knn_dists("d_inf", X, q, k),
                    atol=1e-5, err_msg=f"n={n} k={k} {impl}")


# --------------------------------------------------------------------------
# l1 rides the shared metric registry through all three call sites
# --------------------------------------------------------------------------
def test_l1_metric_end_to_end():
    X = clustered(500, dims=6, seed=19)
    eng = SMTreeEngine.build(X, capacity=8, metric="l1")
    Q = uniform(8, dims=6, seed=20)
    a = eng.knn(Q, k=4, max_frontier=256, impl="xla")
    b = eng.knn(Q, k=4, max_frontier=256, impl="pallas")
    assert_results_equal(a, b, "l1")
    assert not np.asarray(a.overflow).any()
    np.testing.assert_allclose(np.asarray(a.dists),
                               brute_knn_dists("l1", X, Q, 4), atol=1e-5)


# --------------------------------------------------------------------------
# The kNN-LM datastore's query: k = 1,024 over L2 keys of 3,072 dims
# --------------------------------------------------------------------------
def test_knn_k1024_l2_dim3072_matches_brute_force():
    """A few thousand clustered keys at StarCoder2-3B's width, k = 1,024 (a
    parent-distance bound over 1,024 leaf entries, top-k cuts of k rows):
    the served distances are the brute force's, each served id sits at its
    distance, and a frontier above the leaf count never overflows."""
    from repro.core import smtree
    rng = np.random.default_rng(1024)
    centres = rng.normal(size=(24, 3072)).astype(np.float32)
    X = (centres[rng.integers(0, 24, 3000)]
         + 0.3 * rng.normal(size=(3000, 3072))).astype(np.float32)
    Q = (centres[:2] + 0.3 * rng.normal(size=(2, 3072))).astype(np.float32)
    tree = smtree.bulk_build(X, capacity=32, metric="l2", seed=2)
    res = smtree.knn(tree, Q, k=1024, max_frontier=256, impl="xla")
    want = np.sqrt(((Q[:, None, :].astype(np.float64) - X[None]) ** 2)
                   .sum(-1))
    d, ids = np.asarray(res.dists), np.asarray(res.ids)
    np.testing.assert_allclose(d, np.sort(want, axis=1)[:, :1024],
                               rtol=1e-5)
    np.testing.assert_allclose(d, np.take_along_axis(want, ids, 1),
                               rtol=1e-5)
    assert (np.sort(ids, axis=1)[:, 1:] != np.sort(ids, axis=1)[:, :-1]).all()
    assert not np.asarray(res.overflow).any()


@pytest.mark.parametrize("metric", ["d_inf", "l2", "l1"])
def test_bulk_build_on_device_equals_host(metric, monkeypatch):
    """The build's distances on the default device (the path of large
    corpora) give the tree the host's numpy gives, bit for bit (the shared
    metric's fixed fold)."""
    from repro.core import smtree
    rng = np.random.default_rng(7)
    X = (rng.random((1500, 40)) ** 3).astype(np.float32)
    X[100:160] = X[100]                       # duplicates: tied pivots
    host = smtree.bulk_build(X, capacity=16, metric=metric, seed=4)
    monkeypatch.setattr(smtree, "_DEVICE_BUILD_ELEMS", 1)
    dev = smtree.bulk_build(X, capacity=16, metric=metric, seed=4)
    for f in ("vecs", "radius", "pdist", "child", "oid", "valid", "count",
              "is_leaf", "parent", "pslot", "root", "height"):
        np.testing.assert_array_equal(np.asarray(getattr(host, f)),
                                      np.asarray(getattr(dev, f)), err_msg=f)
