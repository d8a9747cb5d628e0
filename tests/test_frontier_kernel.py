"""Interpret-mode parity for the fused frontier-scoring Pallas kernel.

The kernel (kernels/frontier.py) must be *bitwise* identical to the XLA
gather path on every output — the cohort descent's xla-vs-pallas parity
guarantee reduces to this plus determinism of top_k.  Runs the real kernel
code through the Pallas interpreter on CPU.

The parent-distance pre-filter variant (DESIGN.md §17) additionally must:
drop exactly the entries with |qpd − pdist| > rq + r (+ the documented
pad), keep the *boundary* case |qpd − pdist| == rq + r (never prune on
equality — mirrors the descent's _EPS-padded prune test), and leave every
kept entry's outputs bitwise equal to the unfiltered kernel's.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.kernels import frontier
from repro.kernels.frontier import (_PRUNE_PAD, frontier_scores,
                                    frontier_scores_pallas,
                                    frontier_scores_xla)

METRICS = ["d_inf", "l2", "l1"]
OUT_NAMES = ("dmax", "score", "leaf_d", "dq")


def _random_tree_pages(rng, N=40, cap=16, dim=10):
    vecs = rng.normal(size=(N, cap, dim)).astype(np.float32)
    radius = np.abs(rng.normal(size=(N, cap))).astype(np.float32)
    valid = rng.random((N, cap)) < 0.8
    is_leaf = rng.random(N) < 0.5
    internal_valid = valid & ~is_leaf[:, None]
    leaf_valid = valid & is_leaf[:, None]
    return (jnp.asarray(vecs), jnp.asarray(radius),
            jnp.asarray(internal_valid), jnp.asarray(leaf_valid))


def _random_frontier(rng, N, b, w):
    # frontier includes empty (-1) slots, duplicates, and boundary ids
    fids = rng.integers(-1, N, size=(b, w)).astype(np.int32)
    fids[0, :] = -1                      # fully-done query
    fids[1, :] = 0                       # duplicated node
    fids[2, 0] = N - 1                   # last row
    return jnp.asarray(fids)


def _random_prune_inputs(rng, fids, N, cap):
    b, w = fids.shape
    pdist = np.abs(rng.normal(size=(N, cap))).astype(np.float32)
    qpd = np.abs(rng.normal(size=(b, w))).astype(np.float32)
    qpd[np.asarray(fids) < 0] = np.inf   # empty slots carry +inf
    rq = np.abs(rng.normal(size=(b,))).astype(np.float32)
    return jnp.asarray(pdist), jnp.asarray(qpd), jnp.asarray(rq)


@pytest.mark.parametrize("metric", METRICS)
def test_kernel_matches_xla_bitwise(metric):
    rng = np.random.default_rng(0)
    vecs, radius, iv, lv = _random_tree_pages(rng)
    b, w = 8, 5
    queries = jnp.asarray(rng.normal(size=(b, vecs.shape[-1])).astype(np.float32))
    fids = _random_frontier(rng, vecs.shape[0], b, w)

    got = frontier_scores_pallas(fids, queries, vecs, radius, iv, lv,
                                 metric=metric, interpret=True)
    want = frontier_scores_xla(fids, queries, vecs, radius, iv, lv,
                               metric=metric)
    assert len(got) == len(want) == 4
    for g, wv, name in zip(got, want, OUT_NAMES):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(wv),
                                      err_msg=f"{metric}/{name}")


@pytest.mark.parametrize("metric", METRICS)
def test_pruned_kernel_matches_xla_bitwise(metric):
    """With the parent filter engaged, pallas and xla must still agree on
    every output bit — same keep mask, same distances."""
    rng = np.random.default_rng(3)
    vecs, radius, iv, lv = _random_tree_pages(rng)
    b, w = 8, 5
    queries = jnp.asarray(rng.normal(size=(b, vecs.shape[-1])).astype(np.float32))
    fids = _random_frontier(rng, vecs.shape[0], b, w)
    pdist, qpd, rq = _random_prune_inputs(rng, fids, vecs.shape[0],
                                          vecs.shape[1])

    got = frontier_scores_pallas(fids, queries, vecs, radius, iv, lv,
                                 metric=metric, interpret=True,
                                 pdist=pdist, qpd=qpd, rq=rq)
    want = frontier_scores_xla(fids, queries, vecs, radius, iv, lv,
                               metric=metric, pdist=pdist, qpd=qpd, rq=rq)
    for g, wv, name in zip(got, want, OUT_NAMES):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(wv),
                                      err_msg=f"{metric}/{name}")


@pytest.mark.parametrize("impl", ["pallas", "xla"])
@pytest.mark.parametrize("metric", METRICS)
def test_pruned_outputs_subset_of_unpruned(metric, impl):
    """The filter only ever *removes* evaluations: kept entries' outputs are
    bitwise those of the unfiltered kernel; dropped entries are exactly the
    |qpd − pdist| > rq + r + pad set and emit +inf."""
    rng = np.random.default_rng(4)
    vecs, radius, iv, lv = _random_tree_pages(rng)
    b, w = 8, 5
    queries = jnp.asarray(rng.normal(size=(b, vecs.shape[-1])).astype(np.float32))
    fids = _random_frontier(rng, vecs.shape[0], b, w)
    pdist, qpd, rq = _random_prune_inputs(rng, fids, vecs.shape[0],
                                          vecs.shape[1])

    plain = frontier_scores(fids, queries, vecs, radius, iv, lv,
                            metric=metric, impl=impl, interpret=True)
    pruned = frontier_scores(fids, queries, vecs, radius, iv, lv,
                             metric=metric, impl=impl, interpret=True,
                             pdist=pdist, qpd=qpd, rq=rq)
    nodes = np.maximum(np.asarray(fids), 0)
    lb = np.abs(np.asarray(qpd)[:, :, None] - np.asarray(pdist)[nodes])
    keep = lb <= (np.asarray(rq)[:, None, None] + np.asarray(radius)[nodes]
                  + np.float32(_PRUNE_PAD))
    for g_plain, g_pruned, name in zip(plain, pruned, OUT_NAMES):
        a, p = np.asarray(g_plain), np.asarray(g_pruned)
        np.testing.assert_array_equal(p[keep], a[keep],
                                      err_msg=f"{metric}/{impl}/{name}/kept")
        assert np.isposinf(p[~keep]).all(), f"{metric}/{impl}/{name}/dropped"


@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_prune_boundary_is_inclusive(impl):
    """|qpd − pdist| == rq + r must NOT prune (consistent with the _EPS
    padding of the descent's prune test: equality always survives), while a
    gap clearly above the pad must."""
    cap, dim = 4, 6
    vecs = jnp.zeros((1, cap, dim), jnp.float32)
    radius = jnp.asarray([[0.0, 0.25, 0.0, 0.0]], jnp.float32)
    iv = jnp.ones((1, cap), bool)
    lv = jnp.zeros((1, cap), bool)
    # exactly representable f32 values: lb = |1.5 − pdist|, rq = 0.5
    #   slot0: lb = 0.5  == rq + r (0.5)   -> keep (boundary)
    #   slot1: lb = 0.75 == rq + r (0.75)  -> keep (boundary, r > 0)
    #   slot2: lb = 0.5 + pad/2            -> keep (inside the pad)
    #   slot3: lb = 0.625 > rq + r + pad   -> prune
    pdist = jnp.asarray([[1.0, 0.75, 1.0 - _PRUNE_PAD / 2, 0.875]],
                        jnp.float32)
    qpd = jnp.asarray([[1.5]], jnp.float32)
    rq = jnp.asarray([0.5], jnp.float32)
    fids = jnp.zeros((1, 1), jnp.int32)
    queries = jnp.zeros((1, dim), jnp.float32)

    dmax, score, leaf_d, dq = frontier_scores(
        fids, queries, vecs, radius, iv, lv, metric="d_inf", impl=impl,
        interpret=True, pdist=pdist, qpd=qpd, rq=rq)
    finite = np.isfinite(np.asarray(dmax))[0, 0]
    np.testing.assert_array_equal(finite, [True, True, True, False],
                                  err_msg=impl)


@pytest.mark.parametrize("metric", ["d_inf", "l2"])
def test_empty_frontier_emits_inf(metric):
    rng = np.random.default_rng(1)
    vecs, radius, iv, lv = _random_tree_pages(rng, N=8, cap=4, dim=6)
    fids = jnp.full((3, 4), -1, jnp.int32)
    queries = jnp.asarray(rng.normal(size=(3, 6)).astype(np.float32))
    out = frontier_scores_pallas(fids, queries, vecs, radius, iv, lv,
                                 metric=metric, interpret=True)
    for arr in out:
        assert np.isposinf(np.asarray(arr)).all()


def test_masks_partition_outputs():
    """An entry is internal xor leaf xor invalid: dmax/score/dq finite
    exactly where internal-valid, leaf_d finite exactly where leaf-valid."""
    rng = np.random.default_rng(2)
    vecs, radius, iv, lv = _random_tree_pages(rng)
    b, w = 4, 6
    queries = jnp.asarray(rng.normal(size=(b, vecs.shape[-1])).astype(np.float32))
    fids = jnp.asarray(rng.integers(0, vecs.shape[0], size=(b, w)).astype(np.int32))
    dmax, score, leaf_d, dq = frontier_scores_pallas(
        fids, queries, vecs, radius, iv, lv, metric="d_inf", interpret=True)
    iv_g = np.asarray(iv)[np.asarray(fids)]
    lv_g = np.asarray(lv)[np.asarray(fids)]
    assert (np.isfinite(np.asarray(dmax)) == iv_g).all()
    assert (np.isfinite(np.asarray(score)) == iv_g).all()
    assert (np.isfinite(np.asarray(dq)) == iv_g).all()
    assert (np.isfinite(np.asarray(leaf_d)) == lv_g).all()
    # no entry is both internal and leaf
    assert not (iv_g & lv_g).any()


def test_dq_is_raw_distance():
    """dq must be the *unmodified* metric value for internal entries — the
    carry the next level reuses as d(q, parent) must match what pdist of
    the children was computed against."""
    rng = np.random.default_rng(5)
    vecs, radius, iv, lv = _random_tree_pages(rng, N=10, cap=6, dim=8)
    fids = jnp.asarray(rng.integers(0, 10, size=(3, 4)).astype(np.int32))
    queries = jnp.asarray(rng.normal(size=(3, 8)).astype(np.float32))
    dmax, score, leaf_d, dq = frontier_scores_xla(
        fids, queries, vecs, radius, iv, lv, metric="d_inf")
    r_g = np.asarray(radius)[np.maximum(np.asarray(fids), 0)]
    fin = np.isfinite(np.asarray(dq))
    np.testing.assert_array_equal(np.asarray(dmax)[fin],
                                  (np.asarray(dq) + r_g)[fin])


def test_unknown_impl_raises():
    rng = np.random.default_rng(6)
    vecs, radius, iv, lv = _random_tree_pages(rng, N=4, cap=4, dim=4)
    fids = jnp.zeros((1, 1), jnp.int32)
    queries = jnp.zeros((1, 4), jnp.float32)
    with pytest.raises(ValueError, match=r"pallas.*xla"):
        frontier_scores(fids, queries, vecs, radius, iv, lv,
                        metric="d_inf", impl="bogus")


def test_partial_prune_args_raise():
    rng = np.random.default_rng(7)
    vecs, radius, iv, lv = _random_tree_pages(rng, N=4, cap=4, dim=4)
    fids = jnp.zeros((1, 1), jnp.int32)
    queries = jnp.zeros((1, 4), jnp.float32)
    with pytest.raises(ValueError, match="pdist"):
        frontier_scores(fids, queries, vecs, radius, iv, lv,
                        metric="d_inf", impl="xla",
                        qpd=jnp.zeros((1, 1), jnp.float32))


# ---------------------------------------------------------------------------
# Blocked grid: one step scores block_slots(w, cap, dim) slots.  Widths that
# are no multiple of the block, wholly dead rows and live slots that are
# not a prefix, at the served page (cap 42, dim 20, d_inf) and a 128-d
# l2 page, with one slot a step and with the rule's block.

def _blocked_case(rng, w, cap, dim, b=3, N=50):
    vecs, radius, iv, lv = _random_tree_pages(rng, N=N, cap=cap, dim=dim)
    f = rng.integers(0, N, size=(b, w)).astype(np.int32)
    f[0] = -1                                    # wholly dead row
    f[1, rng.random(w) < 0.5] = -1               # interleaved -1
    f[1, -1] = rng.integers(0, N)                # live slot in the tail
    f[2, (w + 1) // 2:] = -1                     # live prefix
    fids = jnp.asarray(f)
    queries = jnp.asarray(rng.normal(size=(b, dim)).astype(np.float32))
    return fids, queries, vecs, radius, iv, lv


@pytest.fixture(params=["one_slot", "rule"])
def block_rule(request, monkeypatch):
    """Score with one slot a step, or with block_slots' own blocks."""
    if request.param == "one_slot":
        monkeypatch.setattr(frontier, "_MAX_BLOCK", 1)
    frontier_scores_pallas.clear_cache()
    yield request.param
    frontier_scores_pallas.clear_cache()


@pytest.mark.parametrize("prune", [False, True], ids=["plain", "parent_prune"])
@pytest.mark.parametrize("cap,dim,metric", [(42, 20, "d_inf"),
                                            (32, 128, "l2"),
                                            (8, 256, "l2"),
                                            (8, 256, "d_inf")],
                         ids=["served", "dim128", "dim256_l2",
                              "dim256_d_inf"])
@pytest.mark.parametrize("w", [1, 7, 42, 441])
def test_blocked_kernel_matches_xla_bitwise(block_rule, w, cap, dim, metric,
                                            prune):
    rng = np.random.default_rng(w + dim)
    fids, queries, vecs, radius, iv, lv = _blocked_case(rng, w, cap, dim)
    # the 20- and 128-d pages are transposed whole; the 256-d one folds
    # once along lanes first
    assert frontier.lane_stages(dim) == (dim == 256)
    g = frontier.block_slots(w, cap, dim)
    assert (g == 1) == (block_rule == "one_slot" or w == 1)
    kw = {}
    if prune:
        pdist, qpd, rq = _random_prune_inputs(rng, fids, vecs.shape[0], cap)
        kw = dict(pdist=pdist, qpd=qpd, rq=rq)
    got = frontier_scores_pallas(fids, queries, vecs, radius, iv, lv,
                                 metric=metric, interpret=True, **kw)
    want = frontier_scores_xla(fids, queries, vecs, radius, iv, lv,
                               metric=metric, **kw)
    for gv, wv, name in zip(got, want, OUT_NAMES):
        assert gv.shape == (fids.shape[0], w, cap)
        np.testing.assert_array_equal(np.asarray(gv), np.asarray(wv),
                                      err_msg=f"{block_rule}/{name}")
    assert np.isposinf(np.asarray(got[0])[0]).all()      # the dead row


@pytest.mark.parametrize("cap,dim", [(42, 20), (32, 128), (42, 2048),
                                     (4, 6), (256, 4096)])
@pytest.mark.parametrize("w", [1, 5, 42, 441, 2048])
def test_block_slots_rule(w, cap, dim):
    """1 <= G <= w; a multiple of 8 from 8 up; the two buffers of G pages
    and their per-entry rows fit the page budget (or G is 1)."""
    g = frontier.block_slots(w, cap, dim)
    assert 1 <= g <= w
    assert g < 8 or g % 8 == 0
    two_bufs = 2 * g * (frontier._vmem_bytes(cap, dim)
                        + frontier._vmem_bytes(frontier._META_ROWS, cap))
    assert g == 1 or two_bufs <= frontier._PAGE_VMEM_BYTES
    if w >= frontier._MAX_BLOCK and dim <= 128:
        assert g == frontier._MAX_BLOCK


def test_block_slots_shrinks_with_the_page():
    """The same rule gives wide pages smaller blocks: the dim-2048 page
    of capacity 42 takes fewer slots a step than the served page."""
    served = frontier.block_slots(441, 42, 20)
    wide = frontier.block_slots(441, 42, 2048)
    assert 1 <= wide < served


def test_live_blocks_counts_blocks_with_a_live_id():
    rng = np.random.default_rng(8)
    for w in (1, 7, 42, 441):
        f = rng.integers(0, 9, size=(4, w)).astype(np.int32)
        f[rng.random((4, w)) < 0.7] = -1
        f[0] = -1
        g = frontier.block_slots(w, 42, 20)
        pad = np.full((4, -w % g), -1, np.int32)
        want = (np.concatenate([f, pad], 1).reshape(4, -1, g) >= 0).any(2)
        np.testing.assert_array_equal(
            np.asarray(frontier.live_blocks(jnp.asarray(f), 42, 20)),
            want.sum(1))


# ---------------------------------------------------------------------------
# The kNN-LM datastore's page: capacity 32 at StarCoder2-3B's width, 3,072
# dims (a whole number of lane tiles, so the wrapper hands the kernel the
# tree's own pages), L2, whose fold of 3 x 2^10 coordinates carries an odd
# tail.  block_slots gives 5 slots a step, so 12 slots are two full blocks
# and a partial one.  The page folds three times along lanes (3,072 ->
# 384) before its transpose, for L2's sums as for L-infinity's max.

@pytest.mark.parametrize("metric", ["l2", "d_inf"])
@pytest.mark.parametrize("prune", [False, True], ids=["plain", "parent_prune"])
def test_knnlm_page_kernel_matches_xla_bitwise(prune, metric):
    rng = np.random.default_rng(3072)
    cap, dim, w = 32, 3072, 12
    fids, queries, vecs, radius, iv, lv = _blocked_case(rng, w, cap, dim,
                                                        N=16)
    assert frontier.block_slots(w, cap, dim) == 5
    assert frontier.lane_stages(dim) == 3
    kw = {}
    if prune:
        pdist, qpd, rq = _random_prune_inputs(rng, fids, vecs.shape[0], cap)
        kw = dict(pdist=pdist, qpd=qpd, rq=rq)
    got = frontier_scores_pallas(fids, queries, vecs, radius, iv, lv,
                                 metric=metric, interpret=True, **kw)
    want = frontier_scores_xla(fids, queries, vecs, radius, iv, lv,
                               metric=metric, **kw)
    for gv, wv, name in zip(got, want, OUT_NAMES):
        np.testing.assert_array_equal(np.asarray(gv), np.asarray(wv),
                                      err_msg=name)
    assert np.isfinite(np.asarray(got[2])).any()


@pytest.mark.parametrize("dim,m", [(20, 0), (128, 0), (784, 0), (960, 0),
                                   (256, 1), (3072, 3), (2048, 4)])
def test_lane_stages_rule(dim, m):
    """Halvings while the half is a whole number of 128-lane tiles."""
    assert frontier.lane_stages(dim) == m


def test_page_bytes_and_vmem_limit():
    """A live slot copies its tile-padded page and per-entry rows; only
    calls whose blocks outgrow the default scoped VMEM ask for more."""
    assert frontier.page_bytes(42, 20) == 48 * 128 * 4 + 8 * 128 * 4
    assert frontier.page_bytes(32, 3072) == 32 * 3072 * 4 + 8 * 128 * 4
    for w, cap, dim in ((441, 42, 20), (2048, 42, 20), (1024, 32, 3072)):
        g = frontier.block_slots(w, cap, dim)
        assert frontier._vmem_limit(w, cap, dim, g, 8, 8) is None
    g = frontier.block_slots(3072, 32, 3072)
    limit = frontier._vmem_limit(3072, 32, 3072, g, 8, 8)
    assert frontier._SCOPED_VMEM_BYTES < limit < 64 * 2**20


@pytest.mark.parametrize("prune", [False, True], ids=["plain", "parent_prune"])
@pytest.mark.parametrize("w", [42, 441])
def test_column_chunked_kernel_matches_xla_bitwise(monkeypatch, w, prune):
    """A level whose id table would overflow SMEM is scored in several
    calls over column chunks of whole blocks; the outputs are the XLA
    path's, bit for bit (here the table's budget is cut so that a few
    blocks make a chunk)."""
    monkeypatch.setattr(frontier, "_SMEM_TABLE_BYTES", 4 * 3 * 9 * 3)
    frontier_scores_pallas.clear_cache()
    rng = np.random.default_rng(w + 5)
    fids, queries, vecs, radius, iv, lv = _blocked_case(rng, w, 42, 20)
    kw = {}
    if prune:
        pdist, qpd, rq = _random_prune_inputs(rng, fids, vecs.shape[0], 42)
        kw = dict(pdist=pdist, qpd=qpd, rq=rq)
    try:
        got = frontier_scores_pallas(fids, queries, vecs, radius, iv, lv,
                                     metric="d_inf", interpret=True, **kw)
    finally:
        frontier_scores_pallas.clear_cache()
    want = frontier_scores_xla(fids, queries, vecs, radius, iv, lv,
                               metric="d_inf", **kw)
    for gv, wv, name in zip(got, want, OUT_NAMES):
        np.testing.assert_array_equal(np.asarray(gv), np.asarray(wv),
                                      err_msg=name)


@pytest.mark.parametrize("cap,dim", [(42, 20), (32, 3072)])
def test_node_page_pallas_reads_the_page(cap, dim):
    vecs = jnp.asarray(np.random.default_rng(5).standard_normal(
        (7, cap, dim)), jnp.float32)
    for node in (0, 3, 6):
        page = frontier.node_page_pallas(vecs, jnp.int32(node),
                                         interpret=True)
        np.testing.assert_array_equal(np.asarray(page),
                                      np.asarray(vecs[node]))
