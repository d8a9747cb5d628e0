"""The churn stream: a function of the seed, drawn lazily in order, with
the live set held at the corpus size."""
import numpy as np

from chipbench.harness import load_module

stream_mod = load_module("data", "anchor_churn")
ref_mod = load_module("reference", "d_inf")
WRITER = {"batch_ops": 64, "per_anchor": 8, "insert_sigma": 0.01}


def corpus():
    return np.random.default_rng(0).random((3000, 6)).astype(np.float32)


def test_batches_depend_on_the_seed_not_on_the_order_asked():
    a = stream_mod.Stream(corpus(), WRITER, 2**31 + 9, ref_mod.pairwise)
    b = stream_mod.Stream(corpus(), WRITER, 2**31 + 9, ref_mod.pairwise)
    late = a.batch(30)
    for i in range(31):
        b.batch(i)
    for x, y in zip(late, b.batch(30)):
        np.testing.assert_array_equal(x, y)


def test_live_set_stays_at_the_corpus_size_past_the_corpus():
    """More batches than the corpus holds deletes: victims come from the
    fresh objects too."""
    s = stream_mod.Stream(corpus(), WRITER, 5, ref_mod.pairwise)
    n_batches = 3000 // 32 + 20
    lives = s.live(n_batches)
    assert all(int(m.sum()) == 3000 for m in lives)
    assert len(s.pool(n_batches)) == len(lives[-1])
    for b in range(n_batches):
        ops, xs, oids = s.batch(b)
        assert len(set(oids.tolist())) == len(oids)
        dels = oids[ops == stream_mod.OP_DELETE]
        assert lives[b][dels].all() and not lives[b + 1][dels].any()
        np.testing.assert_array_equal(xs, s.pool(n_batches)[oids])


def test_victims_are_the_anchors_nearest():
    s = stream_mod.Stream(corpus(), WRITER, 7, ref_mod.pairwise)
    ops, xs, oids = s.batch(0)
    dels = np.sort(oids[ops == stream_mod.OP_DELETE])
    # each delete anchor loses its per_anchor nearest live objects, so the
    # victims sit in clouds: most are within a small radius of another
    d = ref_mod.pairwise(corpus()[dels], corpus()[dels])
    np.fill_diagonal(d, np.inf)
    assert np.median(d.min(1)) < np.median(
        ref_mod.pairwise(corpus()[:64], corpus()[64:128]).min(1))
