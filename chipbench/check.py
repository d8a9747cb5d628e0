"""The comparison that decides ``correct``.

Queries: every answer of the window is held to the plain reference (brute
force over the live set at the epoch that answered it):

* ``unanswered``      queries due in the window that failed or never came
                      back;
* ``bad_ids``         served ids that name no live object at that epoch,
                      or repeat within one answer;
* ``answer_dist_gap`` the widest gap between a served distance and the
                      reference's distance of the same rank, relative
                      above 1 (the two sum in different orders);
* ``answer_id_gap``   the widest gap between a served distance and the
                      true distance of the id it is served with.

Together the two gaps say that the served ids are a k-nearest set: their
distances are the k smallest, and they are the ids' own.  A tie at the
k-th distance may be broken either way.

Writes (cells with a writer), on the final tree and again on an engine
restored from the initial tree and the WAL alone:

* ``lost_writes``, ``replay_lost_writes``: objects that an acknowledged
  batch inserted and are missing or carry another vector, objects it
  deleted and are still there, and objects present twice or never written;
* ``replay_mismatch``: tree arrays of the restored engine that differ from
  the final tree's.
"""
from __future__ import annotations

import numpy as np

TREE_ARRAYS = ("vecs", "radius", "pdist", "child", "oid", "valid", "count",
               "is_leaf", "alive", "parent", "pslot", "root", "n_nodes",
               "height", "free_list", "free_head")


def reference_inputs(cell, rec):
    """(pool, live masks by epoch) of a window: row = oid."""
    if cell.writer() is None:
        corpus = cell.corpus()
        return corpus, [np.ones(len(corpus), bool)]
    stream = cell.stream(rec.seed)
    return stream.pool(rec.n_applied), stream.live(rec.n_applied)


def _gap(a, b):
    """|a - b| / max(1, |b|); 0 where both are +inf, +inf where one is."""
    both = np.isinf(a) & np.isinf(b)
    with np.errstate(invalid="ignore"):
        g = np.abs(a - b) / np.maximum(1.0, np.abs(b))
    g = np.where(both, 0.0, g)
    return np.where(np.isnan(g), np.inf, g)


def answer_numbers(rec, ref, lives, k: int, dists=None, ids=None,
                   answer=None) -> dict:
    """The query numbers above.  ``dists``/``ids`` default to what the
    window served; ``answer(Q, live, k)`` computes answers in the program's
    place (the lower-precision control)."""
    dists = rec.dists if dists is None else dists
    ids = rec.ids if ids is None else ids
    ok = ~rec.failed
    out = {"unanswered": int(np.sum(rec.failed)), "bad_ids": 0,
           "answer_dist_gap": 0.0, "answer_id_gap": 0.0}
    for e in np.unique(rec.epoch[ok]):
        rows = np.nonzero(ok & (rec.epoch == e))[0]
        if not 0 <= e < len(lives):
            out["bad_ids"] += len(rows) * k       # an epoch never published
            continue
        live = lives[e]
        Q = rec.queries[rows]
        rd, _ = ref.knn(Q, live, k)
        if answer is not None:
            sd, si = answer(Q, live, k)
        else:
            sd, si = dists[rows], ids[rows]
        served = si >= 0
        known = served & (si < len(live))
        is_live = known & live[np.where(known, si, 0)]
        dup = np.zeros_like(served)
        srt = np.sort(np.where(served, si, -1 - np.arange(k)), axis=1)
        dup[:, 1:] = srt[:, 1:] == srt[:, :-1]
        out["bad_ids"] += int(np.sum(served & ~is_live) + np.sum(dup))
        out["answer_dist_gap"] = max(out["answer_dist_gap"],
                                     float(np.max(_gap(sd, rd))))
        td = ref.dist(Q, si)
        out["answer_id_gap"] = max(
            out["answer_id_gap"],
            float(np.max(np.where(served, _gap(sd, td), 0.0))))
    return out


def lost_writes(tree, pool: np.ndarray, live: np.ndarray) -> int:
    """Objects of ``tree`` that break the live set ``live`` over ``pool``."""
    alive = np.asarray(tree.alive)
    m = (alive & np.asarray(tree.is_leaf))[:, None] & np.asarray(tree.valid)
    present = np.asarray(tree.oid)[m]
    vecs = np.asarray(tree.vecs)[m]
    _, counts = np.unique(present, return_counts=True)
    bad = int(np.sum(counts > 1))
    known = (present >= 0) & (present < len(live))
    bad += int(np.sum(~known))
    p, v = present[known], vecs[known]
    should = live[p]
    bad += int(np.sum(~should))                       # deleted, still there
    bad += int(np.sum(should & ~np.all(v == pool[p], axis=1)))
    found = np.zeros(len(live), bool)
    found[p] = True
    bad += int(np.sum(live & ~found))                 # written, missing
    return bad


def write_numbers(cell, rec, pool, lives) -> dict:
    """Acknowledged writes on the final tree and on a WAL-only restore."""
    from repro.stream import StreamingEngine
    from repro.stream.wal import KIND_BATCH, iter_wal

    final = rec.engine.epochs.current()[1]
    live = lives[rec.n_applied]
    out = {"lost_writes": lost_writes(final, pool, live) + rec.batch_errors}
    restored = StreamingEngine(cell.tree0)
    for r in iter_wal(str(rec.wal_dir)):
        if r.kind == KIND_BATCH:
            restored.apply(r.ops.astype(np.int32), r.xs, r.oids, log=False)
    out["replay_lost_writes"] = lost_writes(restored.tree, pool, live)
    out["replay_mismatch"] = sum(
        not np.array_equal(np.asarray(getattr(final, f)),
                           np.asarray(getattr(restored.tree, f)))
        for f in TREE_ARRAYS) + int(final.max_nodes
                                    != restored.tree.max_nodes)
    return out


def compare(numbers: dict, limits: dict) -> tuple[bool, list]:
    """(correct, [(name, value, limit)]) — every number at or under its
    limit; a number without a limit is a harness fault."""
    rows = [(name, value, limits[name]) for name, value in numbers.items()]
    return all(v <= lim for _, v, lim in rows), rows


def numbers(cell, rec) -> dict:
    """Every number of a window: the writes while the program's state is
    held, then (state dropped) the answers."""
    pool, lives = reference_inputs(cell, rec)
    out = {}
    if cell.writer() is not None:
        out.update(write_numbers(cell, rec, pool, lives))
    rec.engine = None
    ref = cell.reference.Reference(pool)
    out.update(answer_numbers(rec, ref, lives, cell.cfg["k"]))
    return out


def control_numbers(cell, rec, dtype) -> dict:
    """The query numbers of the control: the plain reference computed in
    ``dtype`` put in the program's place, over the window's queries at the
    epochs that answered them."""
    pool, lives = reference_inputs(cell, rec)
    ref = cell.reference.Reference(pool)
    low = cell.reference.Reference(pool, dtype=dtype)
    return answer_numbers(rec, ref, lives, cell.cfg["k"], answer=low.knn)
