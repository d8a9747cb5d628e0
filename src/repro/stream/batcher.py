"""Mutation batch executor: conflict-free cohorts + one fused device scan.

The write path mirrors what PR 2 did for reads: where the query cohort
amortised descent over a batch of queries, the mutation batcher amortises
*dispatch* over a batch of edits.  A mixed insert/delete log is cut into
**conflict-free cohorts** — maximal runs in which no object id repeats —
and each cohort is applied by ``core.smtree.apply_mutations``: one jitted
``lax.scan`` over the (donation-friendly) ``TreeArrays``, one device
round-trip per cohort instead of one per mutation.

Rows the jitted fast paths cannot absorb (leaf overflow on insert, min-fill
underflow on delete) are **escalated** to the host control plane
(``core.engine._HostView`` — the same split/merge code the one-at-a-time
engine uses) after their cohort's scan, still in log order.  Because a
cohort never contains two ops on the same id, the scan-then-escalate
reordering is invisible: ops within a cohort touch disjoint objects, so any
serialisation of {applied-in-scan} before {escalated} is equivalent to the
original log order, and — critically for the WAL contract — *replaying the
same batches through the same code yields bitwise-identical trees*.

Cohorts are padded to power-of-two lengths so the jit cache stays small
(one entry per bucket per tree geometry).
"""
from __future__ import annotations

import dataclasses

import jax
import numpy as np

from repro import obs
from repro.core import smtree
from repro.core.smtree import (OP_DELETE, OP_INSERT, OP_NOP, ST_APPLIED,
                               ST_MERGE, ST_NOTFOUND, ST_OVERFLOW, ST_SPLIT,
                               ST_UNDERFLOW, TreeArrays)

__all__ = ["MutationBatcher", "BatchResult", "cut_cohorts", "pad_to_bucket",
           "check_oids", "escalate_rows",
           "OP_INSERT", "OP_DELETE", "OP_NOP"]


@dataclasses.dataclass
class BatchResult:
    statuses: np.ndarray      # [B] int32 — final per-row outcome (ST_*)
    n_fast: int               # rows absorbed by the jitted scan
    n_escalated: int          # rows resolved by the host control plane
    n_cohorts: int
    n_split: int = 0          # rows resolved by the on-device split pass
    n_merge: int = 0          # rows resolved by the on-device merge pass


def check_oids(oids: np.ndarray) -> None:
    """Boundary validation for mutation logs: negative object ids are
    reserved (the batcher pads cohorts with the oid = -1 NOP sentinel, and
    the jitted paths treat negatives as never-matching), so they must be
    rejected before a batch is WAL-framed or applied."""
    oids = np.asarray(oids)
    if len(oids) and int(oids.min()) < 0:
        raise ValueError(
            "negative object ids are reserved (NOP pad sentinel); got "
            f"min oid {int(oids.min())}")


def cut_cohorts(oids: np.ndarray) -> list[tuple[int, int]]:
    """Cut a log into maximal conflict-free [start, end) runs.

    A new cohort starts exactly when the incoming row's oid already appears
    in the current one, so within a cohort every id is unique and ops
    commute across the scan/escalation boundary."""
    cuts: list[tuple[int, int]] = []
    start = 0
    seen: set[int] = set()
    for i, oid in enumerate(oids):
        o = int(oid)
        if o in seen:
            cuts.append((start, i))
            start = i
            seen = set()
        seen.add(o)
    if len(oids) or not cuts:
        cuts.append((start, len(oids)))
    return cuts


def pad_to_bucket(n: int, cap: int) -> int:
    """Next power of two ≥ n, clamped to [1, cap] — bounds jit cache size."""
    b = 1
    while b < n and b < cap:
        b <<= 1
    return min(b, cap)


def escalate_rows(tree: TreeArrays, statuses: np.ndarray, ops, xs,
                  oids) -> TreeArrays:
    """Host control plane for the rows the device could not absorb.

    Overflow rows (multi-level / root splits, exhausted free ring) are
    processed before underflow rows, each group in log order.  The ordering
    is load-bearing for the device-split transparency property: the on-device
    split pass handles a log-order *prefix* of a cohort's overflow rows, so
    running the overflow remainder first keeps the total split order
    identical whether device splits are on or off — within a conflict-free
    cohort the two groups touch disjoint objects, so the reorder is
    semantically invisible.  Mutates ``statuses`` in place; returns the
    updated tree."""
    rows = [i for i, st in enumerate(statuses) if st == ST_OVERFLOW]
    rows += [i for i, st in enumerate(statuses) if st == ST_UNDERFLOW]
    if not rows:
        return tree
    from repro.core.engine import _HostView
    hv = _HostView(tree)        # the whole tree, read to the host
    smtree.count_host_sync()
    for i in rows:
        if ops[i] == OP_INSERT:
            hv.insert_with_split(np.asarray(xs[i], np.float32),
                                 int(oids[i]))
            statuses[i] = ST_APPLIED
        else:
            ok = hv.delete_with_merge(np.asarray(xs[i], np.float32),
                                      int(oids[i]))
            statuses[i] = ST_APPLIED if ok else ST_NOTFOUND
    return hv.to_tree()


class MutationBatcher:
    """Applies mutation logs to one ``TreeArrays`` (single tree / one forest
    shard).  Owns the tree between calls; read it back via ``.tree``.

    ``donate=True`` donates the carried tree's buffers to each scan (saves
    one tree of memory on accelerators) — only safe when no other reference
    to the tree is live, which epoch publication violates: a pinned epoch
    (stream/epoch.py) holds the same arrays the next batch would consume.
    The stream pipeline therefore leaves donation off.

    ``device_splits=False`` disables the on-device split pass (every
    overflow escalates to the host, the PR-3 behaviour) and
    ``device_merges=False`` the on-device merge pass (every underflow
    escalates, the PR-4 behaviour) — kept as benchmark baselines and the
    bitwise-transparency test references."""

    def __init__(self, tree: TreeArrays, *, max_batch: int = 4096,
                 donate: bool = False, device_splits: bool = True,
                 device_merges: bool = True):
        self.tree = tree
        self.max_batch = int(max_batch)
        self.donate = donate
        self.device_splits = device_splits
        self.device_merges = device_merges

    # -- host escalation ---------------------------------------------------
    def _escalate(self, statuses: np.ndarray, ops, xs, oids) -> np.ndarray:
        with obs.child_span("mutation.escalate"):
            self.tree = escalate_rows(self.tree, statuses, ops, xs, oids)
        return statuses

    # -- public API --------------------------------------------------------
    def apply(self, ops, xs, oids) -> BatchResult:
        """Apply a mutation log in order.  ops [B] (OP_*), xs [B, dim],
        oids [B] (non-negative).  Returns per-row statuses; the updated
        tree is ``self.tree``."""
        ops = np.asarray(ops, np.int32)
        xs = np.asarray(xs, np.float32)
        oids = np.asarray(oids, np.int32)
        assert ops.shape == oids.shape == xs.shape[:1], \
            (ops.shape, oids.shape, xs.shape)
        check_oids(oids)
        statuses = np.zeros(len(ops), np.int32)
        n_fast = n_esc = n_split = n_merge = 0
        cohorts = cut_cohorts(oids)
        for start, end in cohorts:
            for cs in range(start, end, self.max_batch):
                ce = min(cs + self.max_batch, end)
                st = self._apply_cohort(ops[cs:ce], xs[cs:ce], oids[cs:ce])
                n_esc += int(np.isin(st, (ST_OVERFLOW, ST_UNDERFLOW)).sum())
                n_fast += int((st == ST_APPLIED).sum())
                n_split += int((st == ST_SPLIT).sum())
                n_merge += int((st == ST_MERGE).sum())
                st[np.isin(st, (ST_SPLIT, ST_MERGE))] = ST_APPLIED
                statuses[cs:ce] = self._escalate(st, ops[cs:ce], xs[cs:ce],
                                                 oids[cs:ce])
        return BatchResult(statuses, n_fast, n_esc, len(cohorts), n_split,
                           n_merge)

    def _apply_cohort(self, ops, xs, oids) -> np.ndarray:
        n = len(ops)
        bucket = pad_to_bucket(n, self.max_batch)
        if bucket != n:
            pad = bucket - n
            ops = np.concatenate([ops, np.full(pad, OP_NOP, np.int32)])
            oids = np.concatenate([oids, np.full(pad, -1, np.int32)])
            xs = np.concatenate([xs, np.zeros((pad, xs.shape[1]),
                                              np.float32)])
        tree, st = smtree.apply_mutations(self.tree, ops, xs, oids,
                                          donate=self.donate,
                                          splits=self.device_splits,
                                          merges=self.device_merges)
        with obs.child_span("mutation.status_read"):
            st = np.array(jax.device_get(st[:n]))  # copy: escalation mutates
            smtree.count_host_sync()
        self.tree = tree
        return st
