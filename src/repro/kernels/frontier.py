"""Pallas TPU kernel: fused frontier scoring for the SM-tree cohort descent.

Every level of the level-synchronous kNN descent must evaluate the metric
between each query of the cohort and every entry of every node on that
query's frontier, then derive four per-entry quantities (DESIGN.md §8/§17):

  * ``dmax``   = d + r          for valid internal entries (the d_max bound:
                                 each subtree holds an object within d + r)
  * ``score``  = d - r          for valid internal entries (the triangle-
                                 inequality prune test / closest-first key)
  * ``leaf_d`` = d              for valid leaf entries (exact candidates)
  * ``dq``     = d              for valid internal entries — the raw
                                 query-to-routing-object distance the descent
                                 carries to the next level as ``d(q, parent)``

XLA expresses this as a ``[b, F, cap, dim]`` gather followed by the metric
reduction — one full materialisation of every touched node page *per query*
in HBM.  This kernel instead keys the pipeline on the frontier itself: the
``[b, F]`` node-id table is a *scalar-prefetch* operand
(``pltpu.PrefetchScalarGridSpec``), so the BlockSpec index maps read the ids
before the body runs and the Pallas pipeline streams exactly the referenced
node pages (``vecs``, and the 8-row blocks of ``radius``/``pdist``/validity
holding the node's row) HBM→VMEM, double-buffered across grid steps.  Distances and all four outputs are
computed in one VMEM-resident pass; nothing of size ``[b, F, cap, dim]``
ever exists.

Parent-distance pre-filter (DESIGN.md §17): when the caller supplies the
``pdist`` page (d(entry, parent routing object), maintained by every
mutation path), the per-frontier ``qpd`` vector (d(q, parent) — the
distance that admitted each frontier node, computed at the previous level)
and the per-query radius ``rq``, the prologue drops every entry with

    |qpd - pdist| > rq + r + _PRUNE_PAD

*before* the metric eval: by the triangle inequality
|d(q,p) - d(e,p)| <= d(q,e), so such an entry provably fails the descent's
d - r <= r_q + eps prune test and its distance never needed computing.
Filtered entries emit +inf, and a node whose entries are all filtered
skips the reduction entirely (``pl.when``).  Outputs are bitwise identical
to the unfiltered kernel — only the evaluation count changes.

Grid: ``(b, F)`` — one step per (query, frontier-slot) pair.  Invalid slots
(node id < 0, the frontier padding) emit +inf rows; the metric itself is the
shared definition in ``core/metric.py`` whose fixed-association tree-fold
makes the kernel bitwise identical to the XLA path (``frontier_scores_xla``)
— asserted by tests/test_frontier_kernel.py in interpret mode, which runs
this exact kernel code on CPU CI.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.metric import get_metric

# python literal (not a jnp scalar): kernels may not capture traced consts
_INF = float("inf")

# Filter slack: _EPS (1e-5, the descent's prune-test pad in core/smtree.py)
# plus another 1e-5 absorbing f32 rounding of the triangle lower bound
# (|d(q,p) - pdist| is computed from two independently rounded f32
# distances; the true d(q,e) can undershoot it by a few ulps).  An entry
# filtered at rq + r + _PRUNE_PAD therefore has d - r > rq + _EPS and
# would have been discarded by the prune test anyway — the derivation and
# the exact-boundary tests live in DESIGN.md §17 /
# tests/test_frontier_kernel.py.
_PRUNE_PAD = 2e-5

_IMPLS = ("pallas", "xla")


def _frontier_kernel(fids_ref, *refs, metric: str, prune: bool, qb: int,
                     nb: int):
    """One grid step (i, j): score frontier slot j of query i.

    Operands keep their HBM shapes; the TPU's (8, 128) block rule is met by
    whole-row blocks: queries/rq/qpd arrive as the ``qb``-row block holding
    query i, and each ``[N, cap]`` per-entry array as the ``nb``-row block
    holding node ``fids[i, j]`` — the kernel picks its row with a dynamic
    sublane slice.  The outputs' ``[w, cap]`` block of query i stays
    resident across the j steps and is written one row at a time.

    The page is scored transposed (``[dim, cap]``): the metric then folds
    the coordinates over sublanes and yields the ``[1, cap]`` row the
    outputs want, with no lane slicing.  Masks are i32 rows (1 internal,
    2 leaf, 0 neither), compared only at the final selects."""
    if prune:
        (q_ref, qpd_ref, rq_ref, vecs_ref, rad_ref, pd_ref, ival_ref,
         lval_ref, dmax_ref, score_ref, leafd_ref, dq_ref) = refs
    else:
        (q_ref, vecs_ref, rad_ref, ival_ref, lval_ref,
         dmax_ref, score_ref, leafd_ref, dq_ref) = refs
    i = pl.program_id(0)
    j = pl.program_id(1)
    fid = fids_ref[i, j]
    qrow = pl.ds(i % qb, 1)
    nrow = pl.ds(jnp.maximum(fid, 0) % nb, 1)
    r = rad_ref[nrow, :]                                   # [1, cap]
    kind = ival_ref[nrow, :] + 2 * lval_ref[nrow, :]       # [1, cap] i32
    kind = jnp.where(fid >= 0, kind, 0)
    if prune:
        # triangle-inequality pre-filter on the already-resident rows — no
        # metric eval yet.  Invalid slots carry qpd = +inf, so nothing is
        # kept there and the whole page is skipped.
        w = qpd_ref.shape[1]
        slot = jax.lax.broadcasted_iota(jnp.int32, (1, w), 1) == j
        qpd = jnp.max(jnp.where(slot, qpd_ref[qrow, :], -_INF),
                      axis=1, keepdims=True)               # [1, 1]
        lb = jnp.abs(qpd - pd_ref[nrow, :])
        keep = lb <= rq_ref[qrow, :] + r + _PRUNE_PAD
        kind = jnp.where(keep, kind, 0)
    out_row = pl.ds(j, 1)
    any_live = jnp.max(kind) > 0

    @pl.when(any_live)
    def _():
        q = q_ref[qrow, :]                                 # [1, dim]
        page = vecs_ref[0]                                 # [cap, dim]
        d = get_metric(metric)(q.T, page.T, axis=0, keepdims=True)  # [1, cap]
        iv = kind == 1
        dmax_ref[0, out_row, :] = jnp.where(iv, d + r, _INF)
        score_ref[0, out_row, :] = jnp.where(iv, d - r, _INF)
        leafd_ref[0, out_row, :] = jnp.where(kind == 2, d, _INF)
        dq_ref[0, out_row, :] = jnp.where(iv, d, _INF)

    @pl.when(jnp.logical_not(any_live))
    def _():
        inf_row = jnp.full_like(r, _INF)
        for ref in (dmax_ref, score_ref, leafd_ref, dq_ref):
            ref[0, out_row, :] = inf_row


def _check_prune_args(pdist, qpd, rq):
    given = [x is not None for x in (pdist, qpd, rq)]
    if any(given) and not all(given):
        raise ValueError("parent-distance filtering needs all of "
                         "pdist, qpd and rq (or none of them)")
    return all(given)


@functools.partial(jax.jit, static_argnames=("metric", "interpret"))
def frontier_scores_pallas(fids, queries, vecs, radius, internal_valid,
                           leaf_valid, *, metric: str, interpret: bool = False,
                           pdist=None, qpd=None, rq=None):
    """Fused frontier scoring.

    fids           [b, F] i32  — frontier node ids (-1 = empty slot)
    queries        [b, dim] f32
    vecs           [N, cap, dim] f32 — node pages (entry reference values)
    radius         [N, cap] f32      — entry covering radii
    internal_valid [N, cap] — nonzero where a valid internal entry
    leaf_valid     [N, cap] — nonzero where a valid leaf entry

    Optional parent-distance filter inputs (all three or none):

    pdist          [N, cap] f32 — d(entry, parent routing object) pages
    qpd            [b, F] f32   — d(q, parent routing object) per frontier
                                  slot (+inf at empty slots)
    rq             [b] f32      — current query radius (pre-level value of
                                  min(topk_d[k-1], r_cap, ub))

    Returns (dmax, score, leaf_d, dq), each [b, F, cap] f32 with +inf at
    masked/filtered positions.  ``interpret=True`` runs the identical
    kernel through the Pallas interpreter (the CPU CI path).
    """
    prune = _check_prune_args(pdist, qpd, rq)
    b, w = fids.shape
    n, cap, dim = vecs.shape
    # 8-row blocks satisfy the TPU's sublane rule; a smaller array is one
    # whole block (a block dim equal to the array dim is always legal)
    qb, nb = min(b, 8), min(n, 8)
    internal_valid = internal_valid.astype(jnp.int32)
    leaf_valid = leaf_valid.astype(jnp.int32)

    q_block = lambda cols: pl.BlockSpec((qb, cols), lambda i, j, f: (i // qb, 0))
    node_block = pl.BlockSpec(
        (nb, cap), lambda i, j, f: (jnp.maximum(f[i, j], 0) // nb, 0))
    page = pl.BlockSpec((1, cap, dim),
                        lambda i, j, f: (jnp.maximum(f[i, j], 0), 0, 0))
    if prune:
        in_specs = [q_block(dim), q_block(w), q_block(1), page, node_block,
                    node_block, node_block, node_block]
        operands = (fids, queries, qpd, rq[:, None], vecs, radius, pdist,
                    internal_valid, leaf_valid)
    else:
        in_specs = [q_block(dim), page, node_block, node_block, node_block]
        operands = (fids, queries, vecs, radius, internal_valid, leaf_valid)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, w),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((1, w, cap), lambda i, j, f: (i, 0, 0))] * 4,
    )
    out_shape = [jax.ShapeDtypeStruct((b, w, cap), jnp.float32)] * 4
    return pl.pallas_call(
        functools.partial(_frontier_kernel, metric=metric, prune=prune,
                          qb=qb, nb=nb),
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(*operands)


@functools.partial(jax.jit, static_argnames=("metric",))
def frontier_scores_xla(fids, queries, vecs, radius, internal_valid,
                        leaf_valid, *, metric: str,
                        pdist=None, qpd=None, rq=None):
    """Reference/escape-hatch implementation: the gather the kernel avoids.

    Materialises the [b, F, cap, dim] entry gather and reduces with the same
    shared metric definition — bitwise identical outputs to the kernel: the
    tree-fold + rounding pins in core/metric.py fix the value up to op
    rounding, and jitting keeps both paths whole-program-compiled (eager
    per-op execution rounds sqrt/fusions differently on CPU).

    The parent-distance filter (pdist/qpd/rq — see frontier_scores_pallas)
    applies the identical keep mask and zeroes filtered rows via jnp.where
    *before* the metric eval; on XLA:CPU the compiler still schedules the
    full reduction shape, so this buys parity and honest eval counters, not
    wall-clock (DESIGN.md §17 — the lane skip is a kernel-path win)."""
    prune = _check_prune_args(pdist, qpd, rq)
    nodes = jnp.maximum(fids, 0)
    ok = (fids >= 0)[:, :, None]
    r = radius[nodes]
    iv = (internal_valid[nodes] != 0) & ok
    lv = (leaf_valid[nodes] != 0) & ok
    e = vecs[nodes]
    if prune:
        lb = jnp.abs(qpd[:, :, None] - pdist[nodes])
        keep = lb <= rq[:, None, None] + r + _PRUNE_PAD
        iv = iv & keep
        lv = lv & keep
        e = jnp.where((iv | lv)[..., None], e, 0.0)
    d = get_metric(metric)(queries[:, None, None, :], e)
    return (jnp.where(iv, d + r, _INF),
            jnp.where(iv, d - r, _INF),
            jnp.where(lv, d, _INF),
            jnp.where(iv, d, _INF))


def frontier_scores(fids, queries, vecs, radius, internal_valid, leaf_valid,
                    *, metric: str, impl: str, interpret: bool = False,
                    pdist=None, qpd=None, rq=None):
    """Dispatch one level's frontier scoring to a backend by name.

    ``impl`` must name a scoring backend exactly — 'pallas' (the fused
    kernel; interpret-mode off-TPU) or 'xla' (the gather path).  Anything
    else raises ``ValueError`` naming the valid set rather than silently
    picking a default ('perquery' and 'auto' are descent-level toggles,
    resolved before this point by core/smtree._resolve_impl)."""
    if impl not in _IMPLS:
        raise ValueError(
            f"frontier_scores impl must be one of {_IMPLS}; got {impl!r}")
    if impl == "pallas":
        return frontier_scores_pallas(
            fids, queries, vecs, radius, internal_valid, leaf_valid,
            metric=metric, interpret=interpret,
            pdist=pdist, qpd=qpd, rq=rq)
    return frontier_scores_xla(
        fids, queries, vecs, radius, internal_valid, leaf_valid,
        metric=metric, pdist=pdist, qpd=qpd, rq=rq)
