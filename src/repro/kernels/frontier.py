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
in HBM.  This kernel instead keys the work on the frontier itself: the
``[b, F]`` node-id table is a *scalar-prefetch* operand
(``pltpu.PrefetchScalarGridSpec``), read from SMEM by the body, which copies
exactly the referenced node pages (``vecs``, and a per-entry page holding
the radius, pdist and validity rows) HBM→VMEM itself.  Distances and all
four outputs are computed in one VMEM-resident pass; nothing of size
``[b, F, cap, dim]`` ever exists.

Grid: ``(b, ceil(F / G))`` — one step scores a *block* of ``G`` frontier
slots of one query row (``block_slots``: at most 32, fewer where two
``G``-page buffers would not fit the page budget of scoped VMEM, never more
than ``F``).  A grid step has a fixed cost of about 0.35-0.4 us on a v5e
whatever it holds, and most frontier slots are padding (id -1: 78% of them
in clustered search), so the step is sized to the block, not the slot:

  * the step starts one async copy per *live* slot (id >= 0) of the
    *next* block into the other of two VMEM buffers, then waits for this
    block's copies and scores it — the pages of block n+1 load while
    block n computes (the grid runs in order, rows back to back);
  * a block whose ids are all -1 starts no copy, evaluates nothing and
    only writes its ``[G, cap]`` rows of +inf: the wrapper hands the
    kernel each block's largest id (a second scalar-prefetch table), so
    the test is one SMEM read; a dead slot inside a live block is skipped
    by its own id, so any -1 pattern is handled, not only the live
    prefix the top-k compaction leaves;
  * a width that is no multiple of ``G`` pads the id table with -1; the
    outputs keep their ``[b, F, cap]`` shape (the last block's rows past
    ``F`` are dropped on writeback).

A copy may only slice whole (8, 128) tiles of an HBM operand, so the
wrapper pads ``vecs`` and the per-entry page to whole tiles where the
shapes are not (one copy of the tree's pages a descent: XLA shares it
between the descent's calls); pages that are whole tiles already (a
capacity that is a multiple of 8 at a dim that is a multiple of 128, as
a kNN-LM datastore's 32 x 3,072) go to the kernel as the tree holds them,
with no copy.  The id table and the block tops are scalar-prefetched into
SMEM, so a level too wide for it is scored by several calls, each over a
column chunk of whole blocks; a call whose resident blocks outgrow the
default scoped VMEM asks for more (``_vmem_limit``).

Parent-distance pre-filter (DESIGN.md §17): when the caller supplies the
``pdist`` page (d(entry, parent routing object), maintained by every
mutation path), the per-frontier ``qpd`` vector (d(q, parent) — the
distance that admitted each frontier node, computed at the previous level)
and the per-query radius ``rq``, the body masks every entry with

    |qpd - pdist| > rq + r + _PRUNE_PAD

by the triangle inequality |d(q,p) - d(e,p)| <= d(q,e), so such an entry
provably fails the descent's d - r <= r_q + eps prune test and its
distance is never needed.  Filtered entries emit +inf.  Outputs are
bitwise identical to the unfiltered kernel — only the descent's count
of needed evaluations changes, not the work: the kernel scores every
entry of every live page, because testing a page for a kept entry first
is a vector-to-scalar read, which cost more on a v5e than the metric it
saved.

Invalid slots emit +inf rows.  The metric is the shared definition in
``core/metric.py``, folding the coordinates of each live slot's page over
sublanes, transposed (``[dim, cap]``).  A page whose coordinates halve in
whole 128-lane tiles (``lane_stages``: a kNN-LM datastore's 3,072 three
times, to 384) first takes its terms and the fold's first halvings along
lanes as stored, every lane in use, and transposes only the rest; pages
that do not (20, 128, 784, 960 dims) are transposed whole.  The halvings
are the fold's own (``core.metric.Stages``), and its fixed-association
tree-fold makes the kernel bitwise identical to the XLA path
(``frontier_scores_xla``) — asserted by tests/test_frontier_kernel.py in
interpret mode, which runs this exact kernel code on CPU CI.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.metric import get_metric, get_stages

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


# Block size rule (``block_slots``).  The two page buffers of a step
# (this block's and the prefetched next block's) may take this share of
# the compiler's default scoped VMEM on v5e (16 MiB); the rest stays with
# the pipelined query blocks and the resident output blocks.
_PAGE_VMEM_BYTES = 4 * 2**20
# largest block: of 8, 16 and 32 on a v5e at the served page, 32 ran the
# cohort descent fastest (PERF.md section 6): a dead block's step grows
# with its slots (0.26 us at 8, 0.68 us at 32) but far fewer steps remain
_MAX_BLOCK = 32
# rows of a node's per-entry page: radius, entry kind, pdist
_META_ROWS = 3
# The compiler's default scoped VMEM on a v5e.  A call whose resident
# blocks would not fit under three quarters of it (wide levels of large
# pages: a 3,072-slot leaf chunk's four [3072, cap] output rows of a
# query take 12 MiB double-buffered) asks for a larger limit, with room
# for the compiler's own scratch; every other call keeps the default.
_SCOPED_VMEM_BYTES = 16 * 2**20
_VMEM_HEADROOM = 8 * 2**20
# The frontier's id table and its block tops are scalar-prefetched into
# SMEM, 1 MiB on a v5e.  A call whose tables would pass this share of it
# (a level wider than about 3,700 slots at a cohort of 64) is split into
# calls over column chunks of whole blocks.
_SMEM_TABLE_BYTES = 960 * 2**10


# lanes of a vreg: a lane-axis slice at a multiple of this moves whole vregs
_LANES = 128


def lane_stages(dim: int) -> int:
    """Halvings of the coordinate fold the kernel runs along lanes, on the
    page as stored (``[cap, dim]``), before it transposes what is left: the
    number of leading halvings whose half is a whole number of 128-lane
    tiles (3,072 -> 1,536 -> 768 -> 384 gives 3; 20, 128, 784 and 960
    give 0, and such pages are transposed whole, as they always were)."""
    m = 0
    while dim > 0 and dim % (2 * _LANES) == 0:
        dim //= 2
        m += 1
    return m


def _vmem_bytes(rows: int, cols: int) -> int:
    """VMEM footprint of an f32 ``[rows, cols]`` tile-padded to (8, 128)."""
    return -(-rows // 8) * 8 * (-(-cols // 128) * 128) * 4


def block_slots(w: int, cap: int, dim: int) -> int:
    """Frontier slots ``G`` one grid step scores, from the shapes alone.

    The smaller of ``_MAX_BLOCK`` and what fits two ``G``-page buffers
    (page ``[cap, dim]`` plus its ``[_META_ROWS, cap]`` per-entry rows,
    each tile-padded) in ``_PAGE_VMEM_BYTES``; rounded down to a multiple
    of 8 when it is 8 or more (one sublane-aligned ``[G, cap]`` store a
    step); never more than the level's width ``w``, never less than 1."""
    per_slot = 2 * (_vmem_bytes(cap, dim) + _vmem_bytes(_META_ROWS, cap))
    g = min(_MAX_BLOCK, _PAGE_VMEM_BYTES // per_slot)
    if g >= 8:
        g -= g % 8
    return max(1, min(g, w))


def _vmem_limit(wo: int, cap: int, dim: int, g: int, qb: int,
                meta_rows: int) -> int | None:
    """Scoped VMEM a call asks for: ``None`` (the compiler's default)
    where its blocks fit in three quarters of it, else their size plus
    ``_VMEM_HEADROOM``.  Blocks: the four ``[wo, cap]`` output rows and
    the ``[qb, dim]`` query block, each double-buffered, and the two
    ``g``-page buffers of the scratch."""
    need = (2 * 4 * _vmem_bytes(wo, cap) + 2 * _vmem_bytes(qb, dim)
            + 2 * g * (_vmem_bytes(cap, dim) + _vmem_bytes(meta_rows, cap)))
    if 4 * need <= 3 * _SCOPED_VMEM_BYTES:
        return None
    return need + _VMEM_HEADROOM


def page_bytes(cap: int, dim: int) -> int:
    """Bytes the kernel copies HBM->VMEM for one live frontier slot: the
    node's page ``[cap, dim]`` and its per-entry rows, each tile-padded to
    (8, 128) as the wrapper hands them over (``_tile_pad``; the two rows
    of an unfiltered level pad to the same tile as the three of a
    filtered one)."""
    return _vmem_bytes(cap, dim) + _vmem_bytes(_META_ROWS, cap)


def _block_tops(fids, g: int):
    """[b, ceil(w / g)] i32: the largest id in each block of ``g`` slots of
    ``fids`` [b, w] (the last block padded with -1).  A block is live where
    its top is >= 0."""
    b, w = fids.shape
    nblk = -(-w // g)
    f = jnp.pad(fids, ((0, 0), (0, nblk * g - w)), constant_values=-1)
    return jnp.max(f.reshape(b, nblk, g), axis=2)


def live_blocks(fids, cap: int, dim: int):
    """[b] i32: grid steps of one ``frontier_scores_pallas`` call over
    ``fids`` [b, w] whose block of ``block_slots`` slots holds a live id
    (>= 0) — the steps that copy pages and evaluate the metric."""
    g = block_slots(fids.shape[1], cap, dim)
    return jnp.sum(_block_tops(fids, g) >= 0, axis=1, dtype=jnp.int32)


def _frontier_kernel(fids_ref, tops_ref, *refs, metric: str, prune: bool,
                     g: int, nblk: int, qb: int):
    """One grid step (i, j): score frontier slots ``j*g .. j*g+g-1`` of
    query i.

    ``vecs`` and the per-entry ``meta`` pages stay in HBM; the body copies
    the pages of the block's live slots (id >= 0) into one of two VMEM
    buffers itself, and starts the next step's copies before it scores
    this block, so they load while it computes (the grid runs in order,
    row after row).  A block whose top id (``tops_ref``, flat, one a
    block) is -1 starts no copy and evaluates nothing: it writes its
    ``[g, cap]`` rows of +inf.  The slots of a block are one rolled
    loop: unrolling it ran a v5e descent 4-6% faster but tripled the
    host's lowering time, which every process start pays.

    Each live slot's page yields the ``[1, cap]`` row the outputs want
    by a fold of its coordinates over sublanes, on the page transposed
    (``[dim, cap]``).  A page whose coordinates halve in whole lane tiles
    (``lane_stages``: ``m`` of them) takes its metric terms and the fold's
    first ``m`` halvings in the stored ``[cap, dim]`` orientation, every
    lane in use, and transposes only the ``[cap, dim >> m]`` rest; the
    halvings are the fold's own (``core.metric.Stages``), so the row is
    the same bits.  ``meta`` rows are the radius, the entry kind (1
    internal, 2 leaf, 0 neither, as f32) and pdist (prune only)."""
    if prune:
        (q_ref, rq_ref, qpd_ref, vecs_hbm, meta_hbm, dmax_ref, score_ref,
         leafd_ref, dq_ref, page_buf, meta_buf, sems) = refs
    else:
        (q_ref, vecs_hbm, meta_hbm, dmax_ref, score_ref, leafd_ref, dq_ref,
         page_buf, meta_buf, sems) = refs
    i = pl.program_id(0)
    j = pl.program_id(1)
    step = i * nblk + j
    buf = step % 2

    def copies(row, blk, s, b):
        """The page and per-entry copies of slot ``s`` of block (row, blk)
        into buffer ``b``.  Each copy signals a semaphore of its own: a
        DMA semaphore counts bytes, not copies, so a wait on a shared one
        could be met by another slot's page of the same size while this
        slot's is still in flight."""
        node = jnp.maximum(fids_ref[row, blk * g + s], 0)
        return (pltpu.make_async_copy(vecs_hbm.at[node], page_buf.at[b, s],
                                      sems.at[0, b, s]),
                pltpu.make_async_copy(meta_hbm.at[node], meta_buf.at[b, s],
                                      sems.at[1, b, s]))

    def start(row, blk, b):
        @pl.when(tops_ref[row * nblk + blk] >= 0)
        def _():
            @pl.loop(0, g)
            def _(s):
                @pl.when(fids_ref[row, blk * g + s] >= 0)
                def _():
                    for c in copies(row, blk, s, b):
                        c.start()

    @pl.when(step == 0)
    def _():
        start(i, j, buf)

    last = j == nblk - 1
    nxt_i = jnp.where(last, i + 1, i)

    @pl.when(nxt_i < pl.num_programs(0))
    def _():
        start(nxt_i, jnp.where(last, 0, j + 1), 1 - buf)

    cap, dim = dmax_ref.shape[2], q_ref.shape[1]
    base = j * g
    if g % 8 == 0:
        base = pl.multiple_of(base, 8)
    inf_rows = jnp.full((g, cap), _INF, jnp.float32)
    for ref in (dmax_ref, score_ref, leafd_ref, dq_ref):
        ref[0, pl.ds(base, g), :] = inf_rows

    m = lane_stages(dim)

    @pl.when(tops_ref[step] >= 0)
    def _():
        qrow = pl.ds(i % qb, 1)
        if m:
            q = q_ref[qrow, :]                             # [1, dim]
        else:
            qt = q_ref[qrow, :].T                          # [dim, 1]
        if prune:
            rq = rq_ref[qrow, :]                           # [1, 1]

        @pl.loop(0, g)
        def _(s):
            @pl.when(fids_ref[i, j * g + s] >= 0)
            def _():
                for c in copies(i, j, s, buf):
                    c.wait()
                meta = meta_buf[buf, s][:, :cap]           # [R, cap]
                r = meta[0:1, :]
                kind = meta[1:2, :]
                if prune:
                    # triangle-inequality pre-filter on the copied rows
                    lb = jnp.abs(qpd_ref[0, 0, j * g + s] - meta[2:3, :])
                    keep = lb <= rq + r + _PRUNE_PAD
                    kind = jnp.where(keep, kind, 0.0)
                # every live page is scored (module docstring)
                if m:
                    # the buffer's rows are whole sublane tiles and its
                    # lanes the page's own; rows past cap are cut last
                    st = get_stages(metric)
                    t = st.halve(st.terms(q, page_buf[buf, s]), 1, m)
                    d = st.reduce(t.T, 0, True)[:, :cap]   # [1, cap]
                else:
                    page = page_buf[buf, s][:cap, :dim]    # [cap, dim]
                    d = get_metric(metric)(qt, page.T, axis=0,
                                           keepdims=True)  # [1, cap]
                iv = kind == 1.0
                row = pl.ds(j * g + s, 1)
                dmax_ref[0, row, :] = jnp.where(iv, d + r, _INF)
                score_ref[0, row, :] = jnp.where(iv, d - r, _INF)
                leafd_ref[0, row, :] = jnp.where(kind == 2.0, d, _INF)
                dq_ref[0, row, :] = jnp.where(iv, d, _INF)


def _tile_pad(x):
    """``x`` with its last two dims zero-padded to whole (8, 128) tiles: a
    copy may only slice whole tiles out of an HBM operand."""
    pad = ((0, -x.shape[-2] % 8), (0, -x.shape[-1] % 128))
    if not any(p for _, p in pad):
        return x
    return jnp.pad(x, ((0, 0),) * (x.ndim - 2) + pad)


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
    _, cap, dim = vecs.shape
    g = block_slots(w, cap, dim)
    # one [R, cap] per-entry page per node, so a slot needs two copies
    kind = ((internal_valid != 0).astype(jnp.float32)
            + 2.0 * (leaf_valid != 0).astype(jnp.float32))
    rows = (radius, kind, pdist) if prune else (radius, kind)
    meta = _tile_pad(jnp.stack([x.astype(jnp.float32) for x in rows],
                               axis=1))
    vecs = _tile_pad(vecs)
    # a level whose id table would not fit SMEM is scored in column chunks
    # of whole blocks, each its own call (the slots are independent)
    wc = g * max(1, _SMEM_TABLE_BYTES // (4 * b * (g + 1)))
    outs = [_score_columns(fids[:, c0:c0 + wc], queries, vecs, meta,
                           qpd[:, c0:c0 + wc] if prune else None, rq,
                           cap=cap, g=g, metric=metric, interpret=interpret)
            for c0 in range(0, w, wc)]
    return tuple(o[0] if len(outs) == 1 else jnp.concatenate(o, axis=1)
                 for o in zip(*outs))


def _score_columns(fids, queries, vecs, meta, qpd, rq, *, cap: int, g: int,
                   metric: str, interpret: bool):
    """One ``pallas_call`` over the frontier slots ``fids`` [b, w] in
    blocks of ``g`` (of ``cap`` entries a node); ``vecs`` and ``meta`` are
    tile-padded, ``qpd`` and ``rq`` given with the parent filter (else
    None)."""
    prune = qpd is not None
    b, w = fids.shape
    dim = queries.shape[1]
    g = min(g, w)
    nblk = -(-w // g)
    wp = nblk * g
    # the outputs' resident block of a query: the whole row, or (past a
    # partial last block) a sublane-aligned block the writeback clips
    wo = w if wp == w else -(-wp // 8) * 8
    # the last block's tail slots are empty: id -1, qpd +inf; the block
    # tops go flat into SMEM (a 2-D SMEM array pads its rows to 128 words)
    tops = _block_tops(fids, g).reshape(-1)
    fids = jnp.pad(fids, ((0, 0), (0, wp - w)), constant_values=-1)
    # 8-row blocks satisfy the TPU's sublane rule; a smaller array is one
    # whole block (a block dim equal to the array dim is always legal)
    qb = min(b, 8)

    q_block = lambda cols: pl.BlockSpec((qb, cols),
                                        lambda i, j, *_: (i // qb, 0))
    hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    if prune:
        qpd = jnp.pad(qpd, ((0, 0), (0, wp - w)), constant_values=_INF)
        in_specs = [q_block(dim), q_block(1),
                    pl.BlockSpec((1, 1, wp), lambda i, j, *_: (i, 0, 0),
                                 memory_space=pltpu.SMEM),
                    hbm, hbm]
        operands = (fids, tops, queries, rq[:, None], qpd[:, None], vecs,
                    meta)
    else:
        in_specs = [q_block(dim), hbm, hbm]
        operands = (fids, tops, queries, vecs, meta)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, nblk),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((1, wo, cap), lambda i, j, *_: (i, 0, 0))] * 4,
        scratch_shapes=[
            pltpu.VMEM((2, g) + vecs.shape[1:], jnp.float32),
            pltpu.VMEM((2, g) + meta.shape[1:], jnp.float32),
            pltpu.SemaphoreType.DMA((2, 2, g)),
        ],
    )
    out_shape = [jax.ShapeDtypeStruct((b, w, cap), jnp.float32)] * 4
    return pl.pallas_call(
        functools.partial(_frontier_kernel, metric=metric, prune=prune,
                          g=g, nblk=nblk, qb=qb),
        grid_spec=grid_spec,
        out_shape=out_shape,
        # the copies of the next step start in this one: the grid runs in
        # order on both axes
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(wo, cap, dim, g, qb,
                                         meta.shape[1])),
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
    full reduction shape, so this buys parity and the needed-eval counters, not
    wall-clock (DESIGN.md §17)."""
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


def node_page_pallas(vecs, node, *, interpret: bool = False):
    """``vecs[node]`` ([cap, dim]) by one Pallas block copy, ``node`` an
    in-range i32 scalar.

    A Mosaic call takes its operands in the array's own row-major layout,
    so a loop that reads pages this way keeps the tree's pages as they
    are.  Read by plain indexing inside a loop, XLA lays the loop-carried
    pages out for the row's coordinate fold instead (capacity-minor, the
    capacity padded to 128 lanes) and copies every page into that layout
    on entry: 29.5 GB for a kNN-LM datastore's 18,726 x 32 x 3,072."""
    _, cap, dim = vecs.shape

    def body(node_ref, page_ref, out_ref):
        out_ref[...] = page_ref[...]

    return pl.pallas_call(
        body,
        out_shape=jax.ShapeDtypeStruct((cap, dim), vecs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(1,),
            in_specs=[pl.BlockSpec((None, cap, dim),
                                   lambda i, n: (n[0], 0, 0))],
            out_specs=pl.BlockSpec((cap, dim), lambda i, n: (0, 0))),
        interpret=interpret,
    )(jnp.reshape(node, (1,)).astype(jnp.int32), vecs)
