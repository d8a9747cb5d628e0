"""Pallas TPU kernel: tiled batched pairwise distances (+ fused prune mask).

This is the SM-tree's compute hot spot: every traversal level evaluates the
metric between a tile of queries and every entry of every frontier node.  The
kernel streams `[bq, d]` query and `[be, d]` entry blocks HBM->VMEM, reduces
over the feature dimension in `bd`-sized chunks (running max for d_inf /
running sum for squared-L2), and writes a `[bq, be]` distance tile.  All block
dims default to lane/sublane-aligned sizes (128, 8-multiples).

The optional fused epilogue applies the SM-tree triangle-inequality test
``d <= r_q + r_e`` in-register, emitting the survival mask alongside the
distances — saving one HBM round trip of the distance matrix on the pruning
path (the common case during descent).

Grid: (nq/bq, ne/be, d/bd); the reduction dim is innermost ("arbitrary"
semantics, accumulate in the output tile which Pallas keeps resident in VMEM
across the k-steps of a fixed (i, j) tile).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# metrics whose per-chunk parts add up (the rest take the running max)
_SUMMED = ("l2", "l1", "sqeuclidean", "ip")


def _dist_kernel(q_ref, e_ref, out_ref, *, metric: str, nk: int):
    k = pl.program_id(2)
    q = q_ref[...].astype(jnp.float32)          # [bq, bd]
    e = e_ref[...].astype(jnp.float32)          # [be, bd]
    if metric == "d_inf":
        part = jnp.max(jnp.abs(q[:, None, :] - e[None, :, :]), axis=-1)
    elif metric == "l2":
        # squared differences, summed here; the wrapper takes the sqrt
        diff = q[:, None, :] - e[None, :, :]
        part = jnp.sum(diff * diff, axis=-1)
    elif metric == "l1":
        part = jnp.sum(jnp.abs(q[:, None, :] - e[None, :, :]), axis=-1)
    elif metric == "sqeuclidean":
        # |q-e|^2 = |q|^2 - 2 q.e + |e|^2 : MXU does the q @ e.T contraction
        qq = jnp.sum(q * q, axis=-1, keepdims=True)          # [bq, 1]
        ee = jnp.sum(e * e, axis=-1, keepdims=True).T        # [1, be]
        qe = jax.lax.dot_general(q, e, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        part = qq - 2.0 * qe + ee
    elif metric == "ip":
        part = -jax.lax.dot_general(q, e, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
    else:
        raise ValueError(metric)

    prev = jnp.where(k == 0, jnp.zeros_like(part), out_ref[...])
    out_ref[...] = prev + part if metric in _SUMMED \
        else jnp.maximum(prev, part)


def _dist_prune_kernel(q_ref, e_ref, rq_ref, re_ref, out_ref, mask_ref,
                       *, metric: str, nk: int):
    """Same as _dist_kernel but fuses the triangle-inequality prune mask on
    the final reduction step."""
    _dist_kernel(q_ref, e_ref, out_ref, metric=metric, nk=nk)
    k = pl.program_id(2)

    @pl.when(k == nk - 1)
    def _():
        d = _finish(out_ref[...], metric)
        if metric == "sqeuclidean":
            d = jnp.sqrt(jnp.maximum(d, 0.0))
        rq = rq_ref[...].astype(jnp.float32)    # [bq]
        re = re_ref[...].astype(jnp.float32)    # [be]
        mask_ref[...] = d <= rq[:, None] + re[None, :]


def _finish(acc, metric):
    """Accumulator -> distance: l2 accumulates squares."""
    return jnp.sqrt(acc) if metric == "l2" else acc


def _pad_to(x, mult, axis, value=0.0):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


@functools.partial(jax.jit, static_argnames=("metric", "bq", "be", "bd", "interpret"))
def pairwise_distance_pallas(q: jax.Array, e: jax.Array, *, metric: str = "d_inf",
                             bq: int = 128, be: int = 128, bd: int = 128,
                             interpret: bool = False) -> jax.Array:
    """[nq, d] x [ne, d] -> [nq, ne] distances via the Pallas kernel."""
    nq, d = q.shape
    ne = e.shape[0]
    qp = _pad_to(_pad_to(q, bd, 1), bq, 0)
    # pad entries with +inf-ish sentinel? distances to padded entries are
    # sliced away below, so zero padding is fine.
    ep = _pad_to(_pad_to(e, bd, 1), be, 0)
    nqp, dp = qp.shape
    nep = ep.shape[0]
    nk = dp // bd
    grid = (nqp // bq, nep // be, nk)
    out = pl.pallas_call(
        functools.partial(_dist_kernel, metric=metric, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, bd), lambda i, j, k: (i, k)),
            pl.BlockSpec((be, bd), lambda i, j, k: (j, k)),
        ],
        out_specs=pl.BlockSpec((bq, be), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((nqp, nep), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qp, ep)
    out = _finish(out[:nq, :ne], metric)
    if metric == "sqeuclidean":
        out = jnp.maximum(out, 0.0)
    return out


@functools.partial(jax.jit, static_argnames=("metric", "bq", "be", "bd", "interpret"))
def pairwise_distance_prune_pallas(q, e, r_q, r_e, *, metric: str = "d_inf",
                                   bq: int = 128, be: int = 128, bd: int = 128,
                                   interpret: bool = False):
    """Fused distances + triangle-inequality survival mask.

    Returns (dist [nq, ne] float32, mask [nq, ne] bool).  For 'sqeuclidean'
    the returned distances are *squared* but the mask is computed on true
    distances (sqrt fused in-kernel)."""
    nq, d = q.shape
    ne = e.shape[0]
    qp = _pad_to(_pad_to(q, bd, 1), bq, 0)
    ep = _pad_to(_pad_to(e, bd, 1), be, 0)
    rqp = _pad_to(r_q.astype(jnp.float32), bq, 0, value=-1.0)   # padded queries match nothing
    rep = _pad_to(r_e.astype(jnp.float32), be, 0, value=-jnp.inf)
    nqp, dp = qp.shape
    nep = ep.shape[0]
    nk = dp // bd
    grid = (nqp // bq, nep // be, nk)
    dist, mask = pl.pallas_call(
        functools.partial(_dist_prune_kernel, metric=metric, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, bd), lambda i, j, k: (i, k)),
            pl.BlockSpec((be, bd), lambda i, j, k: (j, k)),
            pl.BlockSpec((bq,), lambda i, j, k: (i,)),
            pl.BlockSpec((be,), lambda i, j, k: (j,)),
        ],
        out_specs=[
            pl.BlockSpec((bq, be), lambda i, j, k: (i, j)),
            pl.BlockSpec((bq, be), lambda i, j, k: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nqp, nep), jnp.float32),
            jax.ShapeDtypeStruct((nqp, nep), jnp.bool_),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qp, ep, rqp, rep)
    return _finish(dist[:nq, :ne], metric), mask[:nq, :ne]
