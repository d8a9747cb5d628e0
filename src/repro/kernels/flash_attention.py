"""Pallas TPU flash attention (forward) with GQA and causal masking.

Design (TPU-native, not a CUDA port):
  * grid = (batch*q_heads, nq_blocks, nk_blocks) with the KV dimension
    innermost ("arbitrary" semantics) so the [bq, d] accumulator, running max
    and running sum live in VMEM scratch across the KV sweep of one q tile.
  * online softmax in float32 on the VPU; the two matmuls (q@k^T, p@v) hit
    the MXU with 128-aligned tiles.
  * GQA: KV blocks are selected by the BlockSpec index map
    (q-head -> kv-head = q_head // group), so KV for a group is fetched from
    HBM once per q-head without materialising the broadcast.
  * causal: block-level early-out via pl.when (skips the MXU work of fully
    masked tiles; the block fetch itself is pipelined by Pallas regardless —
    a scalar-prefetch kv-length map would also skip the fetch; measured as a
    §Perf item).

Backward runs as a chunked XLA recompute (see ops.attention): fwd kernel +
custom_vjp; a dedicated bwd kernel is a recorded optimisation opportunity.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BQ = 128
DEFAULT_BK = 128
NEG_INF = -1e30


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                      *, scale: float, causal: bool, sq: int, sk: int,
                      bq: int, bk: int):
    qb = pl.program_id(1)
    kb = pl.program_id(2)
    nkb = pl.num_programs(2)

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # global positions (causal offset aligns the *ends* of q and k, the
    # standard convention for decode/prefill with history)
    qpos = qb * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + (sk - sq)
    kpos = kb * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)

    # block-level skip: in causal mode a tile whose lowest kpos exceeds the
    # highest qpos is fully masked
    run = True
    if causal:
        run = (kb * bk) <= (qb * bq + bq - 1 + (sk - sq))

    @pl.when(run)
    def _body():
        q = q_ref[0].astype(jnp.float32)          # [bq, d]
        k = k_ref[0].astype(jnp.float32)          # [bk, d]
        v = v_ref[0].astype(jnp.float32)          # [bk, d]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        mask = kpos < sk                           # padded keys
        if causal:
            mask &= kpos <= qpos
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]                        # [bq, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                     # [bq, bk]
        corr = jnp.exp(m_prev - m_new)             # [bq, 1]
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(kb == nkb - 1)
    def _finalise():
        # rows with no unmasked key (padded q rows) have l == 0: emit zeros
        l = l_scr[...]
        o_ref[0] = (acc_scr[...] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "scale", "bq", "bk", "interpret"))
def flash_attention_fwd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True, scale: float | None = None,
                        bq: int = DEFAULT_BQ, bk: int = DEFAULT_BK,
                        interpret: bool = False) -> jax.Array:
    """q: [b, h, sq, d]; k, v: [b, hk, sk, d]; h % hk == 0.  Returns [b, h, sq, d]."""
    b, h, sq, d = q.shape
    _, hk, sk, _ = k.shape
    assert h % hk == 0, (h, hk)
    g = h // hk
    scale = d ** -0.5 if scale is None else scale

    sq_p = -(-sq // bq) * bq
    sk_p = -(-sk // bk) * bk
    qp = jnp.pad(q.reshape(b * h, sq, d), ((0, 0), (0, sq_p - sq), (0, 0)))
    kp = jnp.pad(k.reshape(b * hk, sk, d), ((0, 0), (0, sk_p - sk), (0, 0)))
    vp = jnp.pad(v.reshape(b * hk, sk, d), ((0, 0), (0, sk_p - sk), (0, 0)))

    grid = (b * h, sq_p // bq, sk_p // bk)

    def kv_index(bh, i, j):
        # flattened q-head index -> flattened kv-head index (GQA)
        return ((bh // h) * hk + (bh % h) // g, j, 0)

    out = pl.pallas_call(
        functools.partial(_flash_fwd_kernel, scale=scale, causal=causal,
                          sq=sq, sk=sk, bq=bq, bk=bk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, bk, d), kv_index),
            pl.BlockSpec((1, bk, d), kv_index),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda bh, i, j: (bh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq_p, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qp, kp, vp)
    return out[:, :sq, :].reshape(b, h, sq, d)
