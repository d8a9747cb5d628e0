"""Pure-jnp oracles for every Pallas kernel in this package.

These are the ground truth the kernels are validated against (interpret-mode
sweeps in tests/) and the lowering path used on backends without Mosaic
(CPU dry-run): same math, standard XLA ops.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def pairwise_distance_ref(q: jax.Array, e: jax.Array, metric: str = "d_inf") -> jax.Array:
    """[nq, d] x [ne, d] -> [nq, ne] distances.

    metric: 'd_inf' (Chebyshev), 'l2' (Euclidean), 'l1' (Manhattan),
    'sqeuclidean', 'ip' (negative inner product, for MIPS-style retrieval
    over normalised keys).
    """
    q = q[:, None, :]
    e = e[None, :, :]
    if metric == "d_inf":
        return jnp.max(jnp.abs(q - e), axis=-1)
    if metric in ("l2", "sqeuclidean"):
        d2 = jnp.sum((q - e) ** 2, axis=-1)
        return jnp.sqrt(d2) if metric == "l2" else d2
    if metric == "l1":
        return jnp.sum(jnp.abs(q - e), axis=-1)
    if metric == "ip":
        return -jnp.sum(q * e, axis=-1)
    raise ValueError(f"unknown metric {metric!r}")


def prune_mask_ref(dist: jax.Array, r_q: jax.Array, r_e: jax.Array) -> jax.Array:
    """Triangle-inequality survival mask: d(Q,O_n) <= r(Q) + r(O_n).

    dist: [nq, ne]; r_q: [nq] query search radii; r_e: [ne] covering radii.
    """
    return dist <= r_q[:, None] + r_e[None, :]


def flash_attention_ref(q, k, v, *, causal: bool = True, scale: float | None = None):
    """Reference multi-head attention.  q: [b, h, sq, d]; k,v: [b, hk, sk, d]
    with h a multiple of hk (GQA: kv heads broadcast over query-head groups).
    Computes in float32, returns q.dtype."""
    b, h, sq, d = q.shape
    hk = k.shape[1]
    scale = scale if scale is not None else d ** -0.5
    group = h // hk
    qf = q.astype(jnp.float32).reshape(b, hk, group, sq, d)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    logits = jnp.einsum("bhgqd,bhkd->bhgqk", qf, kf) * scale
    if causal:
        sk = k.shape[2]
        # query position i attends to key positions <= i + (sk - sq)
        qpos = jnp.arange(sq)[:, None] + (sk - sq)
        kpos = jnp.arange(sk)[None, :]
        logits = jnp.where(kpos <= qpos, logits, -jnp.inf)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", w, vf)
    return out.reshape(b, h, sq, d).astype(q.dtype)
