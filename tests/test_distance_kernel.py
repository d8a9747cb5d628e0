"""Pallas distance kernel: interpret-mode shape/dtype sweeps vs the jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


SHAPES = [(8, 8, 4), (128, 128, 128), (100, 130, 20), (1, 257, 96), (300, 7, 160)]
METRICS = ["d_inf", "l2", "l1", "sqeuclidean", "ip"]


@pytest.mark.parametrize("nq,ne,d", SHAPES)
@pytest.mark.parametrize("metric", METRICS)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pairwise_distance_matches_oracle(nq, ne, d, metric, dtype):
    k1, k2 = jax.random.split(jax.random.PRNGKey(nq * 1000 + ne + d))
    q = jax.random.normal(k1, (nq, d), dtype)
    e = jax.random.normal(k2, (ne, d), dtype)
    got = ops.pairwise_distance(q, e, metric=metric, impl="interpret")
    want = ref.pairwise_distance_ref(q.astype(jnp.float32),
                                     e.astype(jnp.float32), metric=metric)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("nq,ne,d", [(64, 64, 32), (50, 200, 20), (9, 300, 130)])
@pytest.mark.parametrize("metric", ["d_inf", "sqeuclidean"])
def test_fused_prune_matches_oracle(nq, ne, d, metric):
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(7), 4)
    q = jax.random.uniform(k1, (nq, d))
    e = jax.random.uniform(k2, (ne, d))
    r_q = jax.random.uniform(k3, (nq,), maxval=0.6)
    r_e = jax.random.uniform(k4, (ne,), maxval=0.6)
    got_d, got_m = ops.pairwise_distance_prune(q, e, r_q, r_e, metric=metric,
                                               impl="interpret")
    want_d, want_m = ops.pairwise_distance_prune(q, e, r_q, r_e, metric=metric,
                                                 impl="xla")
    np.testing.assert_allclose(np.asarray(got_d), np.asarray(want_d),
                               rtol=1e-5, atol=1e-5)
    # mask can differ only where the prune test is within float tolerance of
    # equality; require exact match away from the boundary
    true_d = np.sqrt(np.maximum(np.asarray(want_d), 0)) if metric == "sqeuclidean" \
        else np.asarray(want_d)
    margin = np.abs(true_d - (np.asarray(r_q)[:, None] + np.asarray(r_e)[None, :]))
    decided = margin > 1e-5
    np.testing.assert_array_equal(np.asarray(got_m)[decided],
                                  np.asarray(want_m)[decided])


def test_distance_agrees_with_core_metric():
    """Kernel oracle must agree with the numpy metric used by the ref trees."""
    from repro.core.metric import pairwise
    rng = np.random.default_rng(0)
    X = rng.random((40, 20)).astype(np.float32)
    Y = rng.random((30, 20)).astype(np.float32)
    want = pairwise("d_inf", X, Y)
    got = ref.pairwise_distance_ref(jnp.asarray(X), jnp.asarray(Y), metric="d_inf")
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6, atol=1e-6)
