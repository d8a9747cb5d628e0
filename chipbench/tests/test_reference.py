"""The plain brute force against numpy, at a tiny size on the CPU."""
import jax.numpy as jnp
import numpy as np

from chipbench.harness import load_module

ref_mod = load_module("reference", "d_inf")


def numpy_knn(Q, X, live, k):
    d = np.abs(Q[:, None, :].astype(np.float64) - X[None]).max(-1)
    d[:, ~live] = np.inf
    i = np.argsort(d, axis=1, kind="stable")[:, :k]
    return np.take_along_axis(d, i, 1), i


def test_brute_force_matches_numpy():
    rng = np.random.default_rng(0)
    X = rng.random((1000, 24)).astype(np.float32)
    Q = rng.random((300, 24)).astype(np.float32)
    live = rng.random(1000) < 0.7
    ref = ref_mod.Reference(X, chunk=128, q_chunk=64)
    d, i = ref.knn(Q, live, 10)
    nd, ni = numpy_knn(Q, X, live, 10)
    np.testing.assert_allclose(d, nd, rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(i, ni)
    assert live[i].all()
    np.testing.assert_allclose(ref.dist(Q, i), nd, rtol=1e-6, atol=1e-6)


def test_fewer_live_than_k_pads_with_inf():
    X = np.eye(4, dtype=np.float32)
    live = np.array([True, False, True, False])
    d, i = ref_mod.Reference(X).knn(X[:1], live, 3)
    assert list(i[0]) == [0, 2, -1] and np.isinf(d[0, 2])


def test_bfloat16_control_is_coarser():
    rng = np.random.default_rng(1)
    X = rng.random((2000, 128)).astype(np.float32)
    Q = rng.random((64, 128)).astype(np.float32)
    live = np.ones(2000, bool)
    d32, _ = ref_mod.Reference(X).knn(Q, live, 10)
    d16, _ = ref_mod.Reference(X, dtype=jnp.bfloat16).knn(Q, live, 10)
    assert np.max(np.abs(d16 - d32) / np.maximum(1, d32)) > 1e-4
