"""The metric's fixed-association fold, split where a caller re-lays its
terms out: ``_fold_halve``'s leading halvings leave ``_fold_sum`` bitwise
unchanged, and each metric's stages compose to the metric itself, on
numpy and on jax, along either axis."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.metric import (_fold_halve, _fold_sum, get_metric,
                               get_stages)

BACKENDS = {"numpy": np.asarray, "jax": jnp.asarray}


def _even_halvings(n):
    m = 0
    while n > 1 and n % 2 == 0:
        n //= 2
        m += 1
    return m


def _rows(n, axis, seed=0):
    """Four rows of ``n`` coordinates, the coordinates along ``axis``."""
    x = np.random.default_rng(seed + n).standard_normal((4, n))
    x = x.astype(np.float32)
    return x if axis == 1 else x.T.copy()


@pytest.mark.parametrize("backend", sorted(BACKENDS))
@pytest.mark.parametrize("axis", [0, 1])
@pytest.mark.parametrize("n", [3072, 1536, 256, 20])
def test_fold_halve_keeps_the_fold_bitwise(n, axis, backend):
    x = BACKENDS[backend](_rows(n, axis))
    whole = np.asarray(_fold_sum(x, axis))
    for m in range(1, _even_halvings(n) + 1):
        h = _fold_halve(x, axis, m)
        assert h.shape[axis] == n >> m
        np.testing.assert_array_equal(np.asarray(_fold_sum(h, axis)),
                                      whole, err_msg=f"m={m}")


@pytest.mark.parametrize("metric", ["d_inf", "l2", "l1"])
@pytest.mark.parametrize("backend", sorted(BACKENDS))
@pytest.mark.parametrize("axis", [0, 1])
@pytest.mark.parametrize("n", [3072, 256, 20])
def test_stages_compose_to_the_metric(n, axis, backend, metric):
    """``reduce(halve(terms(x, y), a, m), a)`` is the metric, bit for bit,
    for every count of even halvings (jitted on jax, as the kernel and the
    XLA path run it)."""
    x = BACKENDS[backend](_rows(n, axis, seed=1))
    y = BACKENDS[backend](_rows(n, axis, seed=2))
    st = get_stages(metric)
    fn = get_metric(metric)
    for m in range(_even_halvings(n) + 1):
        def staged(x, y, m=m):
            return st.reduce(st.halve(st.terms(x, y), axis, m), axis, True)

        def whole(x, y):
            return fn(x, y, axis=axis, keepdims=True)
        if backend == "jax":
            staged, whole = jax.jit(staged), jax.jit(whole)
        np.testing.assert_array_equal(np.asarray(staged(x, y)),
                                      np.asarray(whole(x, y)),
                                      err_msg=f"m={m}")
