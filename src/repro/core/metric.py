"""Metric functions for the (S)M-tree.

The paper (§4.1) uses the Chebyshev / L-infinity metric

    d_inf(x, y) = max_i |x_i - y_i|

over 20-dimensional vectors, with experiment dimensionality varied by
truncating the metric (NOT the stored vectors) to the first ``n_dims``
components.  We mirror that: every metric takes an optional ``n_dims``.

All functions here are pure and work on numpy or jax arrays (they only use
ufuncs, slicing and elementwise ops), so the same definitions back the numpy
reference implementation, the JAX engine's cohort descent, and the fused
Pallas frontier kernel (three call sites, one definition — they cannot
drift).

Summing reductions go through ``_fold_sum``, a fixed-association pairwise
tree fold: the reduction tree depends only on the axis length, never on the
leading shape or backend, so l1/l2 distances are *bitwise identical* whether
evaluated on a ``[cap, dim]`` Pallas block, a ``[b, F, cap, dim]`` XLA
gather, or a numpy array.  The engine's xla-vs-pallas parity guarantee
(tests/test_cohort_descent.py) rests on this.  ``get_stages`` splits each
metric where the kernel re-lays a wide page out (terms and the fold's first
halvings along lanes, the rest over sublanes) without changing a bit.
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple

import numpy as np

MetricFn = Callable[..., "np.ndarray"]

_REGISTRY: dict[str, MetricFn] = {}


def register_metric(name: str):
    def deco(fn: MetricFn) -> MetricFn:
        _REGISTRY[name] = fn
        return fn
    return deco


def get_metric(name: str) -> MetricFn:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown metric {name!r}; have {sorted(_REGISTRY)}") from None


def _take(x, start, stop, axis):
    """``x[start:stop]`` along ``axis`` (keeps the axis; numpy or jax)."""
    return x[(slice(None),) * (axis % x.ndim) + (slice(start, stop),)]


def _truncate(x, y, n_dims, axis):
    if n_dims is not None:
        x = _take(x, 0, n_dims, axis)
        y = _take(y, 0, n_dims, axis)
    return x, y


def _fold_halve(x, axis=-1, times=1):
    """``_fold_sum``'s first ``times`` halvings along ``axis``: each adds
    ``x[h:2h]`` to ``x[:h]``, ``h`` half the length, pairing ``x[i]`` with
    ``x[i + h]``.  An odd length's last element is left out (``_fold_sum``
    carries it itself), so while each halved length is even,
    ``_fold_sum(x) == _fold_sum(_fold_halve(x, axis, m), axis)`` bitwise:
    ``_fold_sum`` halves through this very function."""
    for _ in range(times):
        h = x.shape[axis] // 2
        x = _take(x, 0, h, axis) + _take(x, h, 2 * h, axis)
    return x


def _fold_sum(x, axis=-1, keepdims=False):
    """Sum over ``axis`` with a fixed pairwise-tree association.

    Floating-point addition is not associative, and XLA's reduce grouping
    varies with the operand's leading shape — the same row summed inside a
    ``[cap, dim]`` kernel block and a ``[b, F, cap, dim]`` gather can differ
    in the last ulp.  This fold's association is a function of the axis
    length alone (halve, add, carry the odd tail), so every call site
    produces bitwise identical sums, whichever axis holds the coordinates
    (the TPU kernel folds over sublanes, the XLA path over the last axis).
    Works on numpy and jax arrays (static slices + ``+`` only).
    """
    n = x.shape[axis]
    if n == 0:   # empty sum: zeros, association irrelevant
        return x.sum(axis=axis, keepdims=keepdims)
    s = x
    if n > 1:
        s = _fold_sum(_fold_halve(x, axis), axis, keepdims=True)
        if n % 2:
            s = s + _take(x, n - 1, n, axis)
    return s if keepdims else s.squeeze(axis)


def _max_halve(x, axis=-1, times=1):
    """``_fold_halve`` for a max reduction: the elementwise max of
    ``x[:h]`` and ``x[h:2h]``, ``times`` over (each length even).  Max is
    exact in any order, so the halved max equals the whole one."""
    for _ in range(times):
        h = x.shape[axis] // 2
        a, b = _take(x, 0, h, axis), _take(x, h, 2 * h, axis)
        if isinstance(x, np.ndarray):
            x = np.maximum(a, b)
        else:
            import jax.numpy as jnp
            x = jnp.maximum(a, b)
    return x


def _pin_rounding(x):
    """Keep the compiler from contracting the squares into the fold's adds
    as FMAs — contraction is fusion-context-dependent, so without this pin
    the same l2 distance can differ by an ulp between e.g. the Pallas
    kernel and the plain gather (breaking bitwise parity).  ``max(x, 0)``
    is an identity for the squares this guards but interposes an op LLVM's
    contraction pattern cannot see through, so the product is rounded to
    f32 exactly once at every call site (observed on the scalar pdist eval
    inside the fused insert fast path — 1-ulp drift vs the numpy fold,
    caught by tests/test_pdist_invariant.py).  No-op on numpy."""
    if isinstance(x, np.ndarray):
        return x
    import jax.numpy as jnp
    return jnp.maximum(x, 0.0)


def _abs_diff(x, y):
    return abs(x - y)


def _sq_diff(x, y):
    d = x - y
    return _pin_rounding(d * d)


def _max(t, axis=-1, keepdims=False):
    return t.max(axis=axis, keepdims=keepdims)


def _root_fold_sum(t, axis=-1, keepdims=False):
    s = _fold_sum(t, axis, keepdims)
    if isinstance(s, np.ndarray):
        return np.sqrt(s)
    # true sqrt, not s ** 0.5: pow goes through libm whose rounding varies
    # with vectorisation context (another cross-shape parity breaker); IEEE
    # sqrt is correctly rounded everywhere
    import jax.numpy as jnp
    return jnp.sqrt(s)


class Stages(NamedTuple):
    """A metric as the stages a caller may re-lay its terms out between:
    the frontier kernel halves a wide page's coordinates along lanes, then
    transposes what is left to fold it over sublanes.  For each metric
    ``reduce(halve(terms(x, y), a, m), a, keepdims)`` is the metric over
    axis ``a``, bit for bit, while each of the ``m`` halved lengths is
    even; ``m = 0`` is the metric's own definition."""
    terms: Callable    # (x, y) -> per-coordinate terms, broadcast
    halve: Callable    # (t, axis, times) -> the reduction's first halvings
    reduce: Callable   # (t, axis, keepdims) -> the distance


_STAGES: dict[str, Stages] = {
    "d_inf": Stages(_abs_diff, _max_halve, _max),
    "l2": Stages(_sq_diff, _fold_halve, _root_fold_sum),
    "l1": Stages(_abs_diff, _fold_halve, _fold_sum),
}


def get_stages(name: str) -> Stages:
    try:
        return _STAGES[name]
    except KeyError:
        raise KeyError(f"metric {name!r} has no stages; have "
                       f"{sorted(_STAGES)}") from None


def _staged(name, x, y, n_dims, axis, keepdims):
    x, y = _truncate(x, y, n_dims, axis)
    st = _STAGES[name]
    return st.reduce(st.terms(x, y), axis, keepdims)


# Every metric reduces the coordinate axis ``axis`` (default: last) of the
# broadcast difference.  ``keepdims`` leaves it as length 1 — the kernel's
# [dim, cap] page view reduces to a [1, cap] row that way.

@register_metric("d_inf")
def d_inf(x, y, n_dims: int | None = None, *, axis=-1, keepdims=False):
    """Chebyshev metric; broadcasting pairwise over leading axes."""
    return _staged("d_inf", x, y, n_dims, axis, keepdims)


@register_metric("l2")
def l2(x, y, n_dims: int | None = None, *, axis=-1, keepdims=False):
    return _staged("l2", x, y, n_dims, axis, keepdims)


@register_metric("l1")
def l1(x, y, n_dims: int | None = None, *, axis=-1, keepdims=False):
    return _staged("l1", x, y, n_dims, axis, keepdims)


def pairwise(metric: str | MetricFn, X, Y, n_dims: int | None = None):
    """[n, d] x [m, d] -> [n, m] distance matrix (numpy-side helper)."""
    fn = get_metric(metric) if isinstance(metric, str) else metric
    return fn(X[:, None, :], Y[None, :, :], n_dims=n_dims)


def make_metric(name: str, n_dims: int | None = None) -> MetricFn:
    fn = get_metric(name)
    return functools.partial(fn, n_dims=n_dims)
