"""Compile-only checks of the query path's Pallas kernels for a TPU v5e.

The kernels' CPU tests run them through the Pallas interpreter, which
accepts block shapes, layouts and primitives that the TPU compiler
refuses.  These cases compile the kernels at real widths for a described
(not attached) ``v5e:2x2`` topology and check that the executable holds
the Mosaic kernel (``tpu_custom_call``).  Nothing runs, so no chip is
needed.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.distance import pairwise_distance_pallas
from repro.kernels.frontier import frontier_scores_pallas

# the query path's widths: a SIFT-shaped corpus (dim 128) at cohort width
# 1024, frontier 64, node capacity 32
B, F, N, CAP, DIM = 1024, 64, 8192, 32, 128


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler on this host
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache, so keep such compiles out of it
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("prune", [False, True], ids=["plain", "parent_prune"])
@pytest.mark.parametrize("metric", ["d_inf", "l2", "l1"])
def test_frontier_kernel_compiles(one_chip, metric, prune):
    s = functools.partial(_spec, one_chip)
    args = (s((B, F), jnp.int32), s((B, DIM)), s((N, CAP, DIM)),
            s((N, CAP)), s((N, CAP), jnp.bool_), s((N, CAP), jnp.bool_))
    kw = dict(pdist=s((N, CAP)), qpd=s((B, F)), rq=s((B,))) if prune else {}
    fn = jax.jit(functools.partial(frontier_scores_pallas, metric=metric))
    _assert_kernel(fn.lower(*args, **kw).compile())


# the served page (25,000 x 20-d, capacity 42, d_inf, parent filter on, a
# cohort of 64) at an internal level's width and a leaf chunk's, and a
# capacity-42 page at dim 2048, where block_slots gives a smaller block
@pytest.mark.parametrize("w,cap,dim", [(42, 42, 20), (441, 42, 20),
                                       (441, 42, 2048)],
                         ids=["served_w42", "served_w441", "cap42_dim2048"])
def test_blocked_frontier_kernel_compiles(one_chip, w, cap, dim):
    s = functools.partial(_spec, one_chip)
    n, b = 1341, 64
    args = (s((b, w), jnp.int32), s((b, dim)), s((n, cap, dim)),
            s((n, cap)), s((n, cap), jnp.bool_), s((n, cap), jnp.bool_))
    kw = dict(pdist=s((n, cap)), qpd=s((b, w)), rq=s((b,)))
    fn = jax.jit(functools.partial(frontier_scores_pallas, metric="d_inf"))
    _assert_kernel(fn.lower(*args, **kw).compile())


def test_l2_distance_kernel_compiles(one_chip):
    s = functools.partial(_spec, one_chip)
    fn = jax.jit(functools.partial(pairwise_distance_pallas, metric="l2"))
    _assert_kernel(fn.lower(s((256, DIM)), s((65_536, DIM))).compile())
