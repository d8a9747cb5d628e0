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


# the kNN-LM datastore's shapes (262,144 keys of 3,072 dims in 18,685 node
# slots of capacity 32, L2, a cohort of 64): an internal level's width, a
# leaf chunk's, whose four output rows of a query outgrow the default
# scoped VMEM, and a whole frontier's, whose id table outgrows SMEM (an
# internal level of a taller tree).  The pages are whole tiles, so the
# tree's own array goes to the kernel with no padded copy.
@pytest.mark.parametrize("prune", [False, True], ids=["plain", "parent_prune"])
@pytest.mark.parametrize("w", [1024, 3072, 12288])
def test_knnlm_page_kernel_compiles(one_chip, w, prune):
    s = functools.partial(_spec, one_chip)
    n, b, cap, dim = 18_685, 64, 32, 3072
    args = (s((b, w), jnp.int32), s((b, dim)), s((n, cap, dim)),
            s((n, cap)), s((n, cap), jnp.bool_), s((n, cap), jnp.bool_))
    kw = dict(pdist=s((n, cap)), qpd=s((b, w)), rq=s((b,))) if prune else {}
    fn = jax.jit(functools.partial(frontier_scores_pallas, metric="l2"))
    text = fn.lower(*args, **kw).compile().as_text()
    assert "tpu_custom_call" in text
    assert not [ln for ln in text.splitlines()
                if " pad(" in ln and f"{n},{cap},{dim}" in ln]


# The mutation scan reads routing pages inside its choose-subtree loop.
# Read by plain indexing there, XLA copies every page of the tree into a
# capacity-minor layout on entry (four times the tree at the kNN-LM
# datastore's 32 x 3,072 page, 29.5 GB at its 18,726 slots); the Pallas
# page read keeps them in place.  A tree of 512 slots shows it: the
# program's scratch stays under one copy of the pages.
def test_mutation_scan_keeps_the_page_layout(one_chip):
    from repro.core import smtree
    n, b, cap, dim = 512, 1024, 32, 3072
    tree = jax.eval_shape(lambda: smtree.empty_tree(
        dim=dim, capacity=cap, max_nodes=n, metric="l2"))
    tree = jax.tree.map(lambda a: _spec(one_chip, a.shape, a.dtype), tree)
    s = functools.partial(_spec, one_chip)
    compiled = smtree._apply_mutations_jit(False).lower(
        tree, s((b,), jnp.int32), s((b, dim)), s((b,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < n * cap * dim * 4
