"""Compile-only rehearsal of every cell's programs for a described (not
attached) TPU v5e, at the cells' real shapes: the cohort descent of each
configuration at its served frontier and the front end's default cohort
width (with and without the level-stats variant obs samples), and the
mutation scan, split pass and merge pass at the churn traffic's widths.
A kernel that the chip's compiler refuses then costs no chip time.

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests/test_compile.py
"""
import os
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from chipbench.harness import load_json
from repro.core import smtree
from repro.serve.frontend import FrontendConfig
from repro.stream.batcher import pad_to_bucket

BENCH = Path(__file__).resolve().parents[1]
# every configuration and every write mix the benchmark holds, listed in
# BENCHMARK.json or not yet
CONFIGS = {p.stem: load_json(p) for p in sorted((BENCH / "configs")
                                                .glob("*.json"))}
WRITERS = sorted((c, p.stem) for c in CONFIGS
                 for p in (BENCH / "traffic").glob("*.json")
                 if "writer" in load_json(p))


def geometry(cfg: dict) -> tuple[int, int]:
    """(node slots, height) that ``bulk_build`` gives ``cfg['n']`` objects:
    groups of int(0.7 * capacity) per level, 1.5x slack."""
    target = int(cfg["capacity"] * 0.7)
    level, nodes, height = cfg["n"], 0, 0
    while True:
        level = -(-level // target)
        nodes += level
        height += 1
        if level == 1:
            return int(nodes * 1.5), height


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


def abstract_tree(cfg: dict, sharding):
    """A TreeArrays of shapes only, at the configuration's size."""
    n_slots, _ = geometry(cfg)
    t = jax.eval_shape(lambda: smtree.empty_tree(
        dim=cfg["dim"], capacity=cfg["capacity"], max_nodes=n_slots,
        metric=cfg["metric"]))
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        t)


@pytest.mark.parametrize("level_stats", [False, True],
                         ids=["served", "level_stats"])
@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_descent_compiles(one_chip, name, level_stats):
    cfg = CONFIGS[name]
    tree = abstract_tree(cfg, one_chip)
    _, height = geometry(cfg)
    b = FrontendConfig().cohort_width
    q = jax.ShapeDtypeStruct((b, cfg["dim"]), jnp.float32, sharding=one_chip)
    r = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    compiled = smtree._knn_cohort.lower(
        tree, q, r, k=cfg["k"], F=cfg["max_frontier"], height=height,
        impl="pallas", interpret=False, level_stats=level_stats,
        prune=True).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("config,traffic", WRITERS)
def test_mutation_programs_compile(one_chip, config, traffic):
    cfg = CONFIGS[config]
    w = load_json(BENCH / "traffic" / f"{traffic}.json")["writer"]
    tree = abstract_tree(cfg, one_chip)

    def rows(n, *tail, dtype=jnp.int32):
        return jax.ShapeDtypeStruct((n, *tail), dtype, sharding=one_chip)

    width = pad_to_bucket(w["batch_ops"], 4096)
    smtree._apply_mutations_jit(False).lower(
        tree, rows(width), rows(width, cfg["dim"], dtype=jnp.float32),
        rows(width)).compile()
    n = smtree.SPLIT_CHUNK
    smtree._apply_splits_jit(True).lower(
        tree, rows(n), rows(n, cfg["dim"], dtype=jnp.float32),
        rows(n)).compile()
    for n in sorted({smtree.MERGE_CHUNK, smtree.MERGE_CHUNK_MAX}):
        smtree._apply_merges_jit(True).lower(tree, rows(n), rows(n)).compile()


def test_geometry_matches_bulk_build():
    """The rehearsal's node count is bulk_build's, at a small size."""
    cfg = {"n": 5000, "dim": 4, "capacity": 32, "metric": "l2"}
    X = np.random.default_rng(0).random((cfg["n"], cfg["dim"]))
    t = smtree.bulk_build(X.astype(np.float32), capacity=32, metric="l2")
    assert geometry(cfg) == (t.max_nodes, int(t.height))
