"""Distributed SM-forest on an 8-device mesh: build, fan-out query, online
delete — the multi-device form of the paper's structure.

    PYTHONPATH=src python examples/distributed_index.py
"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.distributed import (brute_force_knn, build_forest,
                                    forest_delete, forest_knn)
from repro.core.metric import pairwise
from repro.data.datagen import clustered
from repro.dist.sharding import use_mesh as _use_mesh
from repro.dist.sharding import make_mesh


mesh = make_mesh((2, 4), ("data", "model"))
X = clustered(20_000, dims=12, seed=0)[:, :12].copy()
Q = X[:32] + np.float32(0.005)

t0 = time.time()
forest, _ = build_forest(X, mesh, capacity=32)
print(f"forest build over {mesh.shape['model']} shards: "
      f"{time.time() - t0:.2f}s ({X.shape[0]} objects)")

with _use_mesh(mesh):
    t0 = time.time()
    d, ids = forest_knn(forest, mesh, jnp.asarray(Q), k=5, max_frontier=256)
    jax.block_until_ready(d)
    print(f"forest kNN batch of {len(Q)}: {(time.time()-t0)*1e3:.1f}ms "
          f"(includes compile)")

    # exactness vs global brute force
    D = pairwise("d_inf", Q, X)
    np.testing.assert_allclose(np.asarray(d), np.sort(D, 1)[:, :5], atol=1e-5)
    print("exact vs brute force: OK")

    # the sequential-scan baseline (the paper's horizontal line), sharded
    Xs = jax.device_put(jnp.asarray(X), jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec("model")))
    t0 = time.time()
    d2, _ = brute_force_knn(Xs, mesh, jnp.asarray(Q), k=5)
    jax.block_until_ready(d2)
    print(f"sharded brute-force scan: {(time.time()-t0)*1e3:.1f}ms")

    # online distributed delete (the paper's contribution, fleet form)
    victims = np.arange(0, 512)
    forest, found = forest_delete(forest, mesh, jnp.asarray(X[victims]),
                                  jnp.asarray(victims, jnp.int32))
    print(f"distributed delete: {int(np.asarray(found).sum())}/512 "
          f"applied via the jitted fast path")
    d3, ids3 = forest_knn(forest, mesh, jnp.asarray(X[:8]), k=1,
                          max_frontier=256)
    hit = (np.asarray(ids3)[:, 0] == np.arange(8))
    print(f"victims still self-matching: {int(hit.sum())}/8 "
          f"(expected ~0 for fast-path deletes)")
