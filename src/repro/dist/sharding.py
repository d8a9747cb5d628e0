"""GSPMD sharding policy: pure functions from (config, pytree, mesh) to
``PartitionSpec`` trees.

Design rules (DESIGN.md §7):
  * 'model' is the tensor-parallel axis.  Attention shards the *head* axis
    (weights are head-shaped, see models/attention.py), FFNs shard the hidden
    dim, vocab-sized matrices shard the vocab dim, SSM/xLSTM blocks shard
    d_inner / d_x.  K/V projections are replicated (kv heads are tiny).
  * 'data' (times 'pod' when present) is the data-parallel axis; parameters
    above ``FSDP_MIN_ELEMS`` additionally shard their largest free dim over
    'data' (FSDP), and ZeRO-1 extends every optimizer-moment leaf with 'data'
    on its first free dim (``opt_state_pspec``).
  * Every rule is guarded by exact divisibility — jit argument shardings
    reject uneven shards — so the same table serves every arch in
    configs/all_archs.py on any mesh shape; an axis that does not divide is
    simply dropped (the spec degrades to replication, never errors).
  * Rules are duck-typed on the mesh (only ``.shape``/``.axis_names`` are
    read) so they unit-test without devices (tests/test_sharding_rules.py).

Also hosts the small runtime layer the model code uses: ``make_mesh``
(every mesh of the repo, with Auto axes), ``use_mesh``/``_ambient_mesh``
(the ambient mesh ``constrain`` resolves axis names against),
``constrain``/``constrain_batch_seq`` (divisibility-guarded
with_sharding_constraint), ``set_sequence_parallel`` and ``shard_map``.
"""
from __future__ import annotations

import contextlib
import math
from typing import Any

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

# Parameters with at least this many elements get their largest free dim
# sharded over 'data' on top of tensor parallelism (FSDP).  64 MiB of f32 —
# big enough that smoke/test configs stay simply TP-sharded.
FSDP_MIN_ELEMS = 1 << 24

# Axes that compose the data-parallel dimension, outermost first ('pod' is
# the DCN axis of the multipod mesh, see launch/mesh.py).
DP_AXES = ("pod", "data")


# ---------------------------------------------------------------------------
# meshes and the ambient mesh
# ---------------------------------------------------------------------------
_MESH_STACK: list[Any] = []
_SEQ_PARALLEL = False


def make_mesh(shape, axis_names, devices=None):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``.

    jax's own default is Explicit axes, under which a gather from a sharded
    array must name its output sharding; this repo's sharding is GSPMD's
    (``constrain``, jit argument shardings, ``shard_map`` bodies), so every
    mesh it builds is Auto."""
    return jax.make_mesh(tuple(shape), tuple(axis_names), devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,)
                         * len(axis_names))


def _ambient_mesh():
    """The innermost mesh set via ``use_mesh`` (None outside any context)."""
    return _MESH_STACK[-1] if _MESH_STACK else None


@contextlib.contextmanager
def use_mesh(mesh):
    """Context manager installing ``mesh`` as the ambient mesh.

    ``constrain`` resolves axis names against this mesh, and the physical
    ``Mesh`` context is entered too so named in-jit collectives resolve.
    """
    _MESH_STACK.append(mesh)
    try:
        if hasattr(mesh, "__enter__"):
            with mesh:
                yield mesh
        else:  # duck-typed mesh (tests)
            yield mesh
    finally:
        _MESH_STACK.pop()


def set_sequence_parallel(flag: bool) -> None:
    """Megatron-style sequence parallelism on the residual stream: when on,
    ``constrain_batch_seq`` additionally shards the sequence dim over
    'model'.  Trace-time switch (set by train_step from TrainSettings)."""
    global _SEQ_PARALLEL
    _SEQ_PARALLEL = bool(flag)


def shard_map(f=None, mesh=None, in_specs=None, out_specs=None,
              axis_names=None):
    """``jax.shard_map`` with replication checking off (the forest/moe
    bodies do their own collectives), usable as a partial-application
    decorator.  ``axis_names`` is the set of *manual* axes: e.g. the MoE
    expert-parallel body stays manual over 'data' only while GSPMD
    tensor-shards the expert FFN over 'model'."""
    if f is None:                       # functools.partial decorator form
        return lambda fn: shard_map(fn, mesh, in_specs, out_specs,
                                    axis_names=axis_names)
    kwargs = {} if axis_names is None else {"axis_names": set(axis_names)}
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False, **kwargs)


# ---------------------------------------------------------------------------
# mesh introspection helpers (duck-typed: Mesh, AbstractMesh or test fakes)
# ---------------------------------------------------------------------------
def _mesh_sizes(mesh) -> dict[str, int]:
    if hasattr(mesh, "shape") and mesh.shape is not None:
        return dict(mesh.shape)
    return dict(zip(mesh.axis_names, mesh.axis_sizes))


def _axes_of(part) -> tuple[str, ...]:
    if part is None:
        return ()
    return part if isinstance(part, tuple) else (part,)


def _used_axes(entries) -> set:
    return {a for e in entries for a in _axes_of(e)}


def batch_dp(mesh):
    """The composite data-parallel spec entry for this mesh: 'data', or
    ('pod', 'data') on the multipod mesh."""
    sizes = _mesh_sizes(mesh)
    dp = tuple(a for a in DP_AXES if a in sizes)
    if not dp:
        return None
    return dp if len(dp) > 1 else dp[0]


def _dp_entry(mesh, dim_size: int):
    """Data-parallel entry for a batch dim, or None if it does not divide."""
    sizes = _mesh_sizes(mesh)
    dp = tuple(a for a in DP_AXES if a in sizes and sizes[a] > 1)
    if not dp:
        return None
    total = math.prod(sizes[a] for a in dp)
    if dim_size % total == 0:
        return dp if len(dp) > 1 else dp[0]
    # fall back to the inner 'data' axis alone (pod stays replicated)
    if "data" in dp and dim_size % sizes["data"] == 0:
        return "data"
    return None


def to_named(specs, mesh):
    """Map a PartitionSpec tree to NamedShardings on a concrete mesh."""
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def _path_str(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in path)


# ---------------------------------------------------------------------------
# parameter rules
# ---------------------------------------------------------------------------
# Dense-wrapped weights ({"w": ...}) keyed by their owner, mapped to the
# tensor-parallel dim (negative index into the leaf shape).  Column-parallel
# projections shard their output dim (-1); row-parallel ones their input
# dim (-2) so the following contraction reduces with one psum.
_DENSE_COL = ("wi/w", "wg/w", "up/w", "in_proj/w", "dt_proj/w", "wv/w",
              "w_o/w", "wq/w", "wk/w", "slstm/w")
_DENSE_ROW = ("wo/w", "down/w", "out_proj/w", "x_proj/w")


def _tp_rule(key: str, ndim: int) -> int | None:
    """Tensor-parallel dim (negative index) for a param path, or None."""
    last = key.rsplit("/", 1)[-1]
    # replicated: norms, biases, routers, tiny gate tables, position tables
    if "norm" in key or last in ("scale", "bias", "b", "b_if", "router",
                                 "pos_embed", "dec_pos", "r"):
        return None
    if key.endswith("lm_head/w"):
        return -1                       # vocab (column) parallel
    # attention: head-sharded q/out, replicated k/v (kv heads are tiny and
    # broadcast to query-head groups — keeps attention collective-free)
    if "attn/" in key:                  # matches attn/ and xattn/
        if last in ("wq", "bq"):
            return -2                   # [.., D, H, dh] / [.., H, dh]
        if last == "wo":
            return -3                   # [.., H, dh, D]
        return None                     # wk, wv, bk, bv
    for suffix in _DENSE_COL:
        if key.endswith(suffix):
            return -1
    for suffix in _DENSE_ROW:
        if key.endswith(suffix):
            return -2
    # bare (stacked) weights: MoE experts, SSM/xLSTM tables
    if last in ("wi", "wg"):
        return -1                       # moe [.., E, D, F]: hidden dim
    if last == "wo":
        return -2                       # moe [.., E, F, D]: hidden dim
    if last in ("conv_w", "conv_b", "D"):
        return -1                       # [.., k, d_inner] / [.., d_inner]
    if last in ("A_log", "w_if"):
        return -2                       # [.., d_inner, n] / [.., dx, 2H]
    return None


def param_pspecs(cfg, params, mesh):
    """PartitionSpec tree for a parameter pytree (arrays or
    ShapeDtypeStructs) of this arch on this mesh."""
    sizes = _mesh_sizes(mesh)
    model = sizes.get("model", 1)
    data = sizes.get("data", 1)
    ep = bool(getattr(cfg, "moe_ep", False))
    n_experts = getattr(cfg, "padded_experts", 0)

    def rule(path, leaf):
        key = _path_str(path)
        shape = tuple(leaf.shape)
        ndim = len(shape)
        entries: list = [None] * ndim
        last = key.rsplit("/", 1)[-1]
        no_fsdp = False

        if last == "embed":
            # vocab-parallel, never FSDP'd: the tied head matmul wants the
            # d_model dim intact (tests/test_sharding_rules.py pins this)
            if model > 1 and shape[0] % model == 0:
                entries[0] = "model"
            no_fsdp = True
        else:
            tp = _tp_rule(key, ndim)
            if tp is not None and model > 1:
                dim = ndim + tp
                if 0 <= dim < ndim and shape[dim] % model == 0:
                    entries[dim] = "model"
            if ep and last in ("wi", "wg", "wo") and "moe/" in key \
                    and ndim >= 3 and data > 1 and n_experts \
                    and shape[ndim - 3] % data == 0:
                # expert parallelism: experts ride the data axis (A2A
                # dispatch); that axis is then spoken for — no FSDP on top
                entries[ndim - 3] = "data"
                no_fsdp = True

        if not no_fsdp and data > 1 and "data" not in _used_axes(entries) \
                and math.prod(shape) >= FSDP_MIN_ELEMS:
            free = [i for i in range(ndim)
                    if entries[i] is None and shape[i] % data == 0]
            if free:
                entries[max(free, key=lambda i: shape[i])] = "data"
        return P(*entries)

    return jax.tree_util.tree_map_with_path(rule, params)


def opt_state_pspec(param_spec: P, shape, mesh) -> P:
    """ZeRO-1: extend a param's spec with 'data' on its first free,
    evenly-divisible dim for the optimizer moment of that param."""
    sizes = _mesh_sizes(mesh)
    data = sizes.get("data", 1)
    entries = [param_spec[i] if i < len(param_spec) else None
               for i in range(len(shape))]
    if data > 1 and "data" not in _used_axes(entries):
        for i, dim in enumerate(shape):
            if entries[i] is None and dim % data == 0:
                entries[i] = "data"
                break
    return P(*entries)


# ---------------------------------------------------------------------------
# input / cache / output rules
# ---------------------------------------------------------------------------
def input_pspecs(cfg, kind: str, inputs, mesh):
    """Batch-dim data parallelism for every model input leaf."""
    del kind

    def rule(leaf):
        shape = tuple(leaf.shape)
        if not shape:
            return P()
        entries = [_dp_entry(mesh, shape[0])] + [None] * (len(shape) - 1)
        return P(*entries)

    return jax.tree.map(rule, inputs)


_KV_KEYS = ("kv", "self_k", "self_v", "cross_k", "cross_v")


def cache_pspecs(cfg, cache, mesh, *, seq_shard: bool = False):
    """Decode-cache shardings.  KV caches [layers, b, KV, S, dh] shard batch
    over the dp axes and — for long contexts (``seq_shard``) or whenever
    'model' divides — the sequence axis; kv heads stay replicated (matching
    the attention weight rules).  Recurrent-state caches (SSM/xLSTM) shard
    batch plus their largest inner dim over 'model'."""
    sizes = _mesh_sizes(mesh)
    model = sizes.get("model", 1)

    def rule(path, leaf):
        shape = tuple(leaf.shape)
        ndim = len(shape)
        keys = {str(getattr(p, "key", "")) for p in path}
        entries: list = [None] * ndim
        if ndim >= 2:
            entries[1] = _dp_entry(mesh, shape[1])
        if keys & set(_KV_KEYS) and ndim == 5:
            seq_axes: list[str] = []
            prod = 1
            candidates = ["model"]
            if seq_shard:
                # long-context: fold free dp axes into the sequence split too
                candidates += [a for a in DP_AXES
                               if a in sizes and a not in
                               _used_axes(entries)]
            for a in candidates:
                if sizes.get(a, 1) > 1 and shape[3] % (prod * sizes[a]) == 0:
                    seq_axes.append(a)
                    prod *= sizes[a]
            if seq_axes:
                entries[3] = tuple(seq_axes) if len(seq_axes) > 1 \
                    else seq_axes[0]
        elif ndim >= 3 and model > 1:
            # recurrent state: TP its largest inner dim (d_inner / dx / dh)
            free = [i for i in range(2, ndim) if shape[i] % model == 0]
            if free:
                entries[max(free, key=lambda i: shape[i])] = "model"
        return P(*entries)

    return jax.tree_util.tree_map_with_path(rule, cache)


def logits_pspec(mesh) -> P:
    """[batch, seq, vocab] logits: dp on batch, vocab-parallel on 'model'."""
    return P(batch_dp(mesh), None, "model")


def query_pspecs(mesh, batch_size: int) -> P:
    """SM-tree query-cohort sharding: [b, dim] batches split over the dp
    axes (divisibility-guarded), tree pages replicated.  The cohort descent
    (core/smtree.py) is batched over b in every op, so GSPMD runs each
    query shard's descent locally with zero collectives — the serving fast
    path for the kNN-LM datastore and ``launch/serve.py --mesh host``."""
    return P(_dp_entry(mesh, batch_size), None)


# ---------------------------------------------------------------------------
# activation constraints (used inside model code)
# ---------------------------------------------------------------------------
def constrain(x, *parts):
    """with_sharding_constraint against the ambient mesh, with every axis
    guarded by existence and exact divisibility.  Each positional arg is the
    preference for one dim of ``x``: None, an axis name, or a tuple of axis
    names tried outermost-first.  No-op outside a ``use_mesh`` context."""
    mesh = _ambient_mesh()
    if mesh is None or not hasattr(mesh, "devices"):
        return x
    sizes = _mesh_sizes(mesh)
    entries: list = []
    for i in range(x.ndim):
        pref = parts[i] if i < len(parts) else None
        chosen: list[str] = []
        prod = 1
        for a in _axes_of(pref):
            if sizes.get(a, 1) > 1 and x.shape[i] % (prod * sizes[a]) == 0:
                chosen.append(a)
                prod *= sizes[a]
        entries.append(tuple(chosen) if len(chosen) > 1
                       else (chosen[0] if chosen else None))
    if all(e is None for e in entries):
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*entries)))


def constrain_batch_seq(x):
    """Pin [b, s, D] activations: batch over dp; sequence over 'model' when
    sequence parallelism is on (see ``set_sequence_parallel``)."""
    if x.ndim != 3:
        return constrain(x, DP_AXES)
    return constrain(x, DP_AXES, "model" if _SEQ_PARALLEL else None, None)
