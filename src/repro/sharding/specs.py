"""PartitionSpec rules for every architecture on the production meshes.

Axes: ('data', 'model') single-pod; ('pod', 'data', 'model') multi-pod.
Training batches shard over (pod, data); model weights shard over 'model'
(tensor/expert parallelism); optimizer state follows its parameter.

Every rule is divisibility-guarded: a dim is sharded only when the mesh
axis divides it, otherwise that dim replicates — this is what lets one
rule set cover head counts of 25 (hymba), 8-expert MoE on a 16-way model
axis (falls back to d_ff tensor parallelism), vocab 50280, etc.
"""
from __future__ import annotations

from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.types import ModelConfig, ShapeConfig


def fed_round_specs(mesh: Mesh) -> dict:
    """PartitionSpecs for the shard_map'ed federated sync round.

    The round has exactly two kinds of operands: per-client arrays with a
    leading client axis (batch stacks (n, H_max, ...), weights (n,), the
    H^k iteration vector (n,), per-client losses (n, H)) which shard over
    the mesh's client axis, and fleet-global arrays (params, trainable
    mask, the psum'ed new global) which replicate. Specs are pytree
    prefixes: ``P(axis)`` shards only the leading dim of every leaf.

    On the hierarchical ``('edge', 'clients')`` mesh
    (``launch.mesh.make_fleet_mesh(edges=...)``) the leading client dim
    shards over BOTH axes — shard (e, c) holds the clients of edge
    aggregator e's c-th slot — and ``axis`` is the ``('edge', 'clients')``
    tuple, outermost first, so the round can reduce level by level
    (clients → edge, edge → server).
    """
    if {"edge", "clients"} <= set(mesh.axis_names):
        axis = ("edge", "clients")
        return {"axis": axis, "clients": P(axis), "replicated": P()}
    axis = "clients" if "clients" in mesh.axis_names else mesh.axis_names[0]
    return {"axis": axis, "clients": P(axis), "replicated": P()}


def data_axes(mesh: Mesh):
    """The batch-parallel axes present in a mesh."""
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)


def _axis_size(mesh: Mesh, name) -> int:
    if isinstance(name, tuple):
        s = 1
        for n in name:
            s *= _axis_size(mesh, n)
        return s
    return mesh.shape[name] if name in mesh.axis_names else 0


def _maybe(mesh: Mesh, axis, dim: int):
    """axis if it divides dim (and exists), else None."""
    size = _axis_size(mesh, axis)
    if size and dim % size == 0:
        return axis
    return None


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

def _spec_for(mesh: Mesh, path: tuple, leaf, fsdp: bool = True) -> P:
    """Rule table keyed by the param's path inside the pytree.

    Two-level weight sharding: the "tensor parallel" dim shards over
    'model'; with ``fsdp`` the other large dim additionally shards over
    ('pod','data') (ZeRO-3 style), which is what lets grok-1's 314B fit —
    weights replicated across the data axis would be 39 GiB/chip.
    """
    keys = [str(getattr(p, "key", getattr(p, "idx", p))) for p in path]
    name = keys[-1]
    shape = leaf.shape
    m = lambda dim: _maybe(mesh, "model", dim)  # noqa: E731
    dp_axes = data_axes(mesh)
    dp_flat = dp_axes if len(dp_axes) > 1 else (dp_axes[0] if dp_axes
                                                else None)

    def d(dim):
        if not fsdp or dp_flat is None:
            return None
        return _maybe(mesh, dp_flat, dim)

    # ---- embeddings / heads ----
    if name == "embed":
        return P(m(shape[0]), d(shape[1]))
    if name == "lm_head":
        return P(d(shape[0]), m(shape[1]))

    stacked = "layers" in keys or "enc_layers" in keys or "dec_layers" in keys
    off = 1 if stacked else 0  # leading L axis on scanned stacks

    def lead(*rest):
        return P(*(((None,) * off) + rest))

    # ---- attention ----
    if len(keys) >= 2 and keys[-2] in ("attn", "xattn"):
        if name in ("wq", "wk", "wv"):
            return lead(d(shape[-2]), m(shape[-1]))
        if name == "wo":
            return lead(m(shape[-2]), d(shape[-1]))

    # ---- dense / shared-expert MLP ----
    if name in ("wg", "wi", "shared_wg", "shared_wi") \
            and len(shape) == 2 + off:
        return lead(d(shape[-2]), m(shape[-1]))
    if name in ("wo", "shared_wo") and len(shape) == 2 + off:
        return lead(m(shape[-2]), d(shape[-1]))

    # ---- MoE experts: expert-parallel when E divides, else 2-D tensor ----
    if name in ("wg", "wi") and len(shape) == 3 + off:
        e = m(shape[off])
        if e is not None:
            return lead(e, d(shape[-2]), None)
        return lead(None, d(shape[-2]), m(shape[-1]))
    if name == "wo" and len(shape) == 3 + off:
        e = m(shape[off])
        if e is not None:
            return lead(e, None, d(shape[-1]))
        return lead(None, m(shape[-2]), d(shape[-1]))
    if name == "router":
        return lead(None, None)

    # ---- SSM ----
    if name == "in_proj":
        return lead(d(shape[-2]), m(shape[-1]))
    if name == "out_proj":
        return lead(m(shape[-2]), d(shape[-1]))

    # ---- everything else (norms, convs, biases, resnet) replicates ----
    return P()


def param_pspecs(mesh: Mesh, cfg: ModelConfig, params: Any,
                 fsdp: bool = True):
    """Pytree of PartitionSpec matching ``params`` (shapes or arrays)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    specs = [_spec_for(mesh, path, leaf, fsdp=fsdp) for path, leaf in flat]
    return jax.tree_util.tree_unflatten(treedef, specs)


# ---------------------------------------------------------------------------
# Batches
# ---------------------------------------------------------------------------

def batch_pspecs(mesh: Mesh, cfg: ModelConfig, batch: Any):
    dp = data_axes(mesh)
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    dp = dp if len(dp) > 1 else (dp[0] if dp else None)

    def spec(path, leaf):
        # batch dim shards over (pod, data) when divisible; everything else
        # replicates (feature dims of embedding inputs stay unsharded).
        lead = dp if leaf.shape[0] % max(dp_size, 1) == 0 else None
        return P(*((lead,) + (None,) * (leaf.ndim - 1)))

    flat, treedef = jax.tree_util.tree_flatten_with_path(batch)
    return jax.tree_util.tree_unflatten(
        treedef, [spec(p, l) for p, l in flat])


def cache_pspecs(mesh: Mesh, cfg: ModelConfig, cache: Any,
                 global_batch: int):
    """Serving cache sharding.

    Batched decode: batch dim over ('pod','data'). Single-sequence long
    context (batch 1): shard the cache *sequence* dim over 'data' — the
    attention contraction then reduces over 'data' (flash-decoding style);
    SSM states replicate over 'data' (they are tiny).
    """
    dp = data_axes(mesh)
    dp = dp if len(dp) > 1 else (dp[0] if dp else None)
    dp_size = 1
    for a in data_axes(mesh):
        dp_size *= mesh.shape[a]
    batch_sharded = global_batch % max(dp_size, 1) == 0 and global_batch > 1

    def spec(path, leaf):
        name = str(getattr(path[-1], "key", path[-1]))
        # leading dim is L (stacked layers) — never sharded
        if name in ("k_win", "v_win"):
            # ring buffers: tiny seq dim (=window); batch over data only
            if batch_sharded:
                return P(None, dp, None, None, None)
            return P(None, None, None, None, None)
        if name in ("k", "v", "enc_k", "enc_v"):
            # (L, B, S, KV, hd)
            if batch_sharded:
                return P(None, dp, _maybe(mesh, "model", leaf.shape[2]),
                         None, None)
            return P(None, None, _maybe(mesh, ("data", "model"),
                                        leaf.shape[2]) or
                     _maybe(mesh, "data", leaf.shape[2]), None, None)
        if name == "ssm_state":
            # (L, B, H, P, N)
            if batch_sharded:
                return P(None, dp, _maybe(mesh, "model", leaf.shape[2]),
                         None, None)
            return P(None, None, _maybe(mesh, "model", leaf.shape[2]),
                     None, None)
        if name == "conv_state":
            if batch_sharded:
                return P(None, dp, None, None)
            return P(None, None, None, _maybe(mesh, "model", leaf.shape[3]))
        raise ValueError(f"unknown cache leaf {name}")

    flat, treedef = jax.tree_util.tree_flatten_with_path(cache)
    return jax.tree_util.tree_unflatten(
        treedef, [spec(p, l) for p, l in flat])


def token_pspec(mesh: Mesh, global_batch: int):
    dp = data_axes(mesh)
    dp = dp if len(dp) > 1 else (dp[0] if dp else None)
    dp_size = 1
    for a in data_axes(mesh):
        dp_size *= mesh.shape[a]
    if global_batch % max(dp_size, 1) == 0 and global_batch > 1:
        return P(dp)
    return P(None)


def named(mesh: Mesh, spec_tree):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))
