"""Role-based sharding rules: param / optimizer / cache PartitionSpecs.

The rules are NAME-based (the param tree keys carry the role: wq/wk/wv have
their heads axis at index ndim-2, attention wo at ndim-3, ffn wi/wg shard
the hidden dim, embed shards the vocab) with a divisibility guard — a dim is
only sharded when the mesh axis divides it, otherwise the leaf stays
replicated on that axis.  Everything here returns plain PartitionSpec trees;
``to_shardings`` binds them to a mesh.

Scanned stacks put a leading repeats dim on every decoder leaf, so all index
rules count FROM THE END of the shape.
"""

from __future__ import annotations

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

__all__ = [
    "param_pspecs",
    "opt_state_pspecs",
    "batch_pspec",
    "cache_pspecs",
    "paged_cache_pspecs",
    "deployed_kan_pspecs",
    "to_shardings",
]


def _axis_size(mesh, name: str) -> int:
    return dict(zip(mesh.axis_names, mesh.devices.shape)).get(name, 1)


def _path_str(kp) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in kp)


# (predicate on path, index-from-end of the dim to put on "model")
_MODEL_RULES = [
    (lambda p: p.endswith("wq") or p.endswith("wk") or p.endswith("wv"), 2),
    (lambda p: ("attn/wo" in p) or ("xattn/wo" in p), 3),          # (H, hd, D)
    (lambda p: p.endswith("bq") or p.endswith("bk") or p.endswith("bv"), 2),
    (lambda p: p.endswith("ffn/wi") or p.endswith("ffn/wg"), 1),   # (D, F)
    (lambda p: p.endswith("ffn/wo"), 2),                           # (F, D)
    (lambda p: p.endswith("moe/wi") or p.endswith("moe/wg"), 1),   # (E, D, F)
    (lambda p: p.endswith("moe/wo"), 2),                           # (E, F, D)
    (lambda p: p.endswith("ffn/c1"), 1),   # KAN (D, G+K, H): shard hidden
    (lambda p: p.endswith("ffn/wb1"), 1),
    (lambda p: p.endswith("ffn/c2"), 3),   # (H, G+K, D): shard hidden
    (lambda p: p.endswith("ffn/wb2"), 2),
]


def _leaf_spec(path: str, shape, msize: int, dsize: int, fsdp: bool) -> P:
    nd = len(shape)
    parts = [None] * nd
    if nd == 0:
        return P()
    if path.endswith("embed"):
        # (V, D): vocab on "model" (the lm_head transpose shards likewise)
        if msize > 1 and shape[0] % msize == 0:
            parts[0] = "model"
    elif path.endswith("lm_head") or path.endswith("patch_proj"):
        if msize > 1 and shape[-1] % msize == 0:
            parts[-1] = "model"
    else:
        for pred, from_end in _MODEL_RULES:
            if pred(path) and nd >= from_end:
                dim = nd - from_end
                if msize > 1 and shape[dim] % msize == 0:
                    parts[dim] = "model"
                break
    if fsdp and dsize > 1:
        # ZeRO-3-style: fully shard the largest still-replicated dim on
        # "data" when it divides evenly (skip tiny dims - norm scales etc.)
        cands = [
            i for i in range(nd)
            if parts[i] is None and shape[i] % dsize == 0 and shape[i] >= 2 * dsize
        ]
        if cands:
            parts[max(cands, key=lambda i: shape[i])] = "data"
    return P(*parts)


def param_pspecs(params, mesh, fsdp: bool = False):
    """PartitionSpec tree for a models.model.init_params tree."""
    msize, dsize = _axis_size(mesh, "model"), _axis_size(mesh, "data")
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    specs = [
        _leaf_spec(_path_str(kp), getattr(leaf, "shape", ()), msize, dsize, fsdp)
        for kp, leaf in flat
    ]
    return jax.tree_util.tree_unflatten(treedef, specs)


def opt_state_pspecs(opt_state, params, mesh, zero1: bool = True):
    """Optimizer-state specs: moment trees mirror the param layout.

    zero1 keeps the moments on the same spec as their param (the "data" axis
    placement already fully shards fsdp params; for replicated params the
    moments stay replicated — a conservative ZeRO-1 that never conflicts
    with the param's own axes).
    """
    pspecs = param_pspecs(params, mesh, fsdp=zero1)

    def one(entry):
        # entry is either a moment tree shaped like params or a scalar
        if isinstance(entry, dict) and set(entry) != set():
            leaves = jax.tree.leaves(entry)
            if leaves and len(leaves) == len(jax.tree.leaves(params)):
                return pspecs
        return jax.tree.map(lambda leaf: P(), entry)

    if isinstance(opt_state, dict):
        return {k: one(v) for k, v in opt_state.items()}
    return jax.tree.map(lambda _: P(), opt_state)


def batch_pspec(mesh, global_batch: int) -> P:
    """Batch-dim spec: shard over "data" when it divides; the tuple form is
    used when there is slack for further axes (super-batch > data size)."""
    dsize = _axis_size(mesh, "data")
    if dsize <= 1 or global_batch % dsize != 0:
        return P(None)
    if global_batch > dsize:
        return P(("data",))
    return P("data")


def cache_pspecs(cache, mesh, batch: int):
    """KV/recurrent cache specs: shard the batch dim on "data" if it divides."""
    dsize = _axis_size(mesh, "data")

    def one(leaf):
        shape = getattr(leaf, "shape", ())
        parts = [None] * len(shape)
        if dsize > 1 and batch % dsize == 0:
            for i, d in enumerate(shape):
                if d == batch:
                    parts[i] = "data"
                    break
        return P(*parts)

    return jax.tree.map(one, cache)


def paged_cache_pspecs(cache, mesh, num_blocks: int):
    """Paged KV pool specs: shard the pool (num_blocks) dim on "data" when
    it divides — the paged analogue of ``cache_pspecs``'s slot-dim rule.
    Leaves are (repeats, NB, block_size, H, D); the NB dim is matched by
    size, counting from index 1 so a repeats count equal to NB can't
    shadow it."""
    dsize = _axis_size(mesh, "data")

    def one(leaf):
        shape = getattr(leaf, "shape", ())
        parts = [None] * len(shape)
        if dsize > 1 and num_blocks % dsize == 0:
            for i, d in enumerate(shape):
                if i >= 1 and d == num_blocks:
                    parts[i] = "data"
                    break
        return P(*parts)

    return jax.tree.map(one, cache)


def deployed_kan_pspecs(dep, mesh):
    """PartitionSpecs for a ``repro.runtime`` deployed-KAN bundle's layers.

    The padded banded weights shard their OUTPUT-channel dim on "model"
    (each shard owns whole columns of the MAC — no cross-shard reduction,
    matching the per-output-channel quantization scales), the shared SH-LUT
    stays replicated.  Shardability is the runtime's criterion
    (``kernels.kan_spline.pipeline.model_shardable``: the axis divides the
    128-padded dim and each shard keeps a multiple-of-8 slab), so placement
    and sharded execution always agree — a layer the runtime would fall
    back to replicated is never placed sharded.
    """
    from ..kernels.kan_spline.pipeline import model_shardable

    msize = _axis_size(mesh, "model")

    def one_layer(lw):
        def col_spec(a):
            if model_shardable(int(a.shape[-1]), msize):
                return P(*([None] * (a.ndim - 1) + ["model"]))
            return P(*([None] * a.ndim))

        # key-generic over the deployed forms: the SH-LUT ("lut")
        # replicates; every other leaf — "wc", or the
        # packed "wcp" + per-channel "wscale" row, and "wb" — carries its
        # output channels on the last dim and shards them on "model"
        return {
            k: (P(*([None] * a.ndim)) if k.startswith("lut")
                else col_spec(a))
            for k, a in lw.items()
        }

    return tuple(one_layer(lw) for lw in dep.layers)


def to_shardings(pspecs, mesh):
    """Bind a PartitionSpec tree to a mesh as NamedShardings."""
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        pspecs,
        is_leaf=lambda x: isinstance(x, P),
    )
