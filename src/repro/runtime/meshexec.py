"""Mesh-sharded execution of the KAN runtime (the distributed dimension).

The software analogue of the follow-up paper's multi-chip ACIM scaling
(partitioning spline arrays across tiles): the fused pipeline's **batch**
shards over the mesh's ``"data"`` axis and each layer's **output channels**
shard over ``"model"`` (the `dist.sharding.deployed_kan_pspecs` layout —
every shard owns whole MAC columns, so there is never a cross-shard
reduction inside a layer).  The inter-layer boundary requantizer stays
shard-local: each shard re-codes its own columns, then an all-gather over
``"model"`` restores the full-width code vector the next layer contracts
against (int32 codes — the cheapest possible boundary payload, exactly the
paper's inter-array traffic argument).

Resolution mirrors the backend registry: explicit ``mesh=`` argument >
:func:`use_mesh` scope > the bundle's recorded placement
(``DeployedKAN.placement``) > unsharded.  Geometry that cannot shard (a
model-axis size that does not divide a layer's padded output dim) falls
back to replicated columns for that layer, and the reason is recorded in
:func:`shard_notes`.

Everything here is glue around one ``shard_map``: the per-shard body drives
the SAME fused kernel (``kernels.kan_spline.run_pipeline_layer``) on a
per-shard plan (``shard_local_plan``), so a 1x1 mesh or a pure-``data`` mesh
is bit-identical to the unsharded path (row independence + whole-column
ownership) for every deterministic program — ``pallas``, ``ref``, and
quiet/deterministic ``acim`` — which the acceptance tests assert.  Noisy
``acim`` is the one exception: its PRNG stream is re-derived PER SHARD
(the data index is folded into the key), so binding any mesh changes the
draws; runs stay reproducible under a fixed key + fixed mesh.
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P



def _shard_map_norep(body, *, mesh, in_specs, out_specs):
    """shard_map without replication checking: the checker cannot prove
    replication through pallas_call (it has no replication rule)."""
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


__all__ = [
    "ShardContext",
    "use_mesh",
    "resolve_mesh",
    "mesh_axis_sizes",
    "mesh_fingerprint",
    "register_mesh",
    "mesh_from_fingerprint",
    "shard_notes",
    "reset_shard_notes",
    "build_sharded_runner",
]

# innermost use_mesh() override; ContextVar for the same reason as the
# backend scope — concurrent engines must not clobber each other
_SCOPE_MESH: contextvars.ContextVar = contextvars.ContextVar(
    "repro_kan_mesh_scope", default=None
)

# fingerprint core -> live Mesh (PlanKey must stay hashable/comparable, so
# the key carries the fingerprint and the Mesh object is parked here)
_MESHES: dict = {}
# fingerprint -> tuple of human-readable fallback reasons (replicated layers)
_NOTES: dict = {}


@contextlib.contextmanager
def use_mesh(mesh):
    """Scoped mesh override, mirroring :func:`use_backend`.

    ``None`` is a no-op passthrough so callers can plumb an optional choice.
    """
    token = _SCOPE_MESH.set(mesh if mesh is not None else _SCOPE_MESH.get())
    try:
        yield
    finally:
        _SCOPE_MESH.reset(token)


def resolve_mesh(mesh=None, placement=None):
    """Explicit arg > ``use_mesh`` scope > bundle placement > None."""
    if mesh is not None:
        return mesh
    scoped = _SCOPE_MESH.get()
    if scoped is not None:
        return scoped
    return placement


def mesh_axis_sizes(mesh) -> tuple:
    """(data_size, model_size) of a mesh; absent axes count as 1."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return int(sizes.get("data", 1)), int(sizes.get("model", 1))


def mesh_fingerprint(mesh, layer_sharded) -> tuple:
    """Hashable identity of (mesh layout x per-layer sharded-or-not).

    Axis names x sizes x flat device ids pin the physical layout (two
    meshes over the same devices in a different order are different
    programs); the per-layer bools keep a fallen-back-to-replicated
    geometry from colliding with a fully sharded one.
    """
    return (
        tuple(mesh.axis_names),
        tuple(int(s) for s in mesh.devices.shape),
        tuple(int(d.id) for d in mesh.devices.flat),
        tuple(bool(f) for f in layer_sharded),
    )


def register_mesh(fingerprint: tuple, mesh, notes=()) -> None:
    _MESHES[fingerprint[:3]] = mesh
    if notes:
        _NOTES[fingerprint] = tuple(notes)


def mesh_from_fingerprint(fingerprint: tuple):
    return _MESHES[fingerprint[:3]]


def shard_notes() -> dict:
    """Recorded sharding fallbacks: fingerprint -> reasons (for reporting)."""
    return dict(_NOTES)


def reset_shard_notes() -> None:
    _NOTES.clear()


@dataclasses.dataclass(frozen=True)
class ShardContext:
    """Per-shard coordinates handed to backend hooks inside the shard body.

    ``data_index``/``model_index`` are traced axis indices (or literal 0
    when the mesh lacks the axis); ``layer_sharded`` says which layers'
    columns are split on "model".  The acim backend folds these into its
    PRNG key so every shard draws decorrelated noise — but only folds the
    model index for layers whose columns are actually sharded, keeping
    replicated values bitwise replicated across the model axis.
    """

    data_index: object
    model_index: object
    layer_sharded: tuple


def build_sharded_runner(mesh, *, local_plan, layer_sharded, residual_raw,
                         layer_fn, noise_fn=None):
    """Build the shard_mapped pipeline runner for one cached executor entry.

    Returns ``runner(codes, xraw, layers, *extra)`` -> ``(y, boundaries)``:

      * ``codes``/``xraw`` are GLOBAL, already padded to the global batch
        bucket and the entry feature pad ``fp0``; the batch shards over
        "data" (each shard sees ``bucket / data_size`` rows, further padded
        to the local plan's ``bp`` when a tuned ``bb`` demands it);
      * ``layers`` shard their ``wc``/``wb`` columns over "model" wherever
        ``layer_sharded`` says so (``deployed_kan_pspecs`` layout), the
        SH-LUT is replicated;
      * ``extra`` is the backend's trailing operand (the acim PRNG key),
        replicated and re-derived per shard via ``noise_fn``;
      * ``y`` reassembles to the global (bucket, op_last) array, and
        ``boundaries`` are the full-width int32 boundary codes each layer
        handed to the next (already all-gathered over "model" — the gather
        is load-bearing: the next layer contracts the full feature axis).

    ``layer_fn(li, lp, lw, codes, xraw, psum_noise)`` runs ONE layer on the
    per-shard geometry; ``noise_fn(codes, layers, key, ctx)`` (optional)
    perturbs the entry codes and returns per-layer psum noise tiles.
    """
    axis_names = tuple(mesh.axis_names)
    dname = "data" if "data" in axis_names else None
    mname = "model" if "model" in axis_names else None
    n_layers = len(local_plan.layers)

    from ..kernels.kan_spline.pipeline import layer_weight_keys

    in_specs = [P(dname, None)]
    if residual_raw:
        in_specs.append(P(dname, None))
    # per-leaf specs follow each layer's ACTUAL deployed keys: SH-LUT leaves
    # (f32 or int4-packed) replicate; weight leaves — unpacked "wc", packed
    # "wcp" + its per-channel "wscale" row, and "wb" — shard their
    # output-channel (last) dim on "model" wherever the layer shards
    in_specs.append(tuple(
        {
            k: (P(None, None) if k.startswith("lut")
                else P(None, mname if sharded else None))
            for k in layer_weight_keys(lp)
        }
        for lp, sharded in zip(local_plan.layers, layer_sharded)
    ))
    if noise_fn is not None:
        in_specs.append(P(None))
    out_specs = (
        P(dname, mname if layer_sharded[-1] else None),
        tuple(P(dname, None) for _ in range(n_layers - 1)),
    )

    def body(*args):
        it = iter(args)
        codes = next(it)
        xraw = next(it) if residual_raw else None
        layers = next(it)
        nkey = next(it) if noise_fn is not None else None
        # a tuned bb may not divide the per-shard batch slab: pad rows up to
        # the local plan's bp inside the shard (rows are independent), slice
        # back before reassembly
        b_l = codes.shape[0]
        if b_l != local_plan.bp:
            codes = jnp.pad(codes, ((0, local_plan.bp - b_l), (0, 0)))
            if xraw is not None:
                xraw = jnp.pad(xraw, ((0, local_plan.bp - b_l), (0, 0)))
        ctx = ShardContext(
            data_index=jax.lax.axis_index(dname) if dname else 0,
            model_index=jax.lax.axis_index(mname) if mname else 0,
            layer_sharded=layer_sharded,
        )
        noises = None
        if noise_fn is not None:
            codes, noises = noise_fn(codes, layers, nkey, ctx)
        h_codes, h_raw = codes, xraw
        y = None
        boundary = []
        for li, (lp, lw) in enumerate(zip(local_plan.layers, layers)):
            y, nxt = layer_fn(
                li, lp, lw, h_codes, h_raw,
                noises[li] if noises is not None else None,
            )
            if nxt is None:
                continue  # last layer: f32 output only
            y_next = y if residual_raw else None
            if layer_sharded[li] and mname:
                # the shard-local requantizer has already re-coded this
                # shard's columns; gather the int codes (and the raw f32
                # copy the FFN ReLU branch needs) to full width
                nxt = jax.lax.all_gather(nxt, mname, axis=1, tiled=True)
                if y_next is not None:
                    y_next = jax.lax.all_gather(
                        y_next, mname, axis=1, tiled=True
                    )
            boundary.append(nxt)
            h_codes, h_raw = nxt, y_next
        return y[:b_l], tuple(c[:b_l] for c in boundary)

    fn = _shard_map_norep(
        body, mesh=mesh, in_specs=tuple(in_specs), out_specs=out_specs,
    )

    def runner(codes, xraw, layers, *extra):
        args = [codes]
        if residual_raw:
            args.append(xraw)
        args.append(layers)
        if noise_fn is not None:
            # only the stochastic path consumes the trailing PRNG key; a
            # quiet/deterministic config ignores it (same as the local path,
            # where the zeroed terms are compiled out)
            args.extend(extra)
        return fn(*args)

    return runner
