"""Deployment of whole quantized KAN networks: quantize + bind for the runtime.

This module is the thin host-side layer between trained/quantized KAN params
and :mod:`repro.runtime`: it post-training-quantizes a stack, dequantizes and
zero-pads the weights to the batch-independent pipeline geometry, and hands
the resulting :class:`DeployedKAN` bundle to the runtime's executor registry.
All *execution* concerns — backend selection (``ref`` / ``pallas`` /
``acim``), batch bucketing, plan/compile caching, non-ideality injection —
live in the runtime, not here.

    qparams_list = quantize_kan_network(params_list, kspec)
    dep = deploy_kan_network(qparams_list, kspec, batch=B)
    y = kan_network_deploy_apply(dep, x)                 # resolved backend
    y = kan_network_deploy_apply(dep, x, backend="acim", key=key)
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from .asp_quant import ASPQuantSpec
from .kan_layer import KANSpec, quantize_kan_layer
from .. import runtime
from ..kernels.kan_spline.pipeline import (
    PipelinePlan,
    pack_layer_weights,
    packs_weights,
    pad_layer_weights,
)
from ..runtime.executor import default_interpret  # re-export (PR-1 API)

__all__ = [
    "DeployedKAN",
    "quantize_kan_network",
    "deploy_kan_network",
    "deploy_kan_ffn_stack",
    "place_deployed_kan",
    "kan_network_deploy_apply",
    "kan_network_apply_ref",
    "default_interpret",
]


@dataclasses.dataclass
class DeployedKAN:
    """A quantized KAN stack bound to a pipeline geometry plan.

    layers: tuple of per-layer weight dicts, already padded to the plan:
    {"lut", "wc", "wb"} dequantized f32 for 8-bit layers, or the int4-packed
    {"lut", "wcp", "wscale", "wb"} form for <=4-bit layers (two
    signed weight codes per int8 lane; the kernel decodes in-lane).
    specs/dims describe the logical network for the runtime backends.
    placement: the mesh this bundle's weights were placed on with
    :func:`place_deployed_kan` (or None).  The runtime resolves it as the
    lowest-precedence mesh source (explicit ``mesh=`` arg > ``use_mesh``
    scope > this), and ``replan``/``dataclasses.replace`` carry it along, so
    a placed bundle keeps executing sharded across batch re-binds.
    """

    plan: PipelinePlan
    layers: tuple
    specs: tuple
    dims: tuple
    residual_raw: bool = False
    placement: object = None

    def replan(self, batch: int) -> "DeployedKAN":
        """Rebind to a new batch size — a plan-cache lookup, not a rebuild
        (weights/padding are batch-agnostic; the runtime buckets batches on
        its own, so this only matters for geometry introspection).  The
        placement, if any, survives the re-bind."""
        if batch == self.plan.b:
            return self
        plan = runtime.PLAN_CACHE.plan(
            batch, self.dims, self.specs, residual_raw=self.residual_raw
        )
        return dataclasses.replace(self, plan=plan)


def place_deployed_kan(dep: DeployedKAN, mesh) -> DeployedKAN:
    """Shard a deployed bundle's weights onto a mesh and record the placement.

    Weights are device_put with ``dist.sharding.deployed_kan_pspecs``
    (output channels on "model", SH-LUT replicated) — the exact layout the
    runtime's shard_map consumes, so sharded execution starts from resident
    shards with no re-layout.  The returned bundle carries ``placement=
    mesh``, which the runtime picks up as its default mesh; pass
    ``placement=None`` via ``dataclasses.replace`` to detach.
    """
    import jax as _jax

    from ..dist.sharding import deployed_kan_pspecs, to_shardings

    shardings = to_shardings(deployed_kan_pspecs(dep, mesh), mesh)
    layers = tuple(
        {k: _jax.device_put(a, s[k]) for k, a in lw.items()}
        for lw, s in zip(dep.layers, shardings)
    )
    return dataclasses.replace(dep, layers=layers, placement=mesh)


def quantize_kan_network(params_list, kspec: KANSpec):
    """Post-training-quantize every layer of a KAN stack (host-side).

    Mixed precision rides on the kspec: a per-layer ``n_bits`` tuple gives
    every layer its own spec (input width, clipped lut_bits, and the
    matching signed weight-code width via ``quantize_kan_layer``)."""
    specs = kspec.layer_specs()
    return [
        quantize_kan_layer(p, spec)
        for p, spec in zip(params_list, specs)
    ]


def _dequant_layer(qp: dict) -> tuple:
    wc = qp["c_q"].astype(jnp.float32) * qp["c_scale"]
    wb = qp["w_b_q"].astype(jnp.float32) * qp["w_b_scale"]
    return wc, wb


def deploy_kan_network(
    qparams_list, kspec: KANSpec, *, batch: int = 8
) -> DeployedKAN:
    """Bind a quantized KAN stack to a pipeline plan (per-layer specs)."""
    specs = kspec.layer_specs()
    dims = tuple(kspec.dims)
    return _deploy(qparams_list, dims, specs, batch, residual_raw=False)


def deploy_kan_ffn_stack(
    qparams_list, dims: tuple, spec, *, batch: int = 8
) -> DeployedKAN:
    """Bind a KANLinear chain with the raw-input ReLU branch (FFN contract).

    ``spec``: one ASPQuantSpec (broadcast to every layer) or a per-layer
    sequence of specs (mixed precision)."""
    if isinstance(spec, ASPQuantSpec):
        specs = tuple(spec for _ in qparams_list)
    else:
        specs = tuple(spec)
    return _deploy(qparams_list, tuple(dims), specs, batch, residual_raw=True)


def _deploy(qparams_list, dims, specs, batch, *, residual_raw) -> DeployedKAN:
    if len(dims) != len(qparams_list) + 1:
        raise ValueError(f"dims {dims} vs {len(qparams_list)} layers")
    plan = runtime.PLAN_CACHE.plan(batch, dims, specs,
                                   residual_raw=residual_raw)
    layers = []
    for qp, lp in zip(qparams_list, plan.layers):
        if qp["c_q"].shape != (lp.f, lp.spec.num_basis, lp.o):
            raise ValueError(
                f"layer weights {qp['c_q'].shape} != plan {lp}")
        wb = qp["w_b_q"].astype(jnp.float32) * qp["w_b_scale"]
        if packs_weights(lp.spec):
            # <=4-bit layer: keep the weight CODES, two per int8 lane —
            # the f32 banded matrix never materializes at rest
            layer = {
                "lut": qp["lut"],
                **pack_layer_weights(qp["c_q"], qp["c_scale"], wb, lp),
            }
        else:
            wc, _ = _dequant_layer(qp)
            layer = {"lut": qp["lut"], **pad_layer_weights(wc, wb, lp)}
        layers.append(layer)
    return DeployedKAN(
        plan=plan, layers=tuple(layers), specs=specs, dims=dims,
        residual_raw=residual_raw,
    )


def kan_network_deploy_apply(
    dep: DeployedKAN,
    x: jax.Array,
    *,
    xraw: jax.Array | None = None,
    interpret: bool | None = None,
    backend: str | None = None,
    mesh=None,
    key=None,
    cim=None,
    sam_perms=None,
    return_intermediates: bool = False,
):
    """Run float input x (B, F0) through the runtime-resolved backend.

    ``backend=None`` resolves via the runtime (scope > ``REPRO_KAN_BACKEND``
    env var > "pallas"); ``mesh=None`` likewise (``use_mesh`` scope >
    ``dep.placement`` > unsharded).  ``key``/``cim``/``sam_perms`` only
    matter for the acim backend (``sam_perms``: per-layer KAN-SAM row
    placements).
    """
    return runtime.execute(
        dep, x, backend=backend, default="pallas",
        xraw=xraw, interpret=interpret, mesh=mesh, key=key, cim=cim,
        sam_perms=sam_perms,
        return_intermediates=return_intermediates,
    )


def kan_network_apply_ref(qparams_list, x: jax.Array, kspec: KANSpec):
    """The layered jnp reference the pipeline is bit-exact against
    (runtime ``ref`` composition over the un-padded quantized weights)."""
    from ..core.asp_quant import quantize_input

    specs = kspec.layer_specs()
    logical = []
    for qp in qparams_list:
        wc, wb = _dequant_layer(qp)
        logical.append((qp["lut"], wc, wb))
    codes = quantize_input(x, specs[0])
    return runtime.ref_composition(
        logical, specs, codes, None,
        residual_raw=False,
    )
