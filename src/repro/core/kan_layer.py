"""KAN layers: float reference path and the ASP-quantized LUT path.

A KAN layer (paper eq. (1)-(3)) maps in_dim -> out_dim through per-edge
learnable 1-D functions::

    y_o = sum_f [ w_b[f,o] * relu(x_f) + sum_i c'[f,i,o] * B_i(x_f) ]

* ``b(x)`` is ReLU (the paper replaces SiLU "for improved hardware efficiency
  without accuracy loss").
* ``c' = w_s * c`` is fused (eq. (3)) and, on the quantized path, stored as
  int8 per-output-channel symmetric — this is what lives in the RRAM cells /
  on TPU in the banded weight matrix.
* The spline term is evaluated as a dense banded matmul
  ``basis (B, F*(G+K)) @ Wc (F*(G+K), O)`` — the MXU-native mapping of
  "B(X) on word lines x c' in the array".

Parameters are plain dict pytrees (jit/pjit friendly, no framework deps).
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .asp_quant import (
    ASPQuantSpec,
    build_lut,
    dense_basis_from_codes,
    quantize_input,
    resolve_layer_bits,
)
from .bspline import bspline_basis

__all__ = [
    "KANSpec",
    "init_kan_layer",
    "kan_layer_apply",
    "quantize_kan_layer",
    "kan_layer_apply_quantized",
    "init_kan_network",
    "kan_network_apply",
    "refit_layer_spec",
    "extend_layer_grid",
    "param_count",
]


@dataclasses.dataclass(frozen=True)
class KANSpec:
    """Architecture of a KAN stack: dims + per-layer quantization specs.

    ``n_bits`` is either one int (uniform precision, the paper's deployment)
    or a per-layer tuple of widths (KANtize-style mixed precision) — one
    entry per layer, each independently PowerGap-validated.  A layer's
    ``lut_bits`` is clipped to its input width, so a 4-bit layer stores a
    4-bit SH-LUT (and its weight codes deploy two per int8 lane, see
    ``kernels.kan_spline.pipeline``).
    """

    dims: tuple  # e.g. (17, 1, 14)
    grid_size: int = 5
    order: int = 3
    n_bits: int | tuple = 8
    lut_bits: int = 8
    lo: float = -1.0
    hi: float = 1.0

    def __post_init__(self):
        if not isinstance(self.n_bits, int):
            object.__setattr__(
                self, "n_bits", tuple(int(b) for b in self.n_bits)
            )
        # validate eagerly: an invalid per-layer allocation must fail at
        # construction, not at first deploy
        self.layer_bits

    @property
    def layer_bits(self) -> tuple:
        """Per-layer input bit widths, PowerGap-validated (never clamped)."""
        return resolve_layer_bits(
            self.n_bits, len(self.dims) - 1, self.grid_size
        )

    def layer_spec(self, li: int = 0) -> ASPQuantSpec:
        b = self.layer_bits[li]
        return ASPQuantSpec(
            grid_size=self.grid_size,
            order=self.order,
            n_bits=b,
            lut_bits=min(self.lut_bits, b),
            lo=self.lo,
            hi=self.hi,
        )

    def layer_specs(self) -> tuple:
        return tuple(
            self.layer_spec(li) for li in range(len(self.dims) - 1)
        )

    @property
    def num_basis(self) -> int:
        return self.grid_size + self.order


def init_kan_layer(key, in_dim: int, out_dim: int, spec: ASPQuantSpec, dtype=jnp.float32):
    """c: (in, G+K, out) small-noise init (pykan-style); w_b: (in, out)."""
    kc, kb = jax.random.split(key)
    nb = spec.num_basis
    c = jax.random.normal(kc, (in_dim, nb, out_dim), dtype) * (0.1 / np.sqrt(in_dim))
    w_b = jax.random.normal(kb, (in_dim, out_dim), dtype) * (1.0 / np.sqrt(in_dim))
    return {"c": c, "w_b": w_b}


def _spline_matmul(basis: jax.Array, c: jax.Array) -> jax.Array:
    """(B, F, G+K) x (F, G+K, O) -> (B, O) as a single flattened matmul."""
    bsz = basis.shape[:-2]
    f, nb, o = c.shape
    lhs = basis.reshape(bsz + (f * nb,))
    rhs = c.reshape(f * nb, o)
    return lhs @ rhs


def kan_layer_apply(params, x: jax.Array, spec: ASPQuantSpec) -> jax.Array:
    """Float reference path (training path): Cox-de Boor basis, exact."""
    basis = bspline_basis(x, spec.lo, spec.hi, spec.grid_size, spec.order)
    y = _spline_matmul(basis, params["c"])
    y = y + jax.nn.relu(x) @ params["w_b"]
    return y


# ----------------------------------------------------------------------------
# Quantized inference path (ASP-KAN-HAQ)
# ----------------------------------------------------------------------------


def quantize_kan_layer(params, spec: ASPQuantSpec, weight_bits: int | None = None):
    """Post-training quantization of one layer.

    ``weight_bits`` sets the signed weight-code width (symmetric, per output
    channel): ``qmax = 2**(bits-1) - 1`` (127 at the default 8 bits, 7 at 4).
    ``None`` derives it from the layer's input width — ``min(8, spec.n_bits)``
    — so a 4-bit layer stores 4-bit weight codes the fused kernel packs two
    per int8 lane.

    Returns dict:
      c_q: int8 (in, G+K, out), symmetric per-output-channel.
      c_scale: (out,) float32.
      w_b_q / w_b_scale: same scheme for the residual-branch weights.
      lut: (2**LD, K+1) float32 dequantized SH-LUT values.
      lut_q / lut_scale / hemi: quantized table + physical hemi storage.
    """
    entry = build_lut(spec)
    if weight_bits is None:
        weight_bits = min(8, spec.n_bits)
    qmax = 2 ** (int(weight_bits) - 1) - 1
    c = np.asarray(params["c"], np.float64)
    w_b = np.asarray(params["w_b"], np.float64)

    def chan_q(w, axis_out):
        s = np.maximum(np.abs(w).max(axis=tuple(i for i in range(w.ndim) if i != axis_out)), 1e-12) / qmax
        q = np.clip(np.round(w / s), -qmax, qmax).astype(np.int8)
        return q, s.astype(np.float32)

    c_q, c_scale = chan_q(c, c.ndim - 1)
    w_b_q, w_b_scale = chan_q(w_b, w_b.ndim - 1)
    if spec.lut_bits <= 4:
        # int4-packable tables dequantize as f32(code) * f32(scale) — the
        # exact product the kernel's in-lane unpack computes — instead of
        # the f64-product-then-cast form (1-ulp divergence risk).
        lut_f32 = np.float32(entry["lut_q"]) * np.float32(entry["scale"])
    else:
        lut_f32 = np.asarray(entry["lut_q"] * entry["scale"], np.float32)
    return {
        "c_q": jnp.asarray(c_q),
        "c_scale": jnp.asarray(c_scale),
        "w_b_q": jnp.asarray(w_b_q),
        "w_b_scale": jnp.asarray(w_b_scale),
        "lut": jnp.asarray(lut_f32),
        "lut_q": jnp.asarray(entry["lut_q"], jnp.int32),
        "lut_scale": jnp.float32(entry["scale"]),
        "hemi": jnp.asarray(entry["hemi"], jnp.int32),
    }


def kan_layer_apply_quantized(qparams, x: jax.Array, spec: ASPQuantSpec) -> jax.Array:
    """ASP inference path: quantize -> shared-LUT dense basis -> banded matmul.

    Bit-exact contract with kernels/kan_spline's ref.py (the Pallas kernel is
    validated against this composition).
    """
    codes = quantize_input(x, spec)
    basis = dense_basis_from_codes(codes, qparams["lut"], spec)  # (..., F, G+K)
    c = qparams["c_q"].astype(jnp.float32) * qparams["c_scale"]
    y = _spline_matmul(basis, c)
    xq = jax.nn.relu(
        spec.lo + codes.astype(jnp.float32) * spec.code_step
    )
    wb = qparams["w_b_q"].astype(jnp.float32) * qparams["w_b_scale"]
    return y + xq @ wb


# ----------------------------------------------------------------------------
# Stacks
# ----------------------------------------------------------------------------


def init_kan_network(key, kspec: KANSpec):
    spec = kspec.layer_spec()
    keys = jax.random.split(key, len(kspec.dims) - 1)
    return [
        init_kan_layer(k, din, dout, spec)
        for k, din, dout in zip(keys, kspec.dims[:-1], kspec.dims[1:])
    ]


def kan_network_apply(params_list, x, kspec: KANSpec, quantized=False,
                      qparams_list=None, backend=None, interpret=None,
                      key=None):
    """Apply a KAN stack.

    The quantized path resolves its backend through ``repro.runtime``
    (explicit arg > ``use_backend`` scope > ``REPRO_KAN_BACKEND`` env var >
    "ref"):

      "ref":    layered jnp composition — quantize / SH-LUT / banded matmul /
                tanh-rescale per layer, activations round-trip through f32.
      "pallas": the fused multi-layer executor (kernels/kan_spline/pipeline):
                every layer runs in the Pallas kernel and inter-layer
                requantization is fused, activations stay int codes.
      "acim":   the fused executor with the paper's RRAM-ACIM non-idealities
                injected at the MAC stage (pass ``key`` to seed the noise).
    """
    if quantized:
        from .. import runtime
        from .kan_network_deploy import (
            deploy_kan_network,
            kan_network_deploy_apply,
        )

        name = runtime.resolve_backend(backend, default="ref")
        dep = deploy_kan_network(qparams_list, kspec, batch=x.shape[0])
        return kan_network_deploy_apply(
            dep, x, interpret=interpret, backend=name, key=key
        )
    if backend not in (None, "ref"):
        raise ValueError(
            f"backend={backend!r} is a quantized executor; "
            "pass quantized=True with qparams_list"
        )
    spec = kspec.layer_spec()
    h = x
    n = len(params_list)
    for li in range(n):
        h = kan_layer_apply(params_list[li], h, spec)
        if li < n - 1:
            # keep hidden activations inside the knot domain (KAN layers
            # calibrate their domain; tanh is the standard bounded choice)
            h = jnp.tanh(h) * (0.5 * (spec.hi - spec.lo)) + 0.5 * (spec.hi + spec.lo)
    return h


def param_count(kspec: KANSpec) -> int:
    """Edge count x (G + K + 1), matching the paper's #Param convention.

    (17,1,14) with G=5, K=3 -> 31 * 9 = 279 = the paper's KAN1;
    G=68 -> 31 * 72 = 2232 = the paper's KAN2.
    """
    edges = sum(a * b for a, b in zip(kspec.dims[:-1], kspec.dims[1:]))
    return edges * (kspec.grid_size + kspec.order + 1)


# ----------------------------------------------------------------------------
# Grid extension (original-KAN §2.5; used by KAN-NeuroSim step 2)
# ----------------------------------------------------------------------------


def refit_layer_spec(
    params, old_spec: ASPQuantSpec, new_spec: ASPQuantSpec
) -> dict:
    """Refit layer coefficients onto a different (G, K) basis by least squares.

    Samples the old spline densely, solves for new coefficients such that
    the new-spec spline matches — the standard grid-extension transfer,
    generalized to arbitrary target grid size AND order so the co-design
    search (``repro.tune``) can score candidate (G, K) points from one
    trained base network without retraining per candidate.  Refitting to a
    finer grid is near-lossless; to a coarser grid it is the best L2
    approximation — exactly the fidelity/cost trade-off being searched.
    w_b is unchanged.
    """
    new_g, new_k = new_spec.grid_size, new_spec.order
    xs = jnp.linspace(
        old_spec.lo, old_spec.hi, 4 * (new_g + new_k) + 16, dtype=jnp.float32
    )
    old_b = bspline_basis(xs, old_spec.lo, old_spec.hi, old_spec.grid_size, old_spec.order)
    new_b = bspline_basis(xs, new_spec.lo, new_spec.hi, new_g, new_k)
    c = params["c"]  # (F, nb_old, O)
    f, nb_old, o = c.shape
    targets = jnp.einsum("sn,fno->sfo", old_b, c).reshape(len(xs), f * o)
    sol, *_ = jnp.linalg.lstsq(new_b, targets)
    c_new = sol.reshape(new_g + new_k, f, o).transpose(1, 0, 2)
    return {"c": c_new, "w_b": params["w_b"]}


def extend_layer_grid(params, old_spec: ASPQuantSpec, new_g: int) -> dict:
    """Refit layer coefficients on a finer grid by least squares (same K)."""
    return refit_layer_spec(
        params, old_spec, dataclasses.replace(old_spec, grid_size=new_g)
    )
