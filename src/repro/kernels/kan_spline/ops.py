"""jit'd public wrapper around the kan_spline Pallas kernel.

Handles padding to block multiples (padded F rows get zero weights so their
basis contribution vanishes; padded B rows are sliced off; padded O columns
are sliced off) and exposes a convenience entry point that consumes the
qparams dict produced by core.kan_layer.quantize_kan_layer.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...core.asp_quant import ASPQuantSpec
from .. import default_interpret
from .kernel import kan_spline_pallas

__all__ = ["kan_spline", "kan_spline_from_qparams"]


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


@functools.partial(
    jax.jit,
    static_argnames=("spec", "block_b", "block_o", "block_f", "interpret"),
)
def kan_spline(
    codes: jax.Array,   # (B, F) int32
    lut: jax.Array,     # (2**LD, K+1)
    wc: jax.Array,      # (F, G+K, O)
    wb: jax.Array,      # (F, O)
    spec: ASPQuantSpec,
    *,
    block_b: int = 128,
    block_o: int = 128,
    block_f: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """Pad to block multiples, run the kernel, slice back to (B, O).
    ``interpret=None`` compiles on a TPU and interprets elsewhere
    (:func:`repro.kernels.default_interpret`)."""
    if interpret is None:
        interpret = default_interpret()
    bsz, f = codes.shape
    o = wc.shape[-1]
    nb = spec.num_basis

    bb = min(block_b, _round_up(bsz, 8))
    bo = min(block_o, _round_up(o, 128))
    bf = min(block_f, _round_up(f, 8))

    bp, fp, op = _round_up(bsz, bb), _round_up(f, bf), _round_up(o, bo)
    codes_p = jnp.pad(codes, ((0, bp - bsz), (0, fp - f)))
    wc_p = jnp.pad(wc, ((0, fp - f), (0, 0), (0, op - o))).reshape(fp * nb, op)
    wb_p = jnp.pad(wb, ((0, fp - f), (0, op - o)))

    out = kan_spline_pallas(
        codes_p, lut, wc_p, wb_p, spec,
        block_b=bb, block_o=bo, block_f=bf, interpret=interpret,
    )
    return out[:bsz, :o]


def kan_spline_from_qparams(
    codes: jax.Array, qparams: dict, spec: ASPQuantSpec, *,
    interpret: bool | None = None,
) -> jax.Array:
    """Run the kernel from quantize_kan_layer output (dequantized weights)."""
    wc = qparams["c_q"].astype(jnp.float32) * qparams["c_scale"]
    wb = qparams["w_b_q"].astype(jnp.float32) * qparams["w_b_scale"]
    return kan_spline(codes, qparams["lut"], wc, wb, spec, interpret=interpret)
