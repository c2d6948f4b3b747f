"""Pallas TPU kernel: fused ASP spline-basis construction + banded matmul.

TPU-native realization of the paper's B(X) datapath for ONE layer:

  * PowerGap bit split, SH-LUT retrieval and banded basis placement ->
    :func:`repro.kernels.kan_spline.pipeline.banded_basis` (shared with the
    fused multi-layer pipeline)
  * spline MAC         -> dense (bB, bF*(G+K)) x (bF*(G+K), bO) on the MXU
                          ("B(X) on word lines x c' in the RRAM array")
  * the w_b * relu(x) residual branch is fused into the same tile

Grid: (B/bB, O/bO, F/bF); the F axis is the contraction — partial products
accumulate into the output tile (revisited across the last grid dimension,
per the TPU grid-iteration guarantee).  The VMEM working set is the
pipeline's (:func:`repro.kernels.kan_spline.pipeline.vmem_bytes`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.asp_quant import ASPQuantSpec
from .pipeline import _VMEM_LIMIT, _dot, banded_basis


def _kan_spline_kernel(
    codes_ref,  # (bB, bF) int32
    lut_ref,    # (2**LD, K+1) f32, SMEM
    wc_ref,     # (bF * NB, bO) f32/bf16
    wb_ref,     # (bF, bO) f32/bf16
    out_ref,    # (bB, bO) f32
    *,
    spec: ASPQuantSpec,
):
    k_step = pl.program_id(2)
    codes = codes_ref[...]
    acc = _dot(banded_basis(codes, lut_ref, spec),
               wc_ref[...].astype(jnp.float32))

    # --- fused residual branch: relu(deq(codes)) @ wb
    xdeq = spec.lo + codes.astype(jnp.float32) * spec.code_step
    acc = acc + _dot(jnp.maximum(xdeq, 0.0), wb_ref[...].astype(jnp.float32))

    @pl.when(k_step == 0)
    def _init():
        out_ref[...] = acc

    @pl.when(k_step > 0)
    def _accum():
        out_ref[...] += acc


def kan_spline_pallas(
    codes: jax.Array,   # (B, F) int32
    lut: jax.Array,     # (2**LD, K+1)
    wc: jax.Array,      # (F * NB, O)  — flattened (f, i) rows
    wb: jax.Array,      # (F, O)
    spec: ASPQuantSpec,
    *,
    block_b: int = 128,
    block_o: int = 128,
    block_f: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Raw pallas_call; caller guarantees divisibility (see ops.py)."""
    bsz, f = codes.shape
    o = wc.shape[-1]
    nb = spec.num_basis
    assert wc.shape[0] == f * nb, (wc.shape, f, nb)
    assert bsz % block_b == 0 and o % block_o == 0 and f % block_f == 0

    grid = (bsz // block_b, o // block_o, f // block_f)
    kernel = functools.partial(_kan_spline_kernel, spec=spec)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, block_f), lambda i, j, k: (i, k)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((block_f * nb, block_o), lambda i, j, k: (k, j)),
            pl.BlockSpec((block_f, block_o), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((block_b, block_o), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((bsz, o), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
        name="kan_spline",
    )(codes, lut.astype(jnp.float32), wc, wb)
