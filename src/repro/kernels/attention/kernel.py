"""Flash-attention Pallas kernel: online softmax over tiled KV streaming.

One fused kernel computes ``softmax(q k^T * scale + mask) v`` without ever
materializing the (S, T) score matrix: the KV sequence is streamed in tiles
along the innermost (sequential) grid dimension while VMEM scratch carries
the per-query-row running maximum ``m``, running denominator ``l`` and the
rescaled output accumulator — the FlashAttention recurrence (Dao et al.).

Layout/grid conventions (the :mod:`.ops` wrapper produces these):

  * q:    (B, Hkv, S*G, D) — the GQA query group G is folded into the query
          rows (row ``s*G + g``), so ONE KV head tile streamed from HBM
          serves its whole group and every block stays 2-D in-kernel.
  * k, v: (B, Hkv, T, D)
  * qpos: (B, S*G, 1) int32 — each query row's absolute position, as a
          column so it broadcasts against a key row without a relayout.
  * kpos: (B, 1, T) int32 absolute key positions.  Negative kpos marks an
          invalid key (unwritten rolling-cache slot, padded tile) — masked
          under EVERY kind; negative qpos rows finalize to exact 0.
  * grid: (B, Hkv, S/block_q, T/block_k) with the KV tile index innermost —
          scratch persists across the sequential KV sweep, is initialized at
          the first tile and finalized (guarded division) at the last.

Masks are built on the fly from the position vectors — no (S, T) tensor —
matching the ``layers._sdpa_chunk`` semantics and its ``-1e30`` constant:

  * "causal": kpos <= qpos
  * "local":  causal AND kpos > qpos - window (sliding window)
  * "full":   no positional mask (bidirectional / cross attention)

The denominator is guarded at finalization: rows with no valid key emit
exactly 0 instead of a uniform average over masked garbage (the decode
padding bug this kernel's ref path also fixes).  All arithmetic is f32;
the output is cast back to the query dtype.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30  # finite mask constant shared with layers.py (no NaN risk)
_STAT_LANES = 128  # running m/l scratch is lane-replicated for TPU tiling

KINDS = ("causal", "local", "full")


def _dot(a, b, contract):
    return jax.lax.dot_general(
        a, b, (contract, ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


def _flash_kernel(q_ref, k_ref, v_ref, qpos_ref, kpos_ref, out_ref,
                  m_scr, l_scr, acc_scr, *,
                  kind: str, window: int, softcap: float, scale: float):
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    q = q_ref[0, 0].astype(jnp.float32)  # (bq*G, d)
    k = k_ref[0, 0].astype(jnp.float32)  # (bk, d)
    v = v_ref[0, 0].astype(jnp.float32)  # (bk, d)

    s = _dot(q, k, ((1,), (1,))) * scale
    if softcap > 0.0:
        # softcap BEFORE masking — same op order as layers._sdpa_chunk
        s = jnp.tanh(s / softcap) * softcap

    qp = qpos_ref[0]                     # (bq*G, 1) int32
    kp = kpos_ref[0]                     # (1, bk) int32
    mask = kp >= 0                       # key validity, every kind
    if kind in ("causal", "local"):
        mask = mask & (kp <= qp)
    if kind == "local" and window > 0:
        mask = mask & (kp > qp - window)
    mask = jnp.broadcast_to(mask, s.shape)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[:, :1]                # (bq*G, 1)
    l_prev = l_scr[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    # masked lanes would exp to 1 when the whole tile is masked
    # (s == m_new == NEG_INF); the where keeps them at exactly 0
    e = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)      # rescale factor for the old state
    l_new = l_prev * alpha + jnp.sum(e, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + _dot(e, v, ((1,), (0,)))
    m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ik == nk - 1)
    def _finalize():
        l = l_scr[:, :1]
        # guarded denominator: fully-masked rows (padded queries, qpos < 0)
        # emit exact zeros instead of an average over garbage
        out = jnp.where(l > 0.0, acc_scr[...] / jnp.maximum(l, 1e-30), 0.0)
        out_ref[0, 0] = out.astype(out_ref.dtype)


def flash_attention_fused(q, k, v, qpos, kpos, *, group: int, kind: str,
                          window: int, softcap: float, scale: float,
                          block_q: int, block_k: int, interpret: bool):
    """The raw pallas_call on pre-tiled operands (see module docstring for
    the layout contract; ``group`` is G, the query rows per position).
    S must divide by block_q and T by block_k —
    :func:`repro.kernels.attention.ops.flash_attention` pads and slices."""
    if kind not in KINDS:
        raise ValueError(f"kind {kind!r} not in {KINDS}")
    b, hkv, rows, d = q.shape
    t = k.shape[2]
    s = rows // group
    assert s % block_q == 0 and t % block_k == 0, (s, t, block_q, block_k)
    bqg = block_q * group
    grid = (b, hkv, s // block_q, t // block_k)
    kern = functools.partial(
        _flash_kernel, kind=kind, window=int(window),
        softcap=float(softcap), scale=float(scale),
    )
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bqg, d), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda ib, ih, iq, ik: (ib, ih, ik, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda ib, ih, iq, ik: (ib, ih, ik, 0)),
            pl.BlockSpec((1, bqg, 1), lambda ib, ih, iq, ik: (ib, iq, 0)),
            pl.BlockSpec((1, 1, block_k), lambda ib, ih, iq, ik: (ib, 0, ik)),
        ],
        out_specs=pl.BlockSpec((1, 1, bqg, d),
                               lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bqg, _STAT_LANES), jnp.float32),  # running m
            pltpu.VMEM((bqg, _STAT_LANES), jnp.float32),  # running l
            pltpu.VMEM((bqg, d), jnp.float32),            # output acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        interpret=interpret,
        name="flash_attention",
    )(q, k, v, qpos, kpos)
