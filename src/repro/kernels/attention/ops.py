"""Shape/layout wrapper for the fused flash-attention kernel.

``flash_attention`` accepts the model-facing GQA layout used across
:mod:`repro.models.layers` — q: (B, S, Hq, D), k/v: (B, T, Hkv, D) — folds
each KV head's query group next to the query rows, pads S and T to tile
multiples (padded positions carry -1, so the kernel masks them and emits
exact zeros for padded query rows), runs the Pallas kernel and slices the
result back.  Off-TPU the kernel executes in interpret mode automatically.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .. import default_interpret
from .kernel import KINDS, flash_attention_fused

__all__ = ["flash_attention"]


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _positions(p, b: int, n: int, offset: int):
    """Normalize a position operand to (B, n) int32; None = arange+offset."""
    if p is None:
        p = jnp.arange(n, dtype=jnp.int32) + offset
    p = jnp.asarray(p, jnp.int32)
    if p.ndim == 1:
        p = p[None]
    return jnp.broadcast_to(p, (b, n))


def flash_attention(q, k, v, *, kind: str = "causal", qpos=None, kpos=None,
                    window: int = 0, softcap: float = 0.0,
                    scale: float | None = None, block_q: int = 128,
                    block_k: int = 128, interpret: bool | None = None):
    """Memory-efficient fused attention over GQA layouts.

    Args:
      q: (B, S, Hq, D); k, v: (B, T, Hkv, D) with Hq % Hkv == 0.
      kind: "causal" (kpos <= qpos), "local" (causal AND
        kpos > qpos - window), or "full" (no positional mask).
      qpos / kpos: int32 absolute positions, shaped (S,)/(B, S) resp.
        (T,)/(B, T).  None means contiguous right-aligned positions
        (``arange(S) + (T - S)`` / ``arange(T)`` — the `_sdpa` defaults).
        Negative kpos marks an invalid key (unwritten rolling-cache slot)
        and is masked under every kind; query rows whose mask ends up empty
        (e.g. negative qpos padding) return exactly 0.
      window: sliding-window size for kind="local" (<= 0 disables it).
      softcap: logit soft-cap, applied before masking (0 disables).
      scale: logit scale; defaults to 1/sqrt(D).
      interpret: run the Pallas kernel in interpret mode; None = auto
        (True off-TPU).

    Returns (B, S, Hq, D) in q's dtype.
    """
    if kind not in KINDS:
        raise ValueError(f"kind {kind!r} not in {KINDS}")
    if interpret is None:
        interpret = default_interpret()
    b, s, hq, d = q.shape
    t, hkv = k.shape[1], k.shape[2]
    if hq % hkv:
        raise ValueError(f"Hq={hq} not a multiple of Hkv={hkv}")
    g = hq // hkv
    if scale is None:
        scale = 1.0 / math.sqrt(d)

    qpos = _positions(qpos, b, s, offset=t - s)
    kpos = _positions(kpos, b, t, offset=0)

    bq = min(block_q, _round_up(s, 8))
    bk = min(block_k, _round_up(t, 8))
    sp, tp = _round_up(s, bq), _round_up(t, bk)

    if sp != s:
        q = jnp.pad(q, ((0, 0), (0, sp - s), (0, 0), (0, 0)))
        qpos = jnp.pad(qpos, ((0, 0), (0, sp - s)), constant_values=-1)
    if tp != t:
        k = jnp.pad(k, ((0, 0), (0, tp - t), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, tp - t), (0, 0), (0, 0)))
        kpos = jnp.pad(kpos, ((0, 0), (0, tp - t)), constant_values=-1)
    # (B, S, Hq, D) -> (B, Hkv, S*G, D): row s*G + g is query position s of
    # group member g, so one KV head tile serves its whole group
    qr = q.reshape(b, sp, hkv, g, d).transpose(0, 2, 1, 3, 4)
    qr = qr.reshape(b, hkv, sp * g, d)
    qrow = jnp.repeat(qpos, g, axis=1)[:, :, None]  # (B, S*G, 1)

    out = flash_attention_fused(
        qr, k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3), qrow,
        kpos[:, None, :], group=g, kind=kind, window=window, softcap=softcap,
        scale=scale, block_q=bq, block_k=bk, interpret=interpret,
    )
    out = out.reshape(b, hkv, sp, g, d)[:, :, :s]
    return out.transpose(0, 2, 1, 3, 4).reshape(b, s, hq, d)
