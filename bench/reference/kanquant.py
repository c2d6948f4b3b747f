"""The quantized KAN layer as the paper states it (arXiv:2409.11418 §3.1),
written out plainly: ASP input codes, the shared LUT of cardinal B-spline
values, per-output-channel symmetric weight codes, and a dense basis."""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class Grid:
    """Input coding of one KAN layer: G intervals of order K on [lo, hi],
    ``bits``-bit input codes, ``lut_bits``-bit LUT values, and
    ``weight_bits``-bit signed weight codes."""

    grid: int
    order: int
    bits: int
    lut_bits: int
    weight_bits: int
    lo: float = -1.0
    hi: float = 1.0

    @property
    def ld(self) -> int:
        """Local bits: the largest L with G * 2**L <= 2**bits (eq. 6)."""
        ld = 0
        while self.grid * 2 ** (ld + 1) <= 2 ** self.bits:
            ld += 1
        return ld

    @property
    def per_interval(self) -> int:
        return 2 ** self.ld

    @property
    def n_codes(self) -> int:
        return self.grid * self.per_interval

    @property
    def n_basis(self) -> int:
        return self.grid + self.order

    @property
    def codes_per_unit(self) -> float:
        return self.n_codes / (self.hi - self.lo)

    @property
    def step(self) -> float:
        """Width of one input code."""
        return (self.hi - self.lo) / self.n_codes


def cardinal_bspline(t, k: int):
    """Cardinal B-spline of order k on [0, k+1] (truncated-power form)."""
    t = np.asarray(t, np.float64)
    out = np.zeros_like(t)
    for j in range(k + 2):
        out += (-1) ** j * math.comb(k + 1, j) * np.maximum(t - j, 0.0) ** k
    return np.where((t >= 0) & (t <= k + 1), out / math.factorial(k), 0.0)


def lut(g: Grid) -> np.ndarray:
    """(2**LD, K+1) f32: value of the d-th active basis at local offset u,
    quantized to ``lut_bits`` unsigned levels of the bump's peak."""
    u = np.arange(g.per_interval) / g.per_interval
    vals = np.stack([cardinal_bspline(u + g.order - d, g.order)
                     for d in range(g.order + 1)], axis=1)
    step = float(cardinal_bspline([(g.order + 1) / 2], g.order)[0]) / (
        2 ** g.lut_bits - 1)
    return (np.round(vals / step) * step).astype(np.float32)


def quantize_weights(w, bits: int) -> tuple:
    """Symmetric per-output-channel codes (last axis): (int8 codes, f32
    scales); the weight is ``codes * scales``."""
    w = w.astype(jnp.float32)
    qmax = 2 ** (bits - 1) - 1
    axes = tuple(range(w.ndim - 1))
    s = jnp.maximum(jnp.max(jnp.abs(w), axis=axes), 1e-12) / qmax
    return jnp.clip(jnp.round(w / s), -qmax, qmax).astype(jnp.int8), s


def codes(x, g: Grid):
    """ASP input codes of x (already in [lo, hi])."""
    q = jnp.floor((x - g.lo) * g.codes_per_unit + 0.5)
    return jnp.clip(q, 0, g.n_codes - 1).astype(jnp.int32)


def dense_basis(c, table, g: Grid):
    """(..., F) codes -> (..., F, G+K) basis: the K+1 LUT values of the
    code's offset placed at bands g .. g+K of its interval g.  Written as
    selects, since a gather of this size takes a TPU hundreds of times
    longer."""
    interval = c // g.per_interval
    offset = c % g.per_interval
    table = jnp.asarray(table)
    vals = [sum(jnp.where(offset == u, table[u, d], 0.0)
                for u in range(g.per_interval))
            for d in range(g.order + 1)]                    # K+1 x (..., F)
    band = jnp.arange(g.n_basis) - interval[..., None]      # (..., F, nb)
    return sum(jnp.where(band == d, vals[d][..., None], 0.0)
               for d in range(g.order + 1))


def dequantize(w) -> jax.Array:
    """The f32 weight of a (codes, scales) pair."""
    return w[0].astype(jnp.float32) * w[1]


def layer(c, relu_in, coef, wb, table, g: Grid):
    """One KANLinear: sum_f sum_i B_i(code_f) coef[f,i,o] + relu(in) @ wb,
    with ``coef`` and ``wb`` as (codes, scales) pairs."""
    return layer_f32(c, relu_in, dequantize(coef), dequantize(wb), table, g)


def layer_f32(c, relu_in, coef, wb, table, g: Grid):
    """``layer`` with the weights already dequantized to f32."""
    b = dense_basis(c, table, g)
    rows = c.shape[:-1]
    y = jnp.dot(b.reshape(rows + (-1,)), coef.reshape(-1, coef.shape[-1]),
                precision=HI)
    return y + jnp.dot(jnp.maximum(relu_in, 0.0), wb, precision=HI)
