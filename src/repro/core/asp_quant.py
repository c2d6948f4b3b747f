"""ASP-KAN-HAQ: Alignment-Symmetry and PowerGap KAN hardware-aware quantization.

Paper §3.1.  Two constraints on the input quantization grid:

* **Alignment-Symmetry** (phase one, eq. (4)): the quantization grid is an
  integer multiple ``L`` of the knot grid, ``G * L <= 2**n``.  Zero offset
  between the grids means every basis function B_i(x) is the *same* function of
  the intra-interval offset, so ONE look-up table is shared by all G+K bases;
  the cardinal bump's mirror symmetry ``b_K(t) = b_K(K+1-t)`` then halves the
  shared LUT (the "Sharable-Hemi LUT", SH-LUT).

* **PowerGap** (phase two, eq. (5)): knot spacing is a power of two,
  ``L = 2**LD`` (eq. (6): ``G * 2**LD <= 2**n``), so a quantized code splits
  into bit fields::

      code = [ global bits : ceil(log2 G) ][ local bits : LD ]
      global = code >> LD      -> knot-interval index g  ("which B_i band")
      local  = code &  (2**LD - 1) -> intra-interval offset ("where in the bump")

  On the paper's silicon this replaces an 8-bit decoder + 2L:1 TG-MUX trees
  with split (n-LD)-bit / LD-bit decoders and L:1 MUXes.  On TPU (see
  ``kernels/kan_spline``) the same bit split removes per-element dynamic
  gathers: the dense basis row is the SH-LUT value placed at band position
  ``global``, built with iota-compare/select — VPU-friendly, MXU-ready.

All functions are pure and jit-safe unless noted.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

from .bspline import bspline_basis, cardinal_bump

__all__ = [
    "ASPQuantSpec",
    "max_ld",
    "resolve_layer_bits",
    "lut_scale",
    "quantize_input",
    "dequantize_input",
    "build_lut",
    "hemi_fold",
    "hemi_unfold",
    "lookup_active",
    "dense_basis_from_codes",
    "quantized_dense_basis",
    "pact_quantize",
    "pact_basis_tables",
    "pact_dense_basis",
]


def max_ld(grid_size: int, n_bits: int) -> int:
    """Largest LD with ``G * 2**LD <= 2**n`` (paper eq. (6)).  -1 if none."""
    ld = -1
    while grid_size * 2 ** (ld + 1) <= 2**n_bits:
        ld += 1
    return ld


def resolve_layer_bits(n_bits, n_layers: int, grid_size: int) -> tuple:
    """Normalize a scalar-or-sequence bit width into a per-layer tuple.

    The mixed-precision entry point: every quantization surface that accepts
    ``n_bits`` as either one int (uniform, the paper's deployment) or a
    per-layer sequence (KANtize-style mixed precision) funnels through here.
    Each layer's width must independently satisfy PowerGap (eq. (6)):
    ``G * 2**LD <= 2**b`` must have a solution, i.e. ``max_ld(G, b) >= 0`` —
    an invalid allocation raises ``ValueError``, it is NEVER clamped.
    """
    if isinstance(n_bits, (int, np.integer)):
        bits = (int(n_bits),) * n_layers
    else:
        bits = tuple(int(b) for b in n_bits)
        if len(bits) != n_layers:
            raise ValueError(
                f"{len(bits)} per-layer bit widths for {n_layers} layers"
            )
    for li, b in enumerate(bits):
        if not 2 <= b <= 16:
            raise ValueError(f"layer {li}: n_bits={b} outside [2, 16]")
        if max_ld(grid_size, b) < 0:
            raise ValueError(
                f"layer {li}: n_bits={b} is PowerGap-invalid for "
                f"G={grid_size} (G * 2**LD <= 2**n unsatisfiable, eq. (6))"
            )
    return bits


def lut_scale(spec: "ASPQuantSpec") -> float:
    """Dequantization scale of the SH-LUT int codes (``lut ~= lut_q * s``).

    Derivable from the spec alone — bump peak over the code ceiling.
    """
    K = spec.order
    qmax = 2**spec.lut_bits - 1
    vmax = cardinal_bump(np.array([(K + 1) / 2.0]), K)[0]
    return float(vmax / qmax)


@dataclasses.dataclass(frozen=True)
class ASPQuantSpec:
    """Static description of one ASP-quantized KAN layer input.

    Attributes:
      grid_size: G, number of knot intervals.
      order: K, B-spline order (K=3 -> cubic).
      n_bits: n, system input bit width (paper uses 8).
      lut_bits: precision of stored B(X) values (feeds TM-DV-IG; paper 8).
      lo/hi: float input domain mapped onto the knot grid.
      signed: if True the code range is centered (layers with negative
        inputs, paper §3.1); purely an affine-map choice, the bit split is
        applied to the shifted unsigned code either way.
    """

    grid_size: int
    order: int = 3
    n_bits: int = 8
    lut_bits: int = 8
    lo: float = -1.0
    hi: float = 1.0
    signed: bool = False

    def __post_init__(self):
        if self.grid_size < 1:
            raise ValueError("grid_size must be >= 1")
        if max_ld(self.grid_size, self.n_bits) < 0:
            raise ValueError(
                f"G={self.grid_size} does not fit in {self.n_bits} bits: "
                "G * 2**LD <= 2**n unsatisfiable (eq. (6))"
            )

    @property
    def ld(self) -> int:
        """LD: local bit width (log2 of codes per knot interval)."""
        return max_ld(self.grid_size, self.n_bits)

    @property
    def codes_per_interval(self) -> int:
        return 2**self.ld

    @property
    def num_codes(self) -> int:
        """Data range is [0, G * 2**LD - 1] (paper §3.1.B)."""
        return self.grid_size * self.codes_per_interval

    @property
    def num_basis(self) -> int:
        return self.grid_size + self.order

    @property
    def global_bits(self) -> int:
        return max(1, math.ceil(math.log2(self.grid_size)))

    @property
    def knot_step(self) -> float:
        return (self.hi - self.lo) / self.grid_size

    @property
    def code_step(self) -> float:
        return self.knot_step / self.codes_per_interval


# ----------------------------------------------------------------------------
# Input quantization (the ASP affine map)
# ----------------------------------------------------------------------------


def quantize_input(x: jax.Array, spec: ASPQuantSpec) -> jax.Array:
    """Map float x in [lo, hi] to int32 code in [0, G*2**LD - 1].

    Codes are LEFT-aligned on the knot grid: code q corresponds to
    x = lo + q * code_step, so code q's knot interval is exactly q >> LD.
    This zero offset between grids is the Alignment property (paper §3.1
    phase one, eq. (4): the quantization grid is an integer multiple of
    the knot grid).
    """
    scale = 1.0 / spec.code_step
    q = jnp.floor((x - spec.lo) * scale + 0.5).astype(jnp.int32)
    return jnp.clip(q, 0, spec.num_codes - 1)


def dequantize_input(codes: jax.Array, spec: ASPQuantSpec) -> jax.Array:
    """Inverse affine map of :func:`quantize_input` (code grid -> floats)."""
    return spec.lo + codes.astype(jnp.float32) * spec.code_step


# ----------------------------------------------------------------------------
# SH-LUT construction
# ----------------------------------------------------------------------------


def build_lut(spec: ASPQuantSpec) -> dict:
    """Build the shared LUT of active-basis values (host-side, numpy).

    The payoff of Alignment-Symmetry (paper §3.1, Fig. 3): ONE table of
    (2**LD, K+1) bump values serves every basis function of every input
    feature, and its mirror symmetry halves the physical storage ("hemi").

    Returns dict with:
      "lut":      (2**LD, K+1) float64, lut[u, d] = value of the d-th active
                  basis B_{g+d} at local offset u  (= b_K(u/2**LD + K - d)).
      "lut_q":    same, quantized to ``lut_bits`` unsigned ints.
      "scale":    dequantization scale (lut ~= lut_q * scale).
      "hemi":     1-D hemi storage, ceil(((K+1)*2**LD)/2)+1 entries —
                  the physical SH-LUT (50% of the full table, paper Fig. 3).
      "flat_q":   full flattened (K+1)*2**LD int table reconstructed from
                  hemi (for checking hemi_unfold round-trips).
    """
    K, U = spec.order, spec.codes_per_interval
    u = np.arange(U, dtype=np.float64) / U
    # active slot d covers bump segment s = K - d  (see kernels/kan_spline).
    lut = np.stack([cardinal_bump(u + (K - d), K) for d in range(K + 1)], axis=1)
    scale = lut_scale(spec)  # bump peak / (2**lut_bits - 1)
    lut_q = np.round(lut / scale).astype(np.int64)
    hemi = hemi_fold(lut_q, spec)
    flat_q = hemi_unfold(hemi, spec)
    return {
        "lut": lut,
        "lut_q": lut_q,
        "scale": scale,
        "hemi": hemi,
        "flat_q": flat_q,
    }


def _flat_index_arrays(spec: ASPQuantSpec):
    """Flat bump-argument index f = s * 2**LD + local over the full table."""
    K, U = spec.order, spec.codes_per_interval
    total = (K + 1) * U
    f = np.arange(total)
    return f, total


def hemi_fold(lut_q: np.ndarray, spec: ASPQuantSpec) -> np.ndarray:
    """Fold the full (2**LD, K+1) table into hemi storage using symmetry.

    The Sharable-Hemi LUT (paper §3.1, Fig. 3): the cardinal bump's mirror
    symmetry b_K(t) = b_K(K+1-t) means the table's second half duplicates
    its first, so silicon stores 50% + 1 entries and reflects on retrieval.
    Flat bump position f = s*2**LD + u  (t = f / 2**LD in [0, K+1)) satisfies
    b(t) = b(K+1 - t), i.e. value at f equals value at total - f.  Physical
    storage keeps f in [0, total//2]; larger f are reflected on retrieval.
    """
    K, U = spec.order, spec.codes_per_interval
    f, total = _flat_index_arrays(spec)
    # reorganize (U, K+1)[u, d] -> flat[s*U + u] with s = K - d
    flat = np.zeros(total, dtype=lut_q.dtype)
    for d in range(K + 1):
        s = K - d
        flat[s * U : (s + 1) * U] = lut_q[:, d]
    half = total // 2
    return flat[: half + 1].copy()


def hemi_unfold(hemi: np.ndarray, spec: ASPQuantSpec) -> np.ndarray:
    """Reconstruct the full flat table from hemi storage (retrieval logic)."""
    f, total = _flat_index_arrays(spec)
    half = total // 2
    reflect = np.where(f <= half, f, total - f)
    return hemi[reflect]


# ----------------------------------------------------------------------------
# Quantized basis evaluation (the reference retrieval path)
# ----------------------------------------------------------------------------


def lookup_active(codes: jax.Array, lut: jax.Array, spec: ASPQuantSpec):
    """Active-basis retrieval: code -> (global g, (..., K+1) active values).

    ``lut`` is the (2**LD, K+1) table (float or dequantized).  This is the
    PowerGap bit split (paper §3.1 phase two, eq. (5)): shift/mask replaces
    the paper's split (n-LD)-bit / LD-bit decoders.
    """
    g = jax.lax.shift_right_logical(codes, spec.ld)
    local = jax.lax.bitwise_and(codes, spec.codes_per_interval - 1)
    vals = jnp.take(lut, local, axis=0)  # (..., K+1)
    return g, vals


def dense_basis_from_codes(
    codes: jax.Array, lut: jax.Array, spec: ASPQuantSpec
) -> jax.Array:
    """Dense (..., G+K) basis matrix built from the shared LUT.

    Implements the TPU-native ASP retrieval: place the K+1 active LUT values
    at band positions g..g+K via iota-compare/select (no dynamic gather on
    the output side).  This is the oracle for kernels/kan_spline.
    """
    g, vals = lookup_active(codes, lut, spec)
    nb = spec.num_basis
    iota = jnp.arange(nb, dtype=jnp.int32)  # basis index i
    # d = i - g in [0, K] selects active slot d.
    d = iota - g[..., None]
    active = (d >= 0) & (d <= spec.order)
    dd = jnp.clip(d, 0, spec.order)
    out = jnp.where(active, jnp.take_along_axis(
        jnp.broadcast_to(vals, g.shape + (spec.order + 1,)), dd * active, axis=-1
    ), 0.0)
    return out.astype(lut.dtype)


def quantized_dense_basis(x: jax.Array, spec: ASPQuantSpec, lut_entry: dict | None = None):
    """float x -> quantize -> dense dequantized basis (..., G+K)."""
    if lut_entry is None:
        lut_entry = build_lut(spec)
    lut = jnp.asarray(lut_entry["lut_q"] * lut_entry["scale"], dtype=jnp.float32)
    codes = quantize_input(x, spec)
    return dense_basis_from_codes(codes, lut, spec)


# ----------------------------------------------------------------------------
# Conventional (PACT-style) baseline — misaligned grids
# ----------------------------------------------------------------------------


def pact_quantize(x: jax.Array, alpha: float, n_bits: int) -> jax.Array:
    """PACT quantization (Choi et al. 2018): clip to [0, alpha], uniform n-bit.

    The quantization step alpha/(2**n - 1) is in general NOT an integer
    multiple of the knot step, so the knot and quantization grids are
    misaligned — each B_i(x) then needs its own code->value table.
    """
    q = jnp.round(jnp.clip(x, 0.0, alpha) / alpha * (2**n_bits - 1))
    return q.astype(jnp.int32)


def pact_basis_tables(
    spec: ASPQuantSpec, alpha: float | None = None
) -> np.ndarray:
    """Per-basis LUTs for the conventional path: (G+K, 2**n) table.

    table[i, q] = B_i(x(q)) with x(q) = q * alpha / (2**n - 1) + lo.
    Distinct per i because of grid misalignment (paper Fig. 2) — this is what
    costs G+K programmable LUTs + 8-bit decoders + 2L:1 MUX trees on silicon,
    and per-element dynamic gathers on TPU.
    """
    if alpha is None:
        alpha = spec.hi - spec.lo
    n = spec.n_bits
    q = np.arange(2**n, dtype=np.float64)
    x = spec.lo + q * alpha / (2**n - 1)
    tau = (x - spec.lo) / spec.knot_step  # [0, G]
    tables = np.stack(
        [cardinal_bump(tau - i + spec.order, spec.order) for i in range(spec.num_basis)],
        axis=0,
    )
    qmax = 2**spec.lut_bits - 1
    vmax = cardinal_bump(np.array([(spec.order + 1) / 2.0]), spec.order)[0]
    return np.round(tables / (vmax / qmax)) * (vmax / qmax)


def pact_dense_basis(x: jax.Array, spec: ASPQuantSpec, tables: np.ndarray) -> jax.Array:
    """Baseline dense basis via per-B_i tables (gather per basis function)."""
    alpha = spec.hi - spec.lo
    codes = pact_quantize(x - spec.lo, alpha, spec.n_bits)
    t = jnp.asarray(tables, dtype=jnp.float32)  # (G+K, 2**n)
    return jnp.take(t, codes, axis=1).transpose(
        tuple(range(1, codes.ndim + 1)) + (0,)
    )
