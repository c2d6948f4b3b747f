"""Fused multi-layer quantized KAN executor on the Pallas path.

The paper's hardware win comes from keeping the *whole* quantized datapath
(eq. (1)-(3): ASP PowerGap decode -> SH-LUT retrieval -> banded MAC) on the
accelerator.  ``kernel.py`` covers one layer; this module chains layers so
that activations stay **int codes** between layers instead of round-tripping
through dequantized f32 in Python:

  * each layer runs the same fused datapath as ``_kan_spline_kernel``, on
    the grid ``(Bp/bb, Fp/bf, Op/bo)``: the output tile is innermost, so
    the banded basis of a ``(bb, bf)`` code tile is built once and reused
    by every output tile of the layer (:attr:`LayerPlan.basis_reuse`);
  * the inter-layer boundary — tanh domain rescale followed by
    ``quantize_input`` re-coding (the TPU analogue of the paper's N:1 TMDV
    input generator feeding the next array) — is fused into the producing
    layer's kernel, executed once per output tile on the final contraction
    step;
  * the whole stack runs under ONE jit: no per-layer Python dispatch, no
    host sync, one padding plan for the entire network.

Geometry is described by a static, hashable :class:`PipelinePlan`:

  * batch is padded once to a multiple of ``bb``;
  * every inter-layer boundary dim is padded to a multiple of 128 (the
    producing layer's ``bo`` and the consuming layer's ``bf`` both divide it,
    so codes flow between kernels with NO reslicing);
  * padded weight rows/cols are zero, so padded lanes contribute nothing —
    the boundary requantizer maps their tanh(0) midpoint code to rows whose
    weights are zero in the next layer.

Two residual-branch flavors cover both deployment surfaces:

  * ``residual_raw=False`` (KAN stacks, ``core.kan_layer``): the ReLU branch
    reads ``relu(dequantize(codes))`` — bit-compatible with
    ``kan_layer_apply_quantized``.
  * ``residual_raw=True`` (KAN-FFN, ``core.kan_ffn_deploy``): the ReLU branch
    reads the RAW pre-squash activation (models/layers._kan_linear contract),
    which the previous layer emits as a second f32 output.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.asp_quant import ASPQuantSpec

__all__ = [
    "LayerPlan",
    "PipelinePlan",
    "make_pipeline_plan",
    "model_shardable",
    "normalize_tile_overrides",
    "shard_local_plan",
    "validate_plan",
    "weight_bits",
    "packs_weights",
    "layer_weight_keys",
    "pad_layer_weights",
    "pack_layer_weights",
    "unpacked_wc",
    "banded_basis",
    "vmem_bytes",
    "run_pipeline_layer",
    "kan_pipeline",
    "kan_pipeline_impl",
]


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _pow2_at_least(x: int, lo: int = 8, hi: int = 128) -> int:
    p = lo
    while p < min(x, hi):
        p *= 2
    return p


# ----------------------------------------------------------------------------
# Static geometry plan
# ----------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class LayerPlan:
    """Static per-layer geometry + boundary behavior (hashable, jit-static)."""

    spec: ASPQuantSpec          # quantization grid of THIS layer's input
    next_spec: ASPQuantSpec | None  # None -> last layer (emit f32 only)
    f: int                      # logical input width
    o: int                      # logical output width
    fp: int                     # padded input width  (multiple of bf)
    op: int                     # padded output width (multiple of bo)
    bb: int
    bo: int
    bf: int
    residual_raw: bool          # ReLU branch source: raw f32 vs deq(codes)

    @property
    def emit_codes(self) -> bool:
        return self.next_spec is not None

    @property
    def basis_reuse(self) -> int:
        """Output tiles that share one banded basis tile (``op // bo``).

        The kernel builds the basis once per (row block, feature block) and
        runs this many MACs on it; at 1 it keeps no basis scratch.
        """
        return self.op // self.bo


@dataclasses.dataclass(frozen=True)
class PipelinePlan:
    b: int                      # logical batch
    bp: int                     # padded batch (multiple of layers[0].bb)
    layers: tuple               # tuple[LayerPlan, ...]


# VMEM: the limit every pipeline kernel is compiled with, and the share of
# it a plan's estimated working set (:func:`vmem_bytes`) may take.  A TPU
# v5e core has 128 MiB of VMEM but a 16 MiB default scoped limit, which the
# G=68 weight tile (128*71 x 128 f32, double-buffered) alone would nearly fill.
_VMEM_LIMIT = 64 * 1024 * 1024
_VMEM_BUDGET = 48 * 1024 * 1024


def _tile_bytes(rows: int, cols: int, itemsize: int = 4) -> int:
    """VMEM bytes of a 2-D buffer once padded to the TPU's native tile
    (8 sublanes of 32-bit words x 128 lanes; narrower dtypes pack more rows
    per sublane)."""
    return (_round_up(rows, 8 * 4 // itemsize) * _round_up(cols, 128)
            * itemsize)


def vmem_bytes(bb: int, bf: int, bo: int, op: int,
               spec: ASPQuantSpec) -> int:
    """Estimated VMEM working set of one fused-layer grid step.

    Counts every block with lane/sublane padding: the double-buffered
    inputs and outputs (codes, raw activation, f32 weight tile, wb, psum
    noise, y, out codes — the worst case over backends and packing), the
    unpacked int4 weight scratch, the 0/1 expansion matrix, the
    (bb, bf*(G+K)) band/basis planes and the K+1 per-feature LUT planes.
    When the layer has more than one output tile (``op > bo``) it adds the
    scratch that outlives a grid step: the (bb, bf*(G+K)) basis and the
    (bb, bf) ReLU operand, built once per (row block, feature block), and
    the f32 accumulator of the whole (bb, op) output row block.
    """
    nbf = bf * spec.num_basis
    io = (2 * _tile_bytes(bb, bf) + _tile_bytes(nbf, bo) + _tile_bytes(bf, bo)
          + 3 * _tile_bytes(bb, bo))
    temps = (_tile_bytes(nbf, bo) + _tile_bytes(bf, nbf)
             + 3 * _tile_bytes(bb, nbf)
             + (spec.order + 4) * _tile_bytes(bb, bf))
    if op > bo:
        temps += (_tile_bytes(bb, nbf) + _tile_bytes(bb, bf)
                  + (op // bo) * _tile_bytes(bb, bo))
    return 2 * io + temps


def _is_pow2(x: int) -> bool:
    return x >= 1 and (x & (x - 1)) == 0


def normalize_tile_overrides(tile_overrides, n_layers: int) -> tuple | None:
    """Canonicalize tile overrides to a per-layer ((bb, bo, bf), ...) tuple.

    Accepts a single (bb, bo, bf) triple (broadcast to every layer) or a
    per-layer sequence of triples.  ``bb`` must agree across layers — the
    batch pad ``bp`` is shared by the whole stack.
    """
    if tile_overrides is None:
        return None
    ov = tuple(tile_overrides)
    if len(ov) == 3 and all(not hasattr(v, "__len__") for v in ov):
        ov = tuple((int(ov[0]), int(ov[1]), int(ov[2])) for _ in range(n_layers))
    else:
        ov = tuple((int(b), int(o), int(f)) for b, o, f in ov)
    if len(ov) != n_layers:
        raise ValueError(f"{len(ov)} tile overrides for {n_layers} layers")
    if len({b for b, _, _ in ov}) != 1:
        raise ValueError(f"per-layer bb must agree (shared batch pad): {ov}")
    return ov


def make_pipeline_plan(
    batch: int,
    dims: tuple,
    specs: tuple,
    *,
    residual_raw: bool = False,
    max_block_b: int = 128,
    tile_overrides=None,
) -> PipelinePlan:
    """Choose block sizes + padded dims for a whole stack from shapes alone.

    dims: (F0, O0=F1, O1=F2, ...) — len(dims) == n_layers + 1.
    specs: per-layer ASPQuantSpec, len == n_layers.

    ``tile_overrides`` (from ``repro.tune.tiles`` / the plan cache's tuned
    registry) replaces the heuristic block sizes with explicit per-layer
    ``(bb, bo, bf)`` triples.  Overrides change ONLY the tiling of the grid,
    never the padded dims ``fp``/``op`` — deployed weight bundles padded
    under the heuristic plan stay valid verbatim under any tuned plan, and
    the 128-padded inter-layer boundary contract is untouched.  Invalid
    overrides (non-power-of-two, not dividing the padded dim, a working
    set over the VMEM budget) raise ``ValueError``.

    The kernel's grid is ``(Bp/bb, Fp/bf, Op/bo)`` with the output tile
    innermost, so a layer holds the f32 accumulator of a whole
    ``(bb, op)`` row block in VMEM: :func:`vmem_bytes` counts it, and the
    heuristic halves ``bb`` until every layer fits.
    """
    n_layers = len(dims) - 1
    if len(specs) != n_layers:
        raise ValueError(f"{len(specs)} specs for {n_layers} layers")
    overrides = normalize_tile_overrides(tile_overrides, n_layers)

    bb = min(max_block_b, _round_up(batch, 8))
    if overrides is not None:
        bb = overrides[0][0]
        if bb < 8 or bb % 8:
            raise ValueError(f"bb override must be a multiple of 8 >= 8: {bb}")
        bb = min(bb, _round_up(batch, 8))
    bp = _round_up(batch, bb)

    layers = []
    for li in range(n_layers):
        f, o = dims[li], dims[li + 1]
        spec = specs[li]
        # Features and outputs ride the 128 lanes, so bf/bo are 128 or the
        # whole (small) padded dim: fp/op depend on the layer alone and
        # DeployedKAN.replan can swap the batch without re-padding weights.
        bf = _pow2_at_least(f) if li == 0 else 128
        bo = 128
        fp = _round_up(f, bf) if li == 0 else _round_up(f, 128)
        op = _round_up(o, bo)
        if overrides is not None:
            _, bo_c, bf_c = overrides[li]
            if not (_is_pow2(bo_c) and 8 <= bo_c <= 128 and op % bo_c == 0):
                raise ValueError(
                    f"layer {li}: bo override {bo_c} invalid for op={op}"
                )
            if not (_is_pow2(bf_c) and 8 <= bf_c <= 128 and fp % bf_c == 0):
                raise ValueError(
                    f"layer {li}: bf override {bf_c} invalid for fp={fp}"
                )
            bo, bf = bo_c, bf_c
        layers.append(
            LayerPlan(
                spec=spec,
                next_spec=specs[li + 1] if li + 1 < n_layers else None,
                f=f, o=o, fp=fp, op=op,
                bb=bb, bo=bo, bf=bf,
                residual_raw=residual_raw,
            )
        )
    if overrides is None:
        # the batch block is the one free dim: halve it until every layer's
        # working set fits
        while bb > 8 and any(
            vmem_bytes(bb, lp.bf, lp.bo, lp.op, lp.spec) > _VMEM_BUDGET
            for lp in layers
        ):
            bb = _round_up(bb // 2, 8)
        layers = [dataclasses.replace(lp, bb=bb) for lp in layers]
        bp = _round_up(batch, bb)
    for li, lp in enumerate(layers):
        _check_vmem(li, lp)
    return PipelinePlan(b=batch, bp=bp, layers=tuple(layers))


def _check_vmem(li: int, lp: LayerPlan) -> None:
    need = vmem_bytes(lp.bb, lp.bf, lp.bo, lp.op, lp.spec)
    if need > _VMEM_BUDGET:
        raise ValueError(
            f"layer {li}: tile {lp.bb}x{lp.bf}x{lp.spec.num_basis} -> "
            f"{lp.bo} of {lp.op} needs ~{need >> 20} MiB of VMEM, over the "
            f"{_VMEM_BUDGET >> 20} MiB budget"
        )


def model_shardable(op: int, model_size: int) -> bool:
    """Can an output dim split over a model axis of this size?

    The axis must divide the padded dim AND each shard must keep a
    multiple-of-8 slab (the smallest valid output tile).  This is THE
    shardability criterion — ``shard_local_plan`` (execution) and
    ``dist.sharding.deployed_kan_pspecs`` (weight placement) both use it,
    so a bundle is never placed sharded where the runtime would execute it
    replicated (or vice versa).
    """
    return (model_size > 1 and op % model_size == 0
            and (op // model_size) % 8 == 0)


def shard_local_plan(plan: PipelinePlan, model_size: int) -> tuple:
    """Per-shard geometry for output-channel ("model") sharding of a stack.

    Each model shard owns WHOLE output columns of every sharded layer (no
    cross-shard reduction in the MAC — the contraction axis stays full), so
    the per-shard plan keeps ``f``/``fp``/``bf`` and divides ``op`` by the
    model-axis size.  A layer is shardable when the axis divides its padded
    output dim AND the per-shard slab still admits a valid output tile
    (``op/model_size`` a multiple of 8); otherwise the layer FALLS BACK to
    replicated columns and the reason is recorded.  ``bo`` is halved until it
    divides the per-shard slab, so tuned tile plans (repro.tune.tiles) stay
    valid per-shard wherever they still divide.

    Returns ``(local_plan, sharded_flags, notes)``: the per-shard plan (its
    per-layer ``o``/``op`` are the LOCAL padded widths for sharded layers —
    logical-column slicing happens globally, after the gather), one bool per
    layer, and human-readable fallback reasons.

    The local plan intentionally violates two :func:`validate_plan`
    invariants — the inter-layer boundary (``fp`` stays full while the
    previous ``op`` is local: an all-gather over "model" restores the full
    width between layers) and the 128-padded-boundary rule (the 128 pad is a
    GLOBAL property; each shard holds a power-of-two fraction of it) — so it
    must not be re-validated.
    """
    n = len(plan.layers)
    if model_size <= 1:
        return plan, (False,) * n, ()
    layers, flags, notes = [], [], []
    for li, lp in enumerate(plan.layers):
        if not model_shardable(lp.op, model_size):
            notes.append(
                f"layer {li}: op={lp.op} not shardable over model={model_size}"
                " (needs a multiple-of-8 per-shard slab); columns replicated"
            )
            layers.append(lp)
            flags.append(False)
            continue
        op_l = lp.op // model_size
        bo_l = lp.bo
        while op_l % bo_l:
            bo_l //= 2
        layers.append(dataclasses.replace(lp, o=op_l, op=op_l, bo=bo_l))
        flags.append(True)
    return (
        dataclasses.replace(plan, layers=tuple(layers)),
        tuple(flags),
        tuple(notes),
    )


def validate_plan(plan: PipelinePlan) -> None:
    """Assert every geometric invariant the fused executor relies on.

    Raises ``ValueError`` on the first violation.  Used by the tile
    autotuner to reject candidate geometries before they are ever compiled,
    and by the tests as the single source of truth for plan validity.
    """
    if not plan.layers:
        raise ValueError("plan has no layers")
    if plan.bp < plan.b:
        raise ValueError(f"padded batch {plan.bp} < logical batch {plan.b}")
    prev_op = None
    for li, lp in enumerate(plan.layers):
        nb = lp.spec.num_basis
        if plan.bp % lp.bb:
            raise ValueError(f"layer {li}: bp={plan.bp} not divisible by bb={lp.bb}")
        if lp.fp % lp.bf:
            raise ValueError(f"layer {li}: fp={lp.fp} not divisible by bf={lp.bf}")
        if lp.op % lp.bo:
            raise ValueError(f"layer {li}: op={lp.op} not divisible by bo={lp.bo}")
        if lp.fp < lp.f or lp.op < lp.o:
            raise ValueError(f"layer {li}: padded dims below logical dims")
        if prev_op is not None and lp.fp != prev_op:
            raise ValueError(
                f"layer {li}: boundary mismatch fp={lp.fp} != prev op={prev_op}"
            )
        if lp.emit_codes and lp.op % 128:
            raise ValueError(
                f"layer {li}: boundary op={lp.op} not 128-padded"
            )
        _check_vmem(li, lp)
        prev_op = lp.op


def pad_layer_weights(wc: jax.Array, wb: jax.Array, lp: LayerPlan) -> dict:
    """Zero-pad one layer's dequantized weights to the plan's geometry.

    wc: (F, G+K, O) -> (Fp * (G+K), Op) flattened banded matrix.
    wb: (F, O)      -> (Fp, Op).
    """
    nb = lp.spec.num_basis
    wc_p = jnp.pad(
        wc.astype(jnp.float32), ((0, lp.fp - lp.f), (0, 0), (0, lp.op - lp.o))
    ).reshape(lp.fp * nb, lp.op)
    wb_p = jnp.pad(
        wb.astype(jnp.float32), ((0, lp.fp - lp.f), (0, lp.op - lp.o))
    )
    return {"wc": wc_p, "wb": wb_p}


# ----------------------------------------------------------------------------
# Sub-8-bit packing (KANtize-style mixed precision)
# ----------------------------------------------------------------------------
#
# A layer whose weight codes fit in 4 bits stores them PACKED: two signed
# int4 row-codes per int8 lane along the contraction axis (row 2r in the low
# nibble, row 2r+1 in the high nibble), plus the per-output-channel f32
# scales — the f32 banded matrix is never materialized at rest, halving the
# layer's weight residency.  The kernel unpacks inside the banded-MAC
# contraction with int32 shift arithmetic and multiplies by the scale row in
# f32 — the exact product the unpacked deployment stores — so packed and
# unpacked executions are bit-identical.  A <=4-bit SH-LUT likewise packs
# two unsigned nibbles per lane along the K+1 axis.


def weight_bits(spec: ASPQuantSpec) -> int:
    """Signed weight-code width a layer deploys at (input width, capped 8)."""
    return min(8, spec.n_bits)


def packs_weights(spec: ASPQuantSpec) -> bool:
    """True when the layer's weight codes int4-pack (two per int8 lane)."""
    return weight_bits(spec) <= 4


def layer_weight_keys(lp: LayerPlan) -> tuple:
    """The deployed weight-dict keys this layer's plan implies.

    The mesh runner and ``dist.sharding`` derive their per-leaf
    PartitionSpecs from these (keys starting with "lut" replicate;
    everything else shards its output-channel dim on "model").
    """
    keys = ["lut"]
    if packs_weights(lp.spec):
        keys += ["wcp", "wscale"]
    else:
        keys.append("wc")
    keys.append("wb")
    return tuple(keys)


def _pack_nibbles(lo: jax.Array, hi: jax.Array) -> jax.Array:
    """Pair two int code arrays into one int8 lane (lo nibble, hi nibble)."""
    lo = lo.astype(jnp.int32)
    hi = hi.astype(jnp.int32)
    return (((hi << 4) & 0xF0) | (lo & 0x0F)).astype(jnp.int8)


def _unpack_lo_nibble(p32: jax.Array) -> jax.Array:
    """Sign-extended low nibble of packed int8 lanes (as int32)."""
    return jax.lax.shift_right_arithmetic(jax.lax.shift_left(p32, 28), 28)


def _unpack_hi_nibble(p32: jax.Array) -> jax.Array:
    """Sign-extended high nibble of packed int8 lanes (as int32)."""
    return jax.lax.shift_right_arithmetic(jax.lax.shift_left(p32, 24), 28)


def pack_layer_weights(c_q: jax.Array, c_scale: jax.Array,
                       wb: jax.Array, lp: LayerPlan) -> dict:
    """int4-pack one layer's spline weight CODES to the plan's geometry.

    c_q: int8 (F, G+K, O) signed codes in [-7, 7] -> "wcp" (Fp*(G+K)//2, Op)
    with consecutive contraction rows paired per lane; c_scale: (O,) ->
    "wscale" (1, Op) f32 (padded channels scale 0, so every padded lane
    still decodes to exactly 0); wb stays dequantized f32 (it is the small
    residual branch), zero-padded as in :func:`pad_layer_weights`.
    """
    nb = lp.spec.num_basis
    q = jnp.pad(
        jnp.asarray(c_q, jnp.int8),
        ((0, lp.fp - lp.f), (0, 0), (0, lp.op - lp.o)),
    ).reshape(lp.fp * nb, lp.op)
    wcp = _pack_nibbles(q[0::2], q[1::2])
    wscale = jnp.pad(
        jnp.asarray(c_scale, jnp.float32), (0, lp.op - lp.o)
    )[None, :]
    wb_p = jnp.pad(
        wb.astype(jnp.float32), ((0, lp.fp - lp.f), (0, lp.op - lp.o))
    )
    return {"wcp": wcp, "wscale": wscale, "wb": wb_p}


def unpacked_wc(lw: dict, lp: LayerPlan) -> jax.Array:
    """The padded f32 banded matrix of a deployed layer, packed or not.

    For packed layers this reproduces the kernel's in-lane decode
    arithmetic exactly (int32 nibble extract -> f32 code x f32 scale), so
    jnp consumers (the ref composition, the acim backend's w_lsb / IR-drop
    paths, bundle compression) see bit-identical weight values.
    """
    if "wc" in lw:
        return lw["wc"].astype(jnp.float32)
    p32 = lw["wcp"].astype(jnp.int32)
    half, op = lw["wcp"].shape
    q = jnp.stack(
        [_unpack_lo_nibble(p32), _unpack_hi_nibble(p32)], axis=1
    ).reshape(2 * half, op)
    return q.astype(jnp.float32) * lw["wscale"].astype(jnp.float32)


# ----------------------------------------------------------------------------
# The fused per-layer kernel (single-layer datapath + fused boundary requant)
# ----------------------------------------------------------------------------


def _dot(a: jax.Array, b: jax.Array) -> jax.Array:
    """(M, K) x (K, N) in full f32: on the TPU a default-precision f32 dot
    is one bf16 pass, which would round the LUT values and the weights."""
    return jax.lax.dot_general(
        a, b, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


def banded_basis(codes: jax.Array, lut_ref, spec: ASPQuantSpec) -> jax.Array:
    """In-kernel ASP datapath up to the MAC operand.

    ``codes``: (bb, bf) int32 tile; ``lut_ref``: the (2**LD, K+1) f32
    SH-LUT, in SMEM.  Returns the (bb, bf*(G+K)) f32 banded basis laid out
    like the deployed weight rows (feature-major, column f*(G+K) + n).

    Every array stays 2-D with the features on the lanes, so this lowers
    to Mosaic without lane<->sublane reshapes:

      * PowerGap bit split -> shift/mask on the VPU;
      * SH-LUT retrieval -> one (bb, bf) plane per band offset i, selected
        per element from the LUT's scalars (2**LD is tiny: <= 32);
      * banded placement -> an exact 0/1 expansion matmul spreads each
        feature's planes over its G+K columns, and the band offset n - g of
        each column picks the plane it takes (zero outside 0..K).
    """
    nb = spec.num_basis
    kk = spec.order + 1
    n_local = spec.codes_per_interval
    bb, bf = codes.shape
    nbf = bf * nb

    g = jax.lax.shift_right_logical(codes, spec.ld)
    local = jax.lax.bitwise_and(codes, n_local - 1)

    lutv = [jnp.zeros((bb, bf), jnp.float32) for _ in range(kk)]
    for l in range(n_local):
        hit = local == l
        for i in range(kk):
            lutv[i] = jnp.where(hit, lut_ref[l, i], lutv[i])

    row = jax.lax.broadcasted_iota(jnp.int32, (bf, nbf), 0) * nb
    col = jax.lax.broadcasted_iota(jnp.int32, (bf, nbf), 1)
    spread = ((col >= row) & (col < row + nb)).astype(jnp.float32)
    first = jax.lax.broadcasted_iota(jnp.int32, (bb, bf), 1) * nb + g
    band = (jax.lax.broadcasted_iota(jnp.int32, (bb, nbf), 1)
            - _dot(first.astype(jnp.float32), spread).astype(jnp.int32))
    basis = jnp.zeros((bb, nbf), jnp.float32)
    for i in range(kk):  # static unroll: K+1 disjoint selects
        basis = jnp.where(band == i, _dot(lutv[i], spread), basis)
    return basis


def _pipeline_layer_kernel(
    *refs,
    lp: LayerPlan,
    has_psum_noise: bool = False,
    packed_w: bool = False,
):
    """One KAN layer tile + (optionally) the fused inter-layer requantizer.

    Ref order: codes, [xraw], lut (SMEM), wc | (wcp, wscale), wb,
    [psum_noise], y_out, [codes_out], [wq scratch (packed_w only)],
    [basis, relu operand, accumulator scratch (basis_reuse > 1 only)].
    Grid: (Bp/bb, Fp/bf, Op/bo); the F axis (middle) is the contraction,
    the output tile j is innermost.  A (bb, bo) output tile accumulates
    across the F axis in ascending order, and the boundary fires on the
    final contraction step of each output tile.

    The datapath up to the MAC is :func:`banded_basis`; the spline MAC is
    then ONE (bb, bf*(G+K)) x (bf*(G+K), bo) dot over the deployed row
    order, the same contraction the reference runs.  The basis depends on
    the (i, k) code tile alone: with ``lp.basis_reuse > 1`` it is built on
    the first output tile (j == 0) into VMEM scratch, with the ReLU
    operand, and every j reads it back; the (bb, op) accumulator lives in
    scratch, and the (i, j) output tile is written on the last k.  With
    one output tile the basis is built inline and the output block is the
    accumulator, with no scratch round-trip.  Either way each (i, j, k)
    MAC is the same dot on the same operands, so the bits do not depend on
    the schedule.

    ``packed_w`` (static, from the deployed dict's keys): the weight operand
    arrives as two int4 codes per int8 lane and is unpacked HERE — int32
    nibble extract, then f32 code x f32 scale, the exact product the
    unpacked deployment stores — into a VMEM tile in contraction order
    (strided row stores), so the packed MAC is bit-identical to the
    unpacked one.

    ``psum_noise`` is the ACIM backend's hook: a precomputed (bb, bo) f32
    perturbation (the macro's partial-sum error, already scaled for the
    number of physical arrays this column spans) folded into the
    accumulator on the first contraction step — so the fused boundary
    requantizer sees the NOISY pre-activation and the error propagates
    through the int-code stream exactly as it would on silicon.
    """
    idx = 0
    codes_ref = refs[idx]; idx += 1
    xraw_ref = None
    if lp.residual_raw:
        xraw_ref = refs[idx]; idx += 1
    lut_ref = refs[idx]; idx += 1
    wc_ref = refs[idx]; idx += 1
    wscale_ref = None
    if packed_w:
        wscale_ref = refs[idx]; idx += 1
    wb_ref = refs[idx]; idx += 1
    noise_ref = None
    if has_psum_noise:
        noise_ref = refs[idx]; idx += 1
    y_ref = refs[idx]; idx += 1
    codes_out_ref = None
    if lp.emit_codes:
        codes_out_ref = refs[idx]; idx += 1
    wq_ref = None
    if packed_w:
        wq_ref = refs[idx]; idx += 1
    reuse = lp.basis_reuse > 1
    if reuse:
        basis_ref, relu_ref, acc_ref = refs[idx:idx + 3]

    spec = lp.spec
    k_step = pl.program_id(1)
    n_k = pl.num_programs(1)
    j = pl.program_id(2)

    def relu_operand():
        if lp.residual_raw:
            resid = xraw_ref[...].astype(jnp.float32)
        else:
            codes = codes_ref[...].astype(jnp.float32)
            resid = spec.lo + codes * spec.code_step
        return jnp.maximum(resid, 0.0)

    if reuse:
        @pl.when(j == 0)
        def _build_basis():
            basis_ref[...] = banded_basis(codes_ref[...], lut_ref, spec)
            relu_ref[...] = relu_operand()

        basis, relu = basis_ref[...], relu_ref[...]
    else:
        basis = banded_basis(codes_ref[...], lut_ref, spec)
        relu = relu_operand()

    # --- spline MAC on the MXU
    if packed_w:
        # row 2r from the low nibble, row 2r+1 from the high nibble
        p32 = wc_ref[...].astype(jnp.int32)
        half = p32.shape[0]
        scale = wscale_ref[...].astype(jnp.float32)
        wq_ref[pl.ds(0, half, stride=2), :] = (
            _unpack_lo_nibble(p32).astype(jnp.float32) * scale)
        wq_ref[pl.ds(1, half, stride=2), :] = (
            _unpack_hi_nibble(p32).astype(jnp.float32) * scale)
        wc_tile = wq_ref[...]
    else:
        wc_tile = wc_ref[...].astype(jnp.float32)
    acc = _dot(basis, wc_tile)

    # --- fused residual branch
    acc = acc + _dot(relu, wb_ref[...].astype(jnp.float32))

    # the (bb, bo) accumulator of output tile j
    acc_j = acc_ref.at[j] if reuse else y_ref

    @pl.when(k_step == 0)
    def _init():
        if noise_ref is not None:
            acc_j[...] = acc + noise_ref[...]
        else:
            acc_j[...] = acc

    @pl.when(k_step > 0)
    def _accum():
        acc_j[...] += acc

    if reuse:
        @pl.when(k_step == n_k - 1)
        def _emit():
            y_ref[...] = acc_j[...]

    if lp.emit_codes:
        nxt = lp.next_spec
        half_span = 0.5 * (nxt.hi - nxt.lo)
        mid = 0.5 * (nxt.hi + nxt.lo)
        scale = 1.0 / nxt.code_step

        @pl.when(k_step == n_k - 1)
        def _requant():
            # the fused boundary: tanh domain rescale -> ASP re-coding.
            # Ops mirror core.kan_layer.kan_network_apply +
            # core.asp_quant.quantize_input exactly (bit-exact contract).
            h = jnp.tanh(y_ref[...]) * half_span + mid
            q = jnp.floor((h - nxt.lo) * scale + 0.5).astype(jnp.int32)
            codes_out_ref[...] = jnp.clip(q, 0, nxt.num_codes - 1)


def _run_layer(
    codes: jax.Array,       # (Bp, Fp) int32
    xraw: jax.Array | None,  # (Bp, Fp) f32, only when lp.residual_raw
    lw: dict,               # deployed layer weights (packed or unpacked)
    lp: LayerPlan,
    bp: int,
    *,
    interpret: bool,
    psum_noise: jax.Array | None = None,  # (Bp, Op) f32 (acim backend)
):
    spec = lp.spec
    nb = spec.num_basis
    # packing is a property of the weights ACTUALLY handed in (dict keys are
    # static pytree structure): the acim backend's IR-drop path substitutes
    # an unpacked f32 dict for a packed layer and the kernel follows.
    packed_w = "wcp" in lw
    assert codes.shape == (bp, lp.fp), (codes.shape, bp, lp.fp)
    if packed_w:
        assert lw["wcp"].shape == (lp.fp * nb // 2, lp.op), (
            lw["wcp"].shape, lp.fp, nb, lp.op)
    else:
        assert lw["wc"].shape == (lp.fp * nb, lp.op), (
            lw["wc"].shape, lp.fp, nb, lp.op)

    n_k = lp.fp // lp.bf
    grid = (bp // lp.bb, n_k, lp.basis_reuse)

    in_specs = [pl.BlockSpec((lp.bb, lp.bf), lambda i, k, j: (i, k))]
    inputs = [codes]
    if lp.residual_raw:
        in_specs.append(pl.BlockSpec((lp.bb, lp.bf), lambda i, k, j: (i, k)))
        inputs.append(xraw)
    # the (2**LD, K+1) SH-LUT is read as scalars
    in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
    inputs.append(lw["lut"].astype(jnp.float32))
    scratch = []
    if packed_w:
        # bf >= 8 keeps bf*nb even, so every contraction block owns whole
        # packed lanes and the (k, j) index map stays contiguous
        in_specs += [
            pl.BlockSpec((lp.bf * nb // 2, lp.bo), lambda i, k, j: (k, j)),
            pl.BlockSpec((1, lp.bo), lambda i, k, j: (0, j)),
        ]
        inputs += [lw["wcp"], lw["wscale"]]
        scratch.append(pltpu.VMEM((lp.bf * nb, lp.bo), jnp.float32))
    else:
        in_specs.append(pl.BlockSpec((lp.bf * nb, lp.bo), lambda i, k, j: (k, j)))
        inputs.append(lw["wc"])
    in_specs.append(pl.BlockSpec((lp.bf, lp.bo), lambda i, k, j: (k, j)))
    inputs.append(lw["wb"])
    if psum_noise is not None:
        assert psum_noise.shape == (bp, lp.op), (psum_noise.shape, bp, lp.op)
        in_specs.append(pl.BlockSpec((lp.bb, lp.bo), lambda i, k, j: (i, j)))
        inputs.append(psum_noise)
    if lp.basis_reuse > 1:
        scratch += [
            pltpu.VMEM((lp.bb, lp.bf * nb), jnp.float32),   # basis
            pltpu.VMEM((lp.bb, lp.bf), jnp.float32),        # relu operand
            pltpu.VMEM((lp.basis_reuse, lp.bb, lp.bo), jnp.float32),  # y acc
        ]

    # An output tile is written on the last contraction step alone; until
    # then its block index stays put, so no partial tile is written back.
    def out_index(i, k, j):
        return i, jnp.where(k == n_k - 1, j, 0)

    out_specs = [pl.BlockSpec((lp.bb, lp.bo), out_index)]
    out_shape = [jax.ShapeDtypeStruct((bp, lp.op), jnp.float32)]
    if lp.emit_codes:
        out_specs.append(pl.BlockSpec((lp.bb, lp.bo), out_index))
        out_shape.append(jax.ShapeDtypeStruct((bp, lp.op), jnp.int32))

    kernel = functools.partial(
        _pipeline_layer_kernel, lp=lp, has_psum_noise=psum_noise is not None,
        packed_w=packed_w,
    )
    outs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
        name="kan_pipeline",
    )(*inputs)
    if lp.emit_codes:
        return outs[0], outs[1]
    return outs[0], None


# Public name for the single-layer step: the mesh-sharded runtime composes
# layers itself (it needs an all-gather between them), so it drives the same
# fused kernel one layer at a time instead of through kan_pipeline_impl.
run_pipeline_layer = _run_layer


# ----------------------------------------------------------------------------
# The multi-layer executor: unjitted body + the single-jit entry point
# ----------------------------------------------------------------------------


def kan_pipeline_impl(
    codes: jax.Array,        # (B, F0) int32 — entry activation codes
    xraw: jax.Array | None,  # (B, F0) f32 raw entry input (residual_raw only)
    layers: tuple,           # per-layer dicts: {"lut", "wc", "wb"} PADDED
    plan: PipelinePlan,
    *,
    interpret: bool = False,
    psum_noises: tuple | None = None,  # per-layer (Bp, Op) f32 or None (acim)
    return_intermediates: bool = False,
):
    """Unjitted pipeline body: plan application split from jit dispatch.

    ``repro.runtime`` backends wrap this under their own per-cache-entry
    jit — the pallas backend calls it as-is, the acim backend pre-transforms
    the weights (IR-drop) and threads per-layer ``psum_noises`` into the MAC
    stage, so non-ideality injection never forks the kernel itself.
    """
    lp0 = plan.layers[0]
    b = codes.shape[0]
    assert b == plan.b, (b, plan.b)
    codes = jnp.pad(codes, ((0, plan.bp - b), (0, lp0.fp - lp0.f)))
    if lp0.residual_raw:
        # padded raw lanes are zero: relu(0) @ zero-padded wb rows == 0
        xraw = jnp.pad(
            xraw.astype(jnp.float32), ((0, plan.bp - b), (0, lp0.fp - lp0.f))
        )

    h_codes, h_raw = codes, xraw
    y = None
    boundary_codes = []
    for li, (lp, lw) in enumerate(zip(plan.layers, layers)):
        noise = psum_noises[li] if psum_noises is not None else None
        y, nxt_codes = _run_layer(
            h_codes,
            h_raw if lp.residual_raw else None,
            lw, lp, plan.bp,
            interpret=interpret,
            psum_noise=noise,
        )
        if nxt_codes is not None:
            boundary_codes.append(nxt_codes[: plan.b, : lp.o])
        h_codes, h_raw = nxt_codes, y
    out = y[: plan.b, : plan.layers[-1].o]
    if return_intermediates:
        return out, tuple(boundary_codes)
    return out


@functools.partial(
    jax.jit, static_argnames=("plan", "interpret", "return_intermediates")
)
def kan_pipeline(
    codes: jax.Array,
    xraw: jax.Array | None,
    layers: tuple,
    plan: PipelinePlan,
    *,
    interpret: bool = False,
    return_intermediates: bool = False,
):
    """Run the whole quantized KAN stack on the Pallas path under one jit.

    Between layers only int32 activation codes move (plus the raw f32
    activation when ``residual_raw``); the final layer returns f32 logits
    sliced back to the logical (B, O_last) shape.

    With ``return_intermediates`` also returns the int32 boundary codes each
    layer handed to the next (sliced to logical shapes) — the conformance
    tests assert these are bit-identical to the layered reference's
    re-quantization.

    This is the standalone entry point; the serving/deploy surfaces go
    through ``repro.runtime``, which wraps :func:`kan_pipeline_impl` in
    per-bucket cached jits instead.
    """
    return kan_pipeline_impl(
        codes, xraw, layers, plan,
        interpret=interpret, return_intermediates=return_intermediates,
    )
