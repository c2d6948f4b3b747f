"""Fused multi-layer Pallas pipeline vs the layered quantized reference.

The contract (kernels/kan_spline/pipeline.py): running the whole stack in
the fused executor — int codes across layer boundaries, requantization fused
into the producing kernel — must reproduce the layered
``kan_layer_apply_quantized`` + tanh-rescale composition:

  * the int32 codes each layer hands to the next are BIT-IDENTICAL to the
    reference's re-quantization (the quantizer output is discrete, so the
    fused boundary must land on exactly the same codes);
  * the final f32 output agrees to float-ulp tolerance (the banded matmul
    is tiled/padded differently, so bit-identity of the f32 accumulation is
    not required — only of the code stream).

Shapes deliberately include ragged B/F/O (nothing a multiple of the block
sizes), multi-layer stacks, both paper configs: KAN1 (17,1,14) G=5 and
KAN2 G=68, and a stack with several feature AND output tiles per layer, as
the LM's KAN-FFN has (each basis tile reused by every output tile).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import assert_bit_exact
from repro.core.asp_quant import quantize_input
from repro.core.kan_layer import (
    KANSpec,
    init_kan_network,
    kan_layer_apply_quantized,
    kan_network_apply,
)
from repro.core.kan_network_deploy import (
    deploy_kan_ffn_stack,
    deploy_kan_network,
    kan_network_apply_ref,
    kan_network_deploy_apply,
    quantize_kan_network,
)
from repro.kernels.kan_spline import pipeline as P
from repro.kernels.kan_spline.pipeline import make_pipeline_plan

# (dims, grid, batch) — ragged on purpose; first two are the paper's KAN1/KAN2
SHAPES = [
    ((17, 1, 14), 5, 33),     # KAN1, odd batch
    ((17, 1, 14), 68, 7),     # KAN2 (G=68), tiny batch
    ((3, 2), 4, 1),           # single layer, degenerate everything
    ((5, 9, 3, 2), 8, 130),   # 3-layer stack, batch > one tile
    ((40, 77, 13), 16, 19),   # wide ragged middle
    ((300, 260, 130), 8, 37),  # 3 feature x 3 (then 2) output tiles
]


def _ref_with_boundary_codes(qparams, x, kspec):
    """Layered reference, also returning each boundary's re-quantized codes."""
    spec = kspec.layer_spec()
    h = x
    codes = []
    n = len(qparams)
    for li in range(n):
        h = kan_layer_apply_quantized(qparams[li], h, spec)
        if li < n - 1:
            h = jnp.tanh(h) * (0.5 * (spec.hi - spec.lo)) \
                + 0.5 * (spec.hi + spec.lo)
            codes.append(quantize_input(h, spec))
    return h, codes


@pytest.mark.parametrize("dims,grid,batch", SHAPES)
def test_fused_pipeline_matches_layered_reference(dims, grid, batch):
    kspec = KANSpec(dims=dims, grid_size=grid)
    key = jax.random.PRNGKey(0)
    params = init_kan_network(key, kspec)
    qparams = quantize_kan_network(params, kspec)
    x = jax.random.uniform(key, (batch, dims[0]), minval=-1.0, maxval=1.0)

    ref, ref_codes = _ref_with_boundary_codes(qparams, x, kspec)
    dep = deploy_kan_network(qparams, kspec, batch=batch)
    out, codes = kan_network_deploy_apply(
        dep, x, interpret=True, return_intermediates=True
    )

    assert out.shape == (batch, dims[-1])
    assert len(codes) == len(ref_codes)
    for li, (c, rc) in enumerate(zip(codes, ref_codes)):
        np.testing.assert_array_equal(
            np.asarray(c), np.asarray(rc),
            err_msg=f"boundary codes after layer {li} not bit-exact",
        )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5
    )


def test_backend_switch_in_kan_network_apply():
    kspec = KANSpec(dims=(17, 1, 14), grid_size=5)
    key = jax.random.PRNGKey(1)
    params = init_kan_network(key, kspec)
    qparams = quantize_kan_network(params, kspec)
    x = jax.random.uniform(key, (12, 17), minval=-1.0, maxval=1.0)

    y_ref = kan_network_apply(None, x, kspec, quantized=True,
                              qparams_list=qparams, backend="ref")
    y_pal = kan_network_apply(None, x, kspec, quantized=True,
                              qparams_list=qparams, backend="pallas",
                              interpret=True)
    np.testing.assert_allclose(
        np.asarray(y_pal), np.asarray(y_ref), atol=1e-5, rtol=1e-5
    )
    with pytest.raises(ValueError):
        kan_network_apply(None, x, kspec, quantized=True,
                          qparams_list=qparams, backend="tpu-magic")


def test_kan_network_apply_ref_equals_layered_composition():
    kspec = KANSpec(dims=(5, 9, 3, 2), grid_size=8)
    key = jax.random.PRNGKey(2)
    qparams = quantize_kan_network(init_kan_network(key, kspec), kspec)
    x = jax.random.uniform(key, (9, 5), minval=-1.0, maxval=1.0)
    a = kan_network_apply_ref(qparams, x, kspec)
    # the eager oracle is BIT-identical to the layered per-layer composition
    spec = kspec.layer_spec()
    h = x
    for li, qp in enumerate(qparams):
        h = kan_layer_apply_quantized(qp, h, spec)
        if li < len(qparams) - 1:
            h = jnp.tanh(h) * (0.5 * (spec.hi - spec.lo)) \
                + 0.5 * (spec.hi + spec.lo)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(h))
    # the runtime-routed "ref" backend (jitted + batch-bucketed) agrees to
    # float-ulp tolerance — XLA may fuse the argument-weights graph with a
    # one-ulp different accumulation than the eager constant-folded oracle
    b = kan_network_apply(None, x, kspec, quantized=True, qparams_list=qparams)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               atol=1e-6, rtol=1e-6)


def _check_ffn_stack(dims, batch):
    """residual_raw contract: ReLU branch reads the RAW pre-squash input
    (models/layers._kan_linear), boundary stays tanh->requantize."""
    from repro.core.asp_quant import ASPQuantSpec, dense_basis_from_codes

    spec = ASPQuantSpec(grid_size=8, order=3, n_bits=8, lo=-1.0, hi=1.0)
    key = jax.random.PRNGKey(3)
    kspec = KANSpec(dims=dims, grid_size=8)
    qparams = quantize_kan_network(init_kan_network(key, kspec), kspec)
    x = jax.random.normal(key, (batch, dims[0])) * 0.7

    # layered reference with the FFN residual convention
    h = x.astype(jnp.float32)
    for qp in qparams:
        codes = quantize_input(jnp.tanh(h), spec)
        basis = dense_basis_from_codes(codes, qp["lut"], spec)
        wc = qp["c_q"].astype(jnp.float32) * qp["c_scale"]
        wb = qp["w_b_q"].astype(jnp.float32) * qp["w_b_scale"]
        f, nb, o = wc.shape
        y = basis.reshape(h.shape[0], f * nb) @ wc.reshape(f * nb, o)
        h = y + jax.nn.relu(h) @ wb
    dep = deploy_kan_ffn_stack(qparams, dims, spec, batch=batch)
    out = kan_network_deploy_apply(dep, x, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(h), atol=1e-5, rtol=1e-5
    )
    return dep


def test_ffn_stack_raw_residual_matches_composition():
    _check_ffn_stack((20, 33, 20), 13)


def test_ffn_stack_multi_tile_matches_composition():
    """An FFN stack with several feature and output tiles per layer: each
    basis tile (and the raw ReLU operand) serves every output tile."""
    dep = _check_ffn_stack((300, 260, 300), 37)
    assert [lp.basis_reuse for lp in dep.plan.layers] == [3, 3]
    assert [lp.fp // lp.bf for lp in dep.plan.layers] == [3, 3]


def _tile_order_reference(codes, xraw, layers, plan, psum_noises=None):
    """The fused kernel's arithmetic in plain jnp, tile by tile.

    Each (bb, bo) output tile sums, over the feature tiles in ascending
    order, the same two dots the kernel runs on the same operands (the
    basis from ``banded_basis``, the weights as the kernel decodes them),
    with the partial-sum noise added on the first; the boundary re-codes
    each finished tile.  It knows nothing of the grid's order or of the
    kernel's scratch, so it pins the bits of any schedule.
    """
    b = codes.shape[0]
    lp0 = plan.layers[0]
    h_codes = jnp.pad(codes, ((0, plan.bp - b), (0, lp0.fp - lp0.f)))
    h_raw = None
    if lp0.residual_raw:
        h_raw = jnp.pad(xraw.astype(jnp.float32),
                        ((0, plan.bp - b), (0, lp0.fp - lp0.f)))
    tile = jax.jit(
        lambda c, r, lut, wc, wb, spec: P._dot(P.banded_basis(c, lut, spec),
                                              wc) + P._dot(r, wb),
        static_argnums=5,
    )
    boundary = []
    for li, (lp, lw) in enumerate(zip(plan.layers, layers)):
        spec, nb = lp.spec, lp.spec.num_basis
        wc, wb = P.unpacked_wc(lw, lp), lw["wb"].astype(jnp.float32)
        if lp.residual_raw:
            relu = jnp.maximum(h_raw, 0.0)
        else:
            relu = jnp.maximum(
                spec.lo + h_codes.astype(jnp.float32) * spec.code_step, 0.0)
        rows = []
        for i in range(0, plan.bp, lp.bb):
            cols = []
            for j in range(0, lp.op, lp.bo):
                acc = None
                for k in range(0, lp.fp, lp.bf):
                    a = tile(h_codes[i:i + lp.bb, k:k + lp.bf],
                             relu[i:i + lp.bb, k:k + lp.bf], lw["lut"],
                             wc[k * nb:(k + lp.bf) * nb, j:j + lp.bo],
                             wb[k:k + lp.bf, j:j + lp.bo], spec)
                    if acc is None:
                        acc = a
                        if psum_noises is not None:
                            acc = acc + psum_noises[li][i:i + lp.bb,
                                                        j:j + lp.bo]
                    else:
                        acc = acc + a
                cols.append(acc)
            rows.append(jnp.concatenate(cols, axis=1))
        y = jnp.concatenate(rows, axis=0)
        if lp.emit_codes:
            nxt = lp.next_spec
            h = jnp.tanh(y) * (0.5 * (nxt.hi - nxt.lo)) \
                + 0.5 * (nxt.hi + nxt.lo)
            q = jnp.floor((h - nxt.lo) * (1.0 / nxt.code_step) + 0.5)
            h_codes = jnp.clip(q.astype(jnp.int32), 0, nxt.num_codes - 1)
            h_raw = y
            boundary.append(h_codes[:b, :lp.o])
    return y[:b, :plan.layers[-1].o], tuple(boundary)


@pytest.mark.parametrize("bits,noise", [(8, False), ((8, 4), False),
                                        (8, True)], ids=["8", "8-4", "8-psum"])
def test_multi_tile_pipeline_bit_exact_vs_tile_order_reference(bits, noise):
    """Many output tiles per basis tile, several feature tiles: the fused
    pipeline (8-bit, int4-packed second layer, and with the acim backend's
    partial-sum noise) reproduces the tile-order reference bitwise."""
    dims, batch = (300, 260, 130), 37
    kspec = KANSpec(dims=dims, grid_size=8, n_bits=bits)
    key = jax.random.PRNGKey(4)
    qparams = quantize_kan_network(init_kan_network(key, kspec), kspec)
    dep = deploy_kan_network(qparams, kspec, batch=batch)
    plan = dep.plan
    assert [lp.basis_reuse for lp in plan.layers] == [3, 2]
    assert [lp.fp // lp.bf for lp in plan.layers] == [3, 3]
    x = jax.random.uniform(key, (batch, dims[0]), minval=-1.0, maxval=1.0)
    codes = quantize_input(x, dep.specs[0])
    noises = None
    if noise:
        noises = tuple(
            0.05 * jax.random.normal(jax.random.PRNGKey(10 + li),
                                     (plan.bp, lp.op), jnp.float32)
            for li, lp in enumerate(plan.layers))
    got = P.kan_pipeline_impl(codes, None, dep.layers, plan, interpret=True,
                              psum_noises=noises, return_intermediates=True)
    want = _tile_order_reference(codes, None, dep.layers, plan, noises)
    assert_bit_exact(want, got)
    if not noise:
        # the pallas executor runs the same program
        assert_bit_exact(want, kan_network_deploy_apply(
            dep, x, interpret=True, backend="pallas",
            return_intermediates=True))


def test_pipeline_plan_geometry_is_consistent():
    """Boundary pads line up (producer op == consumer fp) and blocks divide."""
    kspec = KANSpec(dims=(17, 130, 1, 14), grid_size=68)
    specs = tuple(kspec.layer_spec() for _ in range(3))
    plan = make_pipeline_plan(33, kspec.dims, specs)
    assert plan.bp % plan.layers[0].bb == 0
    for lp in plan.layers:
        assert lp.fp % lp.bf == 0 and lp.op % lp.bo == 0
        assert lp.fp >= lp.f and lp.op >= lp.o
        # basis tile stays inside the VMEM working-set ceiling
        assert lp.bb * lp.bf * lp.spec.num_basis * 4 <= 4 * 1024 * 1024
    for a, b in zip(plan.layers[:-1], plan.layers[1:]):
        assert a.op == b.fp, "codes must flow between layers without reslicing"
        assert a.o == b.f


def _spec8(grid):
    from repro.core.asp_quant import ASPQuantSpec

    return ASPQuantSpec(grid_size=grid, order=3, n_bits=8, lut_bits=8)


@pytest.mark.parametrize("rows", [16, 32, 256])
def test_lm_ffn_plan_reuses_each_basis_tile(rows):
    """qwen2.5-14b's KAN-FFN (5120 -> 1280 -> 5120, G=8): every basis tile
    serves 10 output tiles in the first half and 40 in the second, at
    decode and prefill row counts; the (bb, op) accumulator still lets
    prefill keep its 128-row block."""
    spec = _spec8(8)
    plan = make_pipeline_plan(rows, (5120, 1280, 5120), (spec, spec),
                              residual_raw=True)
    assert [lp.basis_reuse for lp in plan.layers] == [10, 40]
    assert plan.layers[0].bb == min(rows, 128)


def test_knot_plan_keeps_one_output_tile():
    spec = _spec8(68)
    plan = make_pipeline_plan(65536, (17, 1, 14), (spec, spec))
    assert [lp.basis_reuse for lp in plan.layers] == [1, 1]


def test_vmem_bytes_counts_the_full_width_accumulator():
    spec = _spec8(8)
    one = P.vmem_bytes(128, 128, 128, 128, spec)
    wide = P.vmem_bytes(128, 128, 128, 5120, spec)
    # the (128, 5120) f32 accumulator and the basis kept across output tiles
    assert wide - one >= 128 * 5120 * 4 + 128 * 128 * spec.num_basis * 4
    assert wide <= P._VMEM_BUDGET


def test_replan_changes_batch_only():
    kspec = KANSpec(dims=(17, 1, 14), grid_size=5)
    qparams = quantize_kan_network(
        init_kan_network(jax.random.PRNGKey(0), kspec), kspec
    )
    dep = deploy_kan_network(qparams, kspec, batch=8)
    dep2 = dep.replan(640)
    assert dep2.plan.b == 640 and dep2.plan.bp % dep2.plan.layers[0].bb == 0
    assert dep2.layers is dep.layers  # weights/padding are batch-agnostic
    x = jax.random.uniform(jax.random.PRNGKey(1), (640, 17), minval=-1, maxval=1)
    out = kan_network_deploy_apply(dep2, x, interpret=True)
    ref = kan_network_apply_ref(qparams, x, kspec)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)
