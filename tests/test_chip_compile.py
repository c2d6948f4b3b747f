"""The hot kernels compile for a TPU v5e at real widths, without the chip.

Interpret mode (every other kernel test) checks neither VMEM limits nor
tiling alignment; the chip's compiler, given a described v5e topology,
does.  Each case lowers and compiles one kernel call for one v5e chip and
checks that the Mosaic kernel is in the compiled program.  Nothing runs.

The topology is described inside a module fixture (never at import): only
one process at a time may hold the TPU library, and a skip decided at
import would give pytest-xdist workers different test lists.
"""

import os

import jax
import jax.numpy as jnp
import pytest

from repro.core.asp_quant import ASPQuantSpec
from repro.kernels.attention import flash_attention
from repro.kernels.kan_spline import pipeline as P


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return jax.sharding.SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _layer_shapes(lp, sharding):
    """Shapes of one deployed layer (packed or not) at the plan's geometry."""
    nb = lp.spec.num_basis
    lw = {"lut": _sds((lp.spec.codes_per_interval, lp.spec.order + 1),
                      jnp.float32, sharding)}
    if P.packs_weights(lp.spec):
        lw["wcp"] = _sds((lp.fp * nb // 2, lp.op), jnp.int8, sharding)
        lw["wscale"] = _sds((1, lp.op), jnp.float32, sharding)
    else:
        lw["wc"] = _sds((lp.fp * nb, lp.op), jnp.float32, sharding)
    lw["wb"] = _sds((lp.fp, lp.op), jnp.float32, sharding)
    return lw


def _spec(grid, bits):
    return ASPQuantSpec(grid_size=grid, order=3, n_bits=bits, lut_bits=bits)


def _assert_mosaic(compiled):
    assert "tpu_custom_call" in compiled.as_text()


# (dims, per-layer (G, bits), rows, residual_raw): the qwen2.5-14b KAN-FFN
# (d_model 5120, hidden 1280, G=8) at a decode and a prefill batch, uniform
# 8-bit and int4-packed second half, and the paper's KAN2 edge net (G=68).
KAN_CASES = {
    "ffn-8bit-decode": ((5120, 1280, 5120), ((8, 8), (8, 8)), 8, True),
    "ffn-8bit-prefill": ((5120, 1280, 5120), ((8, 8), (8, 8)), 256, True),
    "ffn-8-4bit-decode": ((5120, 1280, 5120), ((8, 8), (8, 4)), 8, True),
    "ffn-8-4bit-prefill": ((5120, 1280, 5120), ((8, 8), (8, 4)), 256, True),
    "kan2-g68": ((17, 1, 14), ((68, 8), (68, 8)), 1024, False),
}


@pytest.mark.parametrize("case", sorted(KAN_CASES))
def test_kan_pipeline_compiles_for_v5e(one_chip, case):
    dims, gb, rows, residual_raw = KAN_CASES[case]
    specs = tuple(_spec(g, b) for g, b in gb)
    plan = P.make_pipeline_plan(rows, dims, specs, residual_raw=residual_raw)
    P.validate_plan(plan)
    layers = tuple(_layer_shapes(lp, one_chip) for lp in plan.layers)
    codes = _sds((rows, dims[0]), jnp.int32, one_chip)
    xraw = _sds((rows, dims[0]), jnp.float32, one_chip) if residual_raw \
        else None

    def run(c, x, lw):
        return P.kan_pipeline_impl(c, x, lw, plan, interpret=False,
                                   return_intermediates=True)

    _assert_mosaic(jax.jit(run).lower(codes, xraw, layers).compile())


@pytest.mark.parametrize("li", [0, 1])
def test_kan_mesh_local_layer_compiles_for_v5e(one_chip, li):
    """One model shard of a data=2,model=2 mesh: the prefill KAN-FFN plan
    split over model=2 gives local widths 640 and 2560 (5 and 20 output
    tiles per basis tile), which the mesh runner drives one layer at a
    time."""
    specs = (_spec(8, 8), _spec(8, 8))
    plan = P.make_pipeline_plan(256, (5120, 1280, 5120), specs,
                                residual_raw=True)
    local, sharded, _ = P.shard_local_plan(plan, 2)
    assert sharded == (True, True)
    lp = local.layers[li]
    assert (lp.op, lp.basis_reuse) == ((640, 5), (2560, 20))[li]
    lw = _layer_shapes(lp, one_chip)
    codes = _sds((local.bp, lp.fp), jnp.int32, one_chip)
    xraw = _sds((local.bp, lp.fp), jnp.float32, one_chip)

    def run(c, x, w):
        return P.run_pipeline_layer(c, x, w, lp, local.bp, interpret=False)

    _assert_mosaic(jax.jit(run).lower(codes, xraw, lw).compile())


# qwen2.5-14b attention: 48 padded q heads over 8 KV heads (G=6) x 128,
# a batch of 8 decoding one token over a 2048-token cache, and a batch of 2
# prefilling 512 tokens.
FLASH_CASES = {"decode": (8, 1, 2048), "prefill": (2, 512, 512)}


@pytest.mark.parametrize("case", sorted(FLASH_CASES))
def test_flash_attention_compiles_for_v5e(one_chip, case):
    b, s, t = FLASH_CASES[case]
    q = _sds((b, s, 48, 128), jnp.bfloat16, one_chip)
    kv = _sds((b, t, 8, 128), jnp.bfloat16, one_chip)
    qpos = _sds((b, s), jnp.int32, one_chip)
    kpos = _sds((b, t), jnp.int32, one_chip)

    def run(q, k, v, qp, kp):
        return flash_attention(q, k, v, kind="causal", qpos=qp, kpos=kp,
                               interpret=False)

    _assert_mosaic(jax.jit(run).lower(q, kv, kv, qpos, kpos).compile())
