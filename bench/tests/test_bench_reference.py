"""The plain references against the served paths at smoke size: the
program at its stated precision passes the comparison, and its own
lower-precision path (LM) or the reference at a lower precision in the
program's place (KAN2) fails it."""

import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src"), str(BENCH / "tests")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

import bench_tiny as B  # noqa: E402
from harness import common as C  # noqa: E402
from harness import kan, lm  # noqa: E402
from reference import kan as refkan  # noqa: E402
from reference import kanquant as kq  # noqa: E402


def test_reference_lut_and_codes_match_the_stated_quantization():
    """The reference's own SH-LUT and codes equal the paper's numbers: 32
    codes per interval at G=8 in 8 bits, and the cubic bump's values."""
    g = kq.Grid(grid=8, order=3, bits=8, lut_bits=8, weight_bits=8)
    assert (g.ld, g.n_codes, g.n_basis) == (5, 256, 11)
    assert kq.cardinal_bspline([2.0], 3)[0] == pytest.approx(2 / 3)
    table = kq.lut(g)
    assert table.shape == (32, 4)
    assert np.allclose(table.sum(axis=1), 1.0, atol=4 / 255)
    c = kq.codes(jnp.asarray([-1.0, 0.0, 0.999]), g)
    assert c.tolist() == [0, 128, 255]
    k2 = kq.Grid(grid=68, order=3, bits=8, lut_bits=8, weight_bits=8)
    assert (k2.ld, k2.n_codes) == (1, 136)


@pytest.mark.parametrize("grid,bits", [(8, 8), (68, 8), (8, 4)])
def test_dense_basis_places_the_lut_row_at_the_codes_interval(grid, bits):
    """Every code's basis row holds its offset's K+1 LUT values at bands
    interval .. interval+K and zeros elsewhere (checked code by code)."""
    g = kq.Grid(grid=grid, order=3, bits=bits, lut_bits=bits,
                weight_bits=bits)
    table = kq.lut(g)
    codes = np.arange(g.n_codes)
    got = np.asarray(kq.dense_basis(jnp.asarray(codes), table, g))
    want = np.zeros((g.n_codes, g.n_basis), np.float32)
    for c in codes:
        i, u = divmod(int(c), g.per_interval)
        want[c, i:i + g.order + 1] = table[u]
    assert np.array_equal(got, want)


def test_lm_served_path_matches_and_its_4bit_path_fails():
    conf, traffic = B.tiny_lm_conf(), B.tiny_traffic("chat")
    limit = conf["correct"]["max_logit_gap"]
    cfg = lm.program_config(conf)
    engine = lm.build_engine(lm.make_program_params(conf, cfg, B.SEED), cfg,
                             traffic)
    lm.warm_up(engine, cfg, traffic, B.SEED)
    win = lm.serve(engine, cfg, traffic, B.SEED, 1.5)
    del engine
    checks, detail = lm.check_served(conf, win, B.SEED, traffic["max_len"],
                                     limit)
    assert checks.ok, checks.items
    assert detail["sampled_tokens"] >= 20
    worst, cd = lm.control_gap(conf, cfg, traffic, win, B.SEED)
    assert worst > limit, (worst, cd)


def test_kan2_served_path_matches_and_4bit_weights_fail():
    conf, traffic = (C.read_data(BENCH / "configs" / "kan2-knot.json"),
                     B.tiny_traffic("batch"))
    limit = conf["correct"]["max_row_err"]
    dep, xs = kan.setup(conf, traffic, B.SEED)
    weights = jax.jit(lambda k: refkan.make_weights(conf, k))(
        C.jax_key(B.SEED, 1))
    y = kan.call(dep, xs[0])
    err, ambiguous = refkan.row_errors(y, conf, weights, xs[0])
    assert float(jnp.max(jnp.where(ambiguous, 0.0, err))) <= limit
    y4, _, _ = refkan.outputs(conf, weights, xs[0], weight_bits=4)
    err4, _ = refkan.row_errors(y4, conf, weights, xs[0])
    assert float(jnp.max(err4)) > 10 * limit
