"""Plain reference of a quantized KAN network (the paper's KAN1/KAN2):
per layer, ASP codes of the input, the dense B-spline basis from the
shared LUT, int8 dequantized coefficients, and ReLU of the dequantized
input through the int8 base weights; between layers, tanh rescaled onto
the next layer's domain (arXiv:2409.11418 §3-4).

Boundary codes are integers, so two sound computations can disagree where
a value lies within rounding of a code boundary.  ``outputs`` therefore
also returns each row's output with every such near-boundary code moved to
its other side; a row whose served output matches either is correct.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import kanquant as kq

# how close to a code boundary (in code steps) a value may lie before the
# rounding of a sound computation can move it: the entry codes come from
# one f32 multiply-add, the hidden codes from an f32 sum and a tanh.
ENTRY_EPS = 1e-4
HIDDEN_EPS = 1e-3


def grid(conf: dict, weight_bits: int | None = None) -> kq.Grid:
    return kq.Grid(grid=conf["grid"], order=conf["order"], bits=conf["bits"],
                   lut_bits=conf["lut_bits"],
                   weight_bits=weight_bits or conf["weight_bits"],
                   lo=conf["lo"], hi=conf["hi"])


def make_weights(conf: dict, key) -> list:
    """Float weights of every layer, made on the device from the key:
    coefficients (in, G+K, out) and base weights (in, out), f32."""
    dims, nb = conf["dims"], conf["grid"] + conf["order"]
    out = []
    for k, (a, b) in zip(jax.random.split(key, len(dims) - 1),
                         zip(dims, dims[1:])):
        k1, k2 = jax.random.split(k)
        out.append({"c": jax.random.normal(k1, (a, nb, b)) / a ** 0.5,
                    "w_b": jax.random.normal(k2, (a, b)) / a ** 0.5})
    return out


def make_inputs(conf: dict, key, batches: int, rows: int):
    """Knot-task features: 17 invariants, bell-shaped around 0 and
    truncated to the domain (the surrogate's feature distribution)."""
    inp = conf["inputs"]
    x = jax.random.normal(key, (batches, rows, conf["dims"][0])) * inp["std"]
    return jnp.clip(x, conf["lo"], conf["hi"])


def _code_and_flip(v, eps, g):
    """Codes of v, the codes with every near-boundary one moved to its
    other side, and which were near a boundary."""
    pos = (v - g.lo) * g.codes_per_unit + 0.5
    frac = pos - jnp.floor(pos)
    low, high = frac < eps, frac > 1.0 - eps
    c = kq.codes(v, g)
    alt = jnp.clip(jnp.where(low, c - 1, jnp.where(high, c + 1, c)), 0,
                   g.n_codes - 1)
    return c, alt, low | high


@functools.partial(jax.jit, static_argnames=("g",))
def _forward(x, layers, table, *, g):
    """Nominal output, output with near-boundary codes flipped, and the
    number of near-boundary codes per row."""
    def run(codes, flip):
        y, near = None, 0
        for li, lw in enumerate(layers):
            if li:
                h = jnp.tanh(y) * (0.5 * (g.hi - g.lo)) + 0.5 * (g.hi + g.lo)
                c, alt, n = _code_and_flip(h, HIDDEN_EPS, g)
                codes = alt if flip else c
                near = near + n.sum(-1)
            relu_in = g.lo + codes.astype(jnp.float32) * g.step
            y = kq.layer(codes, relu_in, lw["c"], lw["w_b"], table, g)
        return y, near

    c, c_alt, near0 = _code_and_flip(x, ENTRY_EPS, g)
    nom, near1 = run(c, False)
    alt, _ = run(c_alt, True)
    return nom, alt, near0.sum(-1) + near1


def quantized(conf: dict, weights: list, weight_bits: int | None = None):
    g = grid(conf, weight_bits)
    layers = [{"c": kq.quantize_weights(w["c"], g.weight_bits),
               "w_b": kq.quantize_weights(w["w_b"], g.weight_bits)}
              for w in weights]
    return g, layers


def outputs(conf: dict, weights: list, x, weight_bits: int | None = None):
    """(nominal, flipped, near-boundary count) for rows ``x``."""
    g, layers = quantized(conf, weights, weight_bits)
    return _forward(x, layers, jnp.asarray(kq.lut(g)), g=g)


def row_errors(y, conf: dict, weights: list, x):
    """Per row, the scaled error of ``y`` against the reference: the
    smaller of its errors against the nominal and the flipped outputs,
    over the largest reference output magnitude of the batch.  Returns
    (errors, rows with more than one near-boundary code)."""
    nom, alt, n_near = outputs(conf, weights, x)
    scale = jnp.maximum(jnp.max(jnp.abs(nom)), 1e-30)
    e_nom = jnp.max(jnp.abs(y - nom), axis=-1)
    e_alt = jnp.where(n_near > 0, jnp.max(jnp.abs(y - alt), axis=-1), jnp.inf)
    return jnp.minimum(e_nom, e_alt) / scale, n_near > 1
