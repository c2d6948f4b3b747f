"""Plain reference of a Qwen2-style decoder whose FFN is the paper's
quantized KAN-FFN, from its configuration file.

Per layer: RMSNorm -> attention (GQA, rotary positions on the two halves
of each head, q/k/v bias, causal) -> residual -> RMSNorm -> KAN-FFN ->
residual; then a final RMSNorm and the LM head.  The KAN-FFN is two
KANLinear halves: each quantizes tanh(input) to ASP codes, reads the
dense B-spline basis from the shared LUT, contracts it with the int8
dequantized coefficients, and adds ReLU(raw input) through the int8
dequantized base weights (arXiv:2409.11418 §3; KAN-FFN as in the config).

Everything is f32 at HIGHEST precision, one sequence at a time, in
fixed blocks of rows so that it fits next to nothing else, as one
compiled program for every length up to the cell's ``max_len``; it reads
only the configuration and the weights the benchmark made from the seed.
Departure from Qwen2.5 itself: the FFN (the paper's technique).  See
``harness/lm_weights.py`` for how the embedding table is stored.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from . import kanquant as kq

HI = jax.lax.Precision.HIGHEST
ROWS = 256


def grids(conf: dict, bits: int | None = None) -> tuple:
    k = conf["kan_ffn"]
    b = k["bits"] if bits is None else bits
    g = kq.Grid(grid=k["grid"], order=k["order"], bits=b,
                lut_bits=min(k["lut_bits"], b), weight_bits=min(8, b),
                lo=k["lo"], hi=k["hi"])
    return (g, g)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None, None].astype(jnp.float32) * freq
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _qkv(h, pos, norm, wq, bq, wk, bk, wv, bv, *, eps, theta):
    x = _rms(h, norm, eps)
    q = jnp.einsum("rd,dhk->rhk", x, wq, precision=HI) + bq
    k = jnp.einsum("rd,dhk->rhk", x, wk, precision=HI) + bk
    v = jnp.einsum("rd,dhk->rhk", x, wv, precision=HI) + bv
    return _rope(q, pos, theta), _rope(k, pos, theta), v


def _attend(q, qpos, k, v, wo):
    """One block of queries against every key (causal by position)."""
    hq, hkv = q.shape[1], k.shape[1]
    qg = q.reshape(q.shape[0], hkv, hq // hkv, q.shape[2])
    s = jnp.einsum("rgjd,tgd->rgjt", qg, k, precision=HI) / math.sqrt(
        q.shape[2])
    mask = jnp.arange(k.shape[0])[None, :] <= qpos[:, None]
    s = jnp.where(mask[:, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("rgjt,tgd->rgjd", p, v, precision=HI)
    o = o.reshape(q.shape[0], hq * q.shape[2])
    return jnp.dot(o, wo.reshape(-1, wo.shape[-1]), precision=HI)


def _ffn(h, norm, c1, wb1, c2, wb2, lut1, lut2, *, eps, g1, g2):
    x = _rms(h, norm, eps)
    y1 = kq.layer_f32(kq.codes(jnp.tanh(x), g1), x, c1, wb1, lut1, g1)
    return kq.layer_f32(kq.codes(jnp.tanh(y1), g2), y1, c2, wb2, lut2, g2)


@functools.partial(jax.jit, static_argnames=("eps", "theta", "g1", "g2"))
def _hidden(w, ffn_q, lut1, lut2, tok, nblk, *, eps, theta, g1, g2):
    """(len(tok), d) f32: the final layer's hidden state of each of the
    first ``nblk`` blocks of ROWS tokens (rows past them are zero).

    One program for every sequence up to ``len(tok)``: each layer projects
    the blocks one by one, then lets each block attend to every key
    (masked by position) and adds its KAN-FFN."""
    f32 = lambda a: a.astype(jnp.float32)
    cap = tok.shape[0]
    pos = jnp.arange(cap, dtype=jnp.int32)
    rows = lambda a, b: jax.lax.dynamic_slice_in_dim(a, b * ROWS, ROWS)
    put = lambda a, x, b: jax.lax.dynamic_update_slice_in_dim(
        a, x, b * ROWS, 0)
    h = f32(w["embed"][tok]) * w["embed_scale"]
    hq, hkv, hd = w["wq"].shape[2], w["wk"].shape[2], w["wq"].shape[3]
    for i in range(w["wq"].shape[0]):
        lw = {k: f32(w[k][i]) for k in ("wq", "bq", "wk", "bk", "wv", "bv",
                                        "wo", "attn_norm", "ffn_norm")}
        ffn = [kq.dequantize(x) for x in ffn_q[i]]

        def project(b, qkv, h=h, lw=lw):
            new = _qkv(rows(h, b), rows(pos, b), lw["attn_norm"], lw["wq"],
                       lw["bq"], lw["wk"], lw["bk"], lw["wv"], lw["bv"],
                       eps=eps, theta=theta)
            return tuple(put(a, x, b) for a, x in zip(qkv, new))

        q, k, v = jax.lax.fori_loop(
            0, nblk, project, tuple(jnp.zeros((cap, n, hd), jnp.float32)
                                    for n in (hq, hkv, hkv)))

        def block(b, out, h=h, lw=lw, ffn=ffn, q=q, k=k, v=v):
            hb = rows(h, b) + _attend(rows(q, b), rows(pos, b), k, v,
                                      lw["wo"])
            return put(out, hb + _ffn(hb, lw["ffn_norm"], *ffn, lut1, lut2,
                                      eps=eps, g1=g1, g2=g2), b)

        h = jax.lax.fori_loop(0, nblk, block, jnp.zeros_like(h))
    return h


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(h, norm, w, *, eps):
    return jnp.dot(_rms(h, norm, eps), w.astype(jnp.float32), precision=HI)


def _head_blocks(h, norm, w, eps, blocks: int = 8):
    """The LM head over vocabulary blocks, so no f32 copy of the whole
    head is ever made."""
    v = w.shape[1]
    n = blocks if v % blocks == 0 else 1
    step = v // n
    return jnp.concatenate([_head(h, norm, w[:, i * step:(i + 1) * step],
                                  eps=eps) for i in range(n)], axis=1)


@functools.partial(jax.jit, static_argnames=("b1", "b2"))
def _quantize_ffn(c1, wb1, c2, wb2, *, b1, b2):
    return (kq.quantize_weights(c1, b1), kq.quantize_weights(wb1, b1),
            kq.quantize_weights(c2, b2), kq.quantize_weights(wb2, b2))


class Reference:
    """Logits of whole sequences under the configuration, read at chosen
    positions.  ``bits`` overrides the KAN-FFN width (a lower-precision
    reference)."""

    def __init__(self, conf: dict, weights: dict, max_len: int,
                 bits: int | None = None):
        self.conf, self.w = conf, weights
        self.eps = float(conf["rms_norm_eps"])
        self.theta = float(conf["rope_theta"])
        self.g1, self.g2 = grids(conf, bits)
        self.lut1 = jnp.asarray(kq.lut(self.g1))
        self.lut2 = jnp.asarray(kq.lut(self.g2))
        self.cap = -(-max_len // ROWS) * ROWS
        self.ffn_q = [
            _quantize_ffn(weights["c1"][i], weights["wb1"][i],
                          weights["c2"][i], weights["wb2"][i],
                          b1=self.g1.weight_bits, b2=self.g2.weight_bits)
            for i in range(conf["num_hidden_layers"])]

    def logits(self, tokens: list, positions: list) -> jax.Array:
        """(len(positions), vocab) f32: the next-token logits after
        ``tokens[:p+1]`` for each p in ``positions``."""
        t = len(tokens)
        if t > self.cap:
            raise ValueError(f"{t} tokens exceed the reference's {self.cap}")
        tok = np.zeros(self.cap, np.int32)
        tok[:t] = tokens
        h = _hidden(self.w, self.ffn_q, self.lut1, self.lut2,
                    jnp.asarray(tok), -(-t // ROWS), eps=self.eps,
                    theta=self.theta, g1=self.g1, g2=self.g2)
        n = len(positions)
        idx = np.zeros(-(-n // 64) * 64, np.int32)
        idx[:n] = positions
        rows = h[jnp.asarray(idx)]
        return _head_blocks(rows, self.w["final_norm"], self.w["lm_head"],
                            self.eps)[:n]
