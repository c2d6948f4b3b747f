"""Plain reference of a Qwen2-style decoder whose FFN is the paper's
quantized KAN-FFN, from its configuration file.

Per layer: RMSNorm -> attention (GQA, rotary positions on the two halves
of each head, q/k/v bias, causal) -> residual -> RMSNorm -> KAN-FFN ->
residual; then a final RMSNorm and the LM head.  The KAN-FFN is two
KANLinear halves: each quantizes tanh(input) to ASP codes, reads the
dense B-spline basis from the shared LUT, contracts it with the int8
dequantized coefficients, and adds ReLU(raw input) through the int8
dequantized base weights (arXiv:2409.11418 §3; KAN-FFN as in the config).

Everything is f32 at HIGHEST precision, one sequence at a time, one
layer at a time in fixed blocks of rows, so that it fits next to nothing
else: one compiled program serves every layer and every length up to the
cell's ``max_len``.  The weights may lie spread over the cell's chips
(``spread``); each layer's are fetched to the first chip as it runs.  It
reads only the configuration and the weights the benchmark made from the
seed.
Departure from Qwen2.5 itself: the FFN (the paper's technique).  See
``harness/lm_weights.py`` for how the embedding table is stored.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from . import kanquant as kq

HI = jax.lax.Precision.HIGHEST
ROWS = 256
# the per-layer stacks of the published layout (leading axis: the layer),
# and the axis of the vocabulary in the embedding and the head
ATTN_KEYS = ("wq", "bq", "wk", "bk", "wv", "bv", "wo", "attn_norm",
             "ffn_norm")
FFN_KEYS = ("c1", "wb1", "c2", "wb2")
LAYER_KEYS = ATTN_KEYS + FFN_KEYS
VOCAB_AXIS = {"embed": 0, "lm_head": 1}


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


@jax.jit
def _embed(table, scale, tok):
    return table[tok].astype(jnp.float32) * scale


@functools.partial(jax.jit, static_argnames=("eps", "theta", "g1", "g2"))
def _layer(h, lw, ffn_q, lut1, lut2, nblk, *, eps, theta, g1, g2):
    """(len(h), d) f32: one layer over the first ``nblk`` blocks of ROWS
    rows of ``h`` (rows past them come out zero).

    One program for every layer and every sequence up to ``len(h)``: it
    projects the blocks one by one, then lets each block attend to every
    key (masked by position) and adds its KAN-FFN."""
    cap = h.shape[0]
    pos = jnp.arange(cap, dtype=jnp.int32)
    rows = lambda a, b: jax.lax.dynamic_slice_in_dim(a, b * ROWS, ROWS)
    put = lambda a, x, b: jax.lax.dynamic_update_slice_in_dim(
        a, x, b * ROWS, 0)
    lw = {k: v.astype(jnp.float32) for k, v in lw.items()}
    ffn = [kq.dequantize(x) for x in ffn_q]
    hq, hkv, hd = lw["wq"].shape[1], lw["wk"].shape[1], lw["wq"].shape[2]

    def project(b, qkv):
        new = _qkv(rows(h, b), rows(pos, b), lw["attn_norm"], lw["wq"],
                   lw["bq"], lw["wk"], lw["bk"], lw["wv"], lw["bv"],
                   eps=eps, theta=theta)
        return tuple(put(a, x, b) for a, x in zip(qkv, new))

    q, k, v = jax.lax.fori_loop(
        0, nblk, project, tuple(jnp.zeros((cap, n, hd), jnp.float32)
                                for n in (hq, hkv, hkv)))

    def block(b, out):
        hb = rows(h, b) + _attend(rows(q, b), rows(pos, b), k, v, lw["wo"])
        return put(out, hb + _ffn(hb, lw["ffn_norm"], *ffn, lut1, lut2,
                                  eps=eps, g1=g1, g2=g2), b)

    return jax.lax.fori_loop(0, nblk, block, jnp.zeros_like(h))


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(h, norm, w, *, eps):
    return jnp.dot(_rms(h, norm, eps), w.astype(jnp.float32), precision=HI)


def _head_blocks(h, norm, w, eps, fetch, blocks: int = 8):
    """The LM head over vocabulary blocks, each fetched to ``h``'s chip,
    so no f32 copy of the whole head is ever made."""
    v = w.shape[1]
    n = blocks if v % blocks == 0 else 1
    step = v // n
    return jnp.concatenate([_head(h, norm,
                                  fetch(w[:, i * step:(i + 1) * step]),
                                  eps=eps) for i in range(n)], axis=1)


@functools.partial(jax.jit, static_argnames=("b1", "b2"))
def _quantize_ffn(c1, wb1, c2, wb2, *, b1, b2):
    return (kq.quantize_weights(c1, b1), kq.quantize_weights(wb1, b1),
            kq.quantize_weights(c2, b2), kq.quantize_weights(wb2, b2))


def spread(shapes: dict, mesh) -> dict:
    """Shardings that spread the published-layout weights over every chip
    of ``mesh`` at rest: each per-layer stack on its layer axis, the
    embedding and the head on their vocabulary axis.  A leaf whose axis
    the chips do not divide is kept whole on each."""
    axes = tuple(mesh.axis_names)

    def one(name, a):
        dim = 0 if name in LAYER_KEYS else VOCAB_AXIS.get(name)
        parts = [None] * len(a.shape)
        if dim is not None and a.shape[dim] % mesh.size == 0:
            parts[dim] = axes
        return NamedSharding(mesh, P(*parts))

    return {k: one(k, a) for k, a in shapes.items()}


def _layer_of(a, i: int):
    """Layer ``i`` of a stacked leaf, on the chip that holds it."""
    for shard in a.addressable_shards:
        start, stop, _ = shard.index[0].indices(a.shape[0])
        if start <= i < stop:
            return shard.data[i - start]
    raise IndexError(f"no chip holds layer {i}")


class Reference:
    """Logits of whole sequences under the configuration, read at chosen
    positions.  ``bits`` overrides the KAN-FFN width (a lower-precision
    reference).

    The weights may lie spread over several chips (see ``spread``); the
    reference computes on the first chip, fetching one layer's weights,
    and one block of the head, at a time."""

    def __init__(self, conf: dict, weights: dict, max_len: int,
                 bits: int | None = None):
        self.conf, self.w = conf, weights
        self.dev = jax.devices()[0]
        self.eps = float(conf["rms_norm_eps"])
        self.theta = float(conf["rope_theta"])
        self.g1, self.g2 = grids(conf, bits)
        self.lut1 = jnp.asarray(kq.lut(self.g1))
        self.lut2 = jnp.asarray(kq.lut(self.g2))
        self.cap = -(-max_len // ROWS) * ROWS
        self.embed = self.fetch(weights["embed"])
        self.ffn_q = [
            _quantize_ffn(*(_layer_of(weights[k], i) for k in FFN_KEYS),
                          b1=self.g1.weight_bits, b2=self.g2.weight_bits)
            for i in range(conf["num_hidden_layers"])]

    def fetch(self, tree):
        """``tree`` on the chip the reference computes on."""
        return jax.device_put(tree, self.dev)

    def logits(self, tokens: list, positions: list) -> jax.Array:
        """(len(positions), vocab) f32: the next-token logits after
        ``tokens[:p+1]`` for each p in ``positions``."""
        t = len(tokens)
        if t > self.cap:
            raise ValueError(f"{t} tokens exceed the reference's {self.cap}")
        tok = np.zeros(self.cap, np.int32)
        tok[:t] = tokens
        w, nblk = self.w, -(-t // ROWS)
        h = _embed(self.embed, self.fetch(w["embed_scale"]),
                   self.fetch(jnp.asarray(tok)))
        for i, ffn_q in enumerate(self.ffn_q):
            lw = self.fetch({k: _layer_of(w[k], i) for k in ATTN_KEYS})
            h = _layer(h, lw, self.fetch(ffn_q), self.lut1, self.lut2, nblk,
                       eps=self.eps, theta=self.theta, g1=self.g1,
                       g2=self.g2)
        n = len(positions)
        idx = np.zeros(-(-n // 64) * 64, np.int32)
        idx[:n] = positions
        rows = h[jnp.asarray(idx)]
        return _head_blocks(rows, self.fetch(w["final_norm"]), w["lm_head"],
                            self.eps, self.fetch)[:n]
