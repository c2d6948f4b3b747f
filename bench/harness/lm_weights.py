"""Weights of an LM configuration, made on the device from the seed.

``canonical`` builds them in the published layout, which the plain
reference reads: Qwen2-style names, 40 query heads in groups of 5 per KV
head, RMSNorm weights, and a KAN-FFN of two KANLinear halves.  ``program``
loads that same set into the program's parameter tree.  The program's
layout differs in three ways, each mapped here so that both compute the
same function:

* query heads are padded to ``phys_heads``; the padded slot of each KV
  group (rows of ``wq``/``bq`` and of ``wo``) is zero, so every real head
  keeps its published KV head;
* RMSNorm stores ``scale = weight - 1`` (the program multiplies by
  ``1 + scale``);
* the program multiplies token embeddings by ``bf16(sqrt(d_model))``; the
  table it is given is the published one divided by that factor, which is
  exact because the published table is made as ``table * factor``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def shapes(conf: dict) -> dict:
    d = conf["hidden_size"]
    hq, hkv = conf["num_attention_heads"], conf["num_key_value_heads"]
    hd = d // hq if "head_dim" not in conf else conf["head_dim"]
    k = conf["kan_ffn"]
    return dict(d=d, hq=hq, hkv=hkv, hd=hd, v=conf["vocab_size"],
                n=conf["num_hidden_layers"], h=k["hidden"],
                nb=k["grid"] + k["order"])


def embed_factor(conf: dict) -> float:
    """The program's embedding multiplier, as the bf16 number it uses."""
    import ml_dtypes

    return float(ml_dtypes.bfloat16(math.sqrt(conf["hidden_size"])))


def canonical(conf: dict, key) -> dict:
    """All weights, published layout, bf16 (norm weights f32).  ``embed``
    holds the table divided by ``embed_factor``; the published embedding
    is ``embed * embed_factor`` (see the module docstring)."""
    s = shapes(conf)
    d, hq, hkv, hd, n, h, nb = (s[x] for x in
                                ("d", "hq", "hkv", "hd", "n", "h", "nb"))
    bf = jnp.bfloat16
    ks = iter(jax.random.split(key, 20))

    def normal(shape, std, dtype=bf):
        return (jax.random.normal(next(ks), shape, jnp.float32) * std
                ).astype(dtype)

    def norm_w(shape):
        return 1.0 + normal(shape, 0.05, jnp.float32)

    return {
        "embed": normal((s["v"], d), 0.02),
        "embed_scale": jnp.float32(embed_factor(conf)),
        "final_norm": norm_w((d,)),
        "lm_head": normal((d, s["v"]), 1 / math.sqrt(d)),
        "attn_norm": norm_w((n, d)),
        "ffn_norm": norm_w((n, d)),
        "wq": normal((n, d, hq, hd), 1 / math.sqrt(d)),
        "bq": normal((n, hq, hd), 0.1),
        "wk": normal((n, d, hkv, hd), 1 / math.sqrt(d)),
        "bk": normal((n, hkv, hd), 0.1),
        "wv": normal((n, d, hkv, hd), 1 / math.sqrt(d)),
        "bv": normal((n, hkv, hd), 0.1),
        "wo": normal((n, hq, hd, d), 1 / math.sqrt(d)),
        "c1": normal((n, d, nb, h), 0.1 / math.sqrt(d)),
        "wb1": normal((n, d, h), 1 / math.sqrt(d)),
        "c2": normal((n, h, nb, d), 0.1 / math.sqrt(h)),
        "wb2": normal((n, h, d), 1 / math.sqrt(h)),
    }


def _pad_heads(w, axis: int, hkv: int, phys: int):
    """Spread ``hq`` logical heads (axis ``axis``) over ``phys`` slots in
    ``hkv`` groups, zero slots at the end of each group.  A reshape and a
    pad, not a concatenation: on a mesh that shards the heads the
    partitioner then keeps each chip's groups on that chip."""
    hq = w.shape[axis]
    g, gp = hq // hkv, phys // hkv
    grouped = w.reshape(w.shape[:axis] + (hkv, g) + w.shape[axis + 1:])
    widths = [(0, 0)] * grouped.ndim
    widths[axis + 1] = (0, gp - g)
    padded = jnp.pad(grouped, widths)
    return padded.reshape(w.shape[:axis] + (phys,) + w.shape[axis + 1:])


def program(conf: dict, w: dict, phys_heads: int) -> dict:
    """The program's parameter tree (``repro.models.model.init_params``
    layout, one scanned group of ``num_hidden_layers``) for weights ``w``."""
    s = shapes(conf)
    hkv = s["hkv"]
    if phys_heads % hkv or phys_heads < s["hq"]:
        raise ValueError(f"{phys_heads} physical heads cannot hold "
                         f"{s['hq']} heads in {hkv} groups")
    pad = lambda a, ax: _pad_heads(a, ax, hkv, phys_heads)
    attn = {"wq": pad(w["wq"], 2), "wk": w["wk"], "wv": w["wv"],
            "wo": pad(w["wo"], 1), "bq": pad(w["bq"], 1), "bk": w["bk"],
            "bv": w["bv"]}
    return {
        "embed": w["embed"],
        "final_norm": {"scale": w["final_norm"] - 1.0},
        "lm_head": w["lm_head"],
        "decoder": [{
            "l0_attn": attn,
            "l0_ln1": {"scale": w["attn_norm"] - 1.0},
            "l0_ffn": {"c1": w["c1"], "wb1": w["wb1"], "c2": w["c2"],
                       "wb2": w["wb2"]},
            "l0_ln2": {"scale": w["ffn_norm"] - 1.0},
        }],
    }
