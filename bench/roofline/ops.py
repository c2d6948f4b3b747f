"""Operations and bytes that each kernel's work requires.

Counted from the configuration and the call's shapes, never from a
kernel's tiles or HLO, so that a rewrite of the kernel leaves the yardstick
where it was.  An operation is one multiply or one add.

KAN layer (in -> out, G intervals, order K, weight codes of ``wbits``):
  ops   = 2 * rows * in * out * (K + 1)      the non-zero band of the basis
        + 2 * rows * in * out                the ReLU base branch
  bytes = in * (G + K) * out * wbits / 8     spline weight codes
        + in * out * wbits / 8               base weight codes
        + 2 * out * 4                        their f32 scales
        + 2**LD * (K + 1) * 4                the LUT
        + rows * in * act_in + rows * out * act_out    activations, once

Attention (causal, GQA), per query over L keys it may see:
  ops   = 4 * Hq * hd * L                    QK^T and PV
  bytes = Q and O once (2 * Hq * hd * 2), K and V of the L keys once
          (2 * L * Hkv * hd * 2), bf16.
"""

from __future__ import annotations


def kan_layer(rows: int, fin: int, fout: int, *, grid: int, order: int,
              wbits: int, lut_rows: int, act_in: int, act_out: int) -> tuple:
    ops = 2 * rows * fin * fout * (order + 1) + 2 * rows * fin * fout
    nbytes = (fin * (grid + order) * fout * wbits / 8 + fin * fout * wbits / 8
              + 2 * fout * 4 + lut_rows * (order + 1) * 4
              + rows * fin * act_in + rows * fout * act_out)
    return ops, nbytes


def lut_rows(grid: int, bits: int) -> int:
    ld = 0
    while grid * 2 ** (ld + 1) <= 2 ** bits:
        ld += 1
    return 2 ** ld


def kan_ffn_halves(conf: dict, rows: int) -> list:
    """(ops, bytes) of the two KAN-FFN halves of one LM layer; activations
    at the model's 2-byte width."""
    k = conf["kan_ffn"]
    d, h = conf["hidden_size"], k["hidden"]
    kw = dict(grid=k["grid"], order=k["order"], wbits=k["bits"],
              lut_rows=lut_rows(k["grid"], k["bits"]), act_in=2, act_out=2)
    return [kan_layer(rows, d, h, **kw), kan_layer(rows, h, d, **kw)]


def kan_network(conf: dict, rows: int) -> list:
    """(ops, bytes) of each layer of a KAN network; f32 activations."""
    dims = conf["dims"]
    kw = dict(grid=conf["grid"], order=conf["order"], wbits=conf["bits"],
              lut_rows=lut_rows(conf["grid"], conf["bits"]), act_in=4,
              act_out=4)
    return [kan_layer(rows, a, b, **kw) for a, b in zip(dims, dims[1:])]


def _heads(conf: dict) -> tuple:
    hq, hkv = conf["num_attention_heads"], conf["num_key_value_heads"]
    return hq, hkv, conf["head_dim"]


def attention_decode(conf: dict, live: list) -> tuple:
    """One decode step: each live slot's query over its ``L`` keys."""
    hq, hkv, hd = _heads(conf)
    keys = sum(live)
    ops = 4 * hq * hd * keys
    nbytes = 2 * keys * hkv * hd * 2 + len(live) * 2 * hq * hd * 2
    return ops, nbytes


def attention_prefill(conf: dict, start: int, take: int) -> tuple:
    """One prefill chunk: queries start..start+take-1, causal."""
    hq, hkv, hd = _heads(conf)
    keys = take * start + take * (take + 1) // 2
    ops = 4 * hq * hd * keys
    nbytes = 2 * (start + take) * hkv * hd * 2 + take * 2 * hq * hd * 2
    return ops, nbytes


def lm_token_ops(conf: dict) -> float:
    """Model operations per token of one layer outside attention scores:
    the q/k/v/o projections and the KAN-FFN."""
    hq, hkv, hd = _heads(conf)
    d = conf["hidden_size"]
    proj = 2 * d * (hq + 2 * hkv) * hd + 2 * hq * hd * d
    ffn = sum(o for o, _ in kan_ffn_halves(conf, 1))
    return proj + ffn


def lm_head_ops(conf: dict) -> float:
    return 2 * conf["hidden_size"] * conf["vocab_size"]


def lm_step_ops(conf: dict, kind: str, shape) -> float:
    """Model operations of one decode step (``shape`` = live lengths) or
    one prefill chunk (``shape`` = (start, take, final))."""
    n = conf["num_hidden_layers"]
    if kind == "decode":
        live = shape
        return (n * (len(live) * lm_token_ops(conf)
                     + attention_decode(conf, live)[0])
                + len(live) * lm_head_ops(conf))
    start, take, final = shape
    return (n * (take * lm_token_ops(conf)
                 + attention_prefill(conf, start, take)[0])
            + (lm_head_ops(conf) if final else 0))
