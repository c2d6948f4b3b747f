"""Transformer substrate layers: norms, RoPE, GQA attention (causal /
sliding-window / bidirectional / cross), SwiGLU & GeLU & KAN FFN, top-k MoE,
RG-LRU, Mamba-2 SSD.

Conventions
-----------
* Params are plain nested dicts of jnp arrays; init fns take (key, cfg) and
  are shape-deterministic (usable under jax.eval_shape for the dry-run).
* Activations: (B, S, D) in cfg dtype; reductions/softmax in float32.
* Every layer has a full-sequence path (train/prefill) and a single-step
  decode path with an explicit state/cache pytree.
* The KAN-FFN is the paper's technique as a first-class LM layer: each of
  the two projections is a KANLinear (B-spline edges); its quantized
  deployment path reuses core.asp_quant / kernels.kan_spline.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..core.asp_quant import ASPQuantSpec
from ..core.bspline import bspline_basis, bspline_basis_fast

Params = Any

# ----------------------------------------------------------------------------
# misc
# ----------------------------------------------------------------------------


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# Activation-sharding constraint applied at residual-stream boundaries.
# The launcher installs a NamedSharding for (B, S, D) activations; without it
# XLA may resolve the FSDP-weight (contracting-dim over "data") vs
# batch-over-"data" conflict by ALL-GATHERING THE BATCH — a measured 16x
# compute/memory blowup (EXPERIMENTS.md §Perf, qwen train iteration 3).
_ACT_SPEC = None


def set_activation_spec(spec):
    """spec: NamedSharding/PartitionSpec for (batch, seq, d_model), or None."""
    global _ACT_SPEC
    _ACT_SPEC = spec


def constrain_act(x):
    if _ACT_SPEC is None or x.ndim < 2:
        return x
    return jax.lax.with_sharding_constraint(x, _ACT_SPEC)


def init_rmsnorm(d: int):
    return {"scale": jnp.zeros((d,), jnp.float32)}


def rmsnorm(p, x, eps: float):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps) * (1.0 + p["scale"])
    return y.astype(x.dtype)


def softcap(x, cap: float):
    if cap and cap > 0.0:
        return jnp.tanh(x / cap) * cap
    return x


# ----------------------------------------------------------------------------
# RoPE
# ----------------------------------------------------------------------------


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, S, H, D) with D even; positions: (B, S) int32."""
    d = x.shape[-1]
    half = d // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freq  # (B, S, half)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


def sinusoidal_positions(seq: int, d: int) -> jax.Array:
    pos = jnp.arange(seq, dtype=jnp.float32)[:, None]
    i = jnp.arange(d // 2, dtype=jnp.float32)[None, :]
    ang = pos / (10000.0 ** (2 * i / d))
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ----------------------------------------------------------------------------
# Attention (GQA; causal / local / bidirectional / cross; KV cache)
# ----------------------------------------------------------------------------


def init_attention(key, cfg: ModelConfig, cross: bool = False):
    """Physical head counts may be PADDED to a TP multiple (cfg.phys_heads).

    Padded wo rows start at zero so the logical function is exactly the
    published architecture at init; padding is a deployment layout choice
    (see configs/base.py head_pad_multiple)."""
    d, hd = cfg.d_model, cfg.head_dim
    hq, hkv = cfg.phys_heads, cfg.phys_kv_heads
    ks = jax.random.split(key, 4)
    dt = _dtype(cfg)
    sc = 1.0 / math.sqrt(d)
    wo = jax.random.normal(ks[3], (hq, hd, d), dt) * sc
    if hq != cfg.num_heads:  # zero the padded heads' output rows
        mask = (jnp.arange(hq) < cfg.num_heads).astype(dt)[:, None, None]
        wo = wo * mask
    p = {
        "wq": jax.random.normal(ks[0], (d, hq, hd), dt) * sc,
        "wk": jax.random.normal(ks[1], (d, hkv, hd), dt) * sc,
        "wv": jax.random.normal(ks[2], (d, hkv, hd), dt) * sc,
        "wo": wo,
    }
    if cfg.qkv_bias and not cross:
        p["bq"] = jnp.zeros((hq, hd), dt)
        p["bk"] = jnp.zeros((hkv, hd), dt)
        p["bv"] = jnp.zeros((hkv, hd), dt)
    return p


def _qkv(p, x, cfg, use_rope, positions):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    if use_rope:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    # Block XLA's dot reassociation (x·Wq)·Kᵀ -> x·(Wq·Kᵀ): when the qkv
    # projections are replicated (head count not divisible by the TP axis)
    # the rewrite costs 2·S·D·T flops instead of 2·S·(D+T)·hd — an ~18x
    # compute blowup measured on qwen/phi3 train cells (EXPERIMENTS.md §Perf).
    q, k, v = _grad_safe_barrier((q, k, v))
    return q, k, v


# jax.lax.optimization_barrier has no differentiation rule; the barrier is
# purely a scheduling hint, so its VJP is the identity (with the same barrier
# applied to the cotangents to keep the backward dots un-reassociated too).
@jax.custom_vjp
def _grad_safe_barrier(xs):
    return jax.lax.optimization_barrier(xs)


def _grad_safe_barrier_fwd(xs):
    return _grad_safe_barrier(xs), None


def _grad_safe_barrier_bwd(_, g):
    return (jax.lax.optimization_barrier(g),)


_grad_safe_barrier.defvjp(_grad_safe_barrier_fwd, _grad_safe_barrier_bwd)


ATTN_CHUNK = 1024  # query-chunk size for the memory-bounded attention path


def _masked_softmax(logits, mask):
    """Softmax over the last axis with an explicit validity mask.

    Matches ``jax.nn.softmax`` bit-for-bit whenever a row has at least one
    valid key (the max valid logit contributes exp(0) = 1, so the
    denominator is >= 1 and masked lanes underflow to exactly 0 either
    way); fully-masked rows — e.g. qpos = -1 padding from the remainder
    chunk — produce EXACT zeros instead of a uniform average over -1e30
    garbage."""
    if mask is None:
        return jax.nn.softmax(logits, axis=-1)
    logits = jnp.where(mask, logits, -1e30)
    m = jnp.max(logits, axis=-1, keepdims=True)
    e = jnp.where(mask, jnp.exp(logits - m), 0.0)
    return e / jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)


def _sdpa_chunk(qc, qpos, k, v, kpos, cfg: ModelConfig, kind: str):
    """One query chunk.  qc: (B,C,Hkv,G,D); qpos: (C,); k/v: (B,T,Hkv,D);
    kpos: (T,).  Masks are built on the fly from positions — no (S,T)
    tensor is ever materialized (the 32k/500k cells depend on this)."""
    d = qc.shape[-1]
    logits = jnp.einsum(
        "bchgd,bthd->bhgct", qc.astype(jnp.float32), k.astype(jnp.float32)
    ) / math.sqrt(d)
    logits = softcap(logits, cfg.attn_logit_softcap)
    m = None
    if kind in ("global", "local"):
        m = kpos[None, :] <= qpos[:, None]                    # causal (C,T)
        if kind == "local" and cfg.window_size > 0:
            m &= kpos[None, :] > qpos[:, None] - cfg.window_size
        m = m[None, None, None]
    probs = _masked_softmax(logits, m)
    return jnp.einsum("bhgct,bthd->bchgd", probs.astype(v.dtype), v)


# layers.py attention kinds -> kernels.attention mask kinds
_FLASH_KIND = {"global": "causal", "local": "local",
               "bidir": "full", "cross": "full"}


def _sdpa_flash(q, k, v, cfg: ModelConfig, kind: str, qpos, kpos):
    """The fused Pallas flash-attention path (backend "flash").

    XLA cannot partition a Mosaic kernel, so under a bound mesh
    (``runtime.use_mesh`` — the serving engine's scope) the kernel runs in
    a ``shard_map``: batch on "data" and KV heads (with their query groups)
    on "model" wherever the axis divides them, replicated otherwise."""
    from ..kernels.attention import flash_attention
    from ..runtime.meshexec import mesh_axis_sizes, resolve_mesh

    def call(q, k, v, qpos, kpos):
        return flash_attention(
            q, k, v, kind=_FLASH_KIND[kind], qpos=qpos, kpos=kpos,
            window=cfg.window_size, softcap=cfg.attn_logit_softcap,
        )

    mesh = resolve_mesh()
    if mesh is None:
        return call(q, k, v, qpos, kpos).astype(v.dtype)
    b, s = q.shape[:2]
    t, hkv = k.shape[1], k.shape[2]
    qpos = jnp.arange(s) + (t - s) if qpos is None else qpos
    kpos = jnp.arange(t) if kpos is None else kpos
    qpos = jnp.broadcast_to(jnp.atleast_2d(jnp.asarray(qpos, jnp.int32)),
                            (b, s))
    kpos = jnp.broadcast_to(jnp.atleast_2d(jnp.asarray(kpos, jnp.int32)),
                            (b, t))
    dsize, msize = mesh_axis_sizes(mesh)
    bax = "data" if dsize > 1 and b % dsize == 0 else None
    hax = "model" if msize > 1 and hkv % msize == 0 else None
    heads = jax.sharding.PartitionSpec(bax, None, hax, None)
    pos = jax.sharding.PartitionSpec(bax, None)
    out = jax.shard_map(call, mesh=mesh,
                        in_specs=(heads, heads, heads, pos, pos),
                        out_specs=heads, check_vma=False)(q, k, v, qpos, kpos)
    return out.astype(v.dtype)


def _sdpa_ref(q, k, v, cfg: ModelConfig, kind: str, qpos=None, kpos=None):
    """The chunked XLA composition (backend "ref" — the parity oracle).

    Long sequences are processed in ATTN_CHUNK query chunks under lax.scan;
    a non-multiple remainder is PADDED to a full chunk (padded rows carry
    qpos = -1, are fully masked, and provably contribute zeros) instead of
    abandoning the memory-bounded path for the whole sequence."""
    b, s, hq, d = q.shape
    t = k.shape[1]
    hkv = k.shape[2]
    g = hq // hkv
    if qpos is None:
        qpos = jnp.arange(s) + (t - s)
    if kpos is None:
        kpos = jnp.arange(t)

    if s <= ATTN_CHUNK:
        out = _sdpa_chunk(q.reshape(b, s, hkv, g, d), qpos, k, v, kpos,
                          cfg, kind)
        return out.reshape(b, s, hq, d)

    pad = (-s) % ATTN_CHUNK
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        qpos = jnp.pad(qpos, (0, pad), constant_values=-1)
    sp = s + pad
    nc = sp // ATTN_CHUNK
    qcs = q.reshape(b, nc, ATTN_CHUNK, hkv, g, d).swapaxes(0, 1)
    qps = qpos.reshape(nc, ATTN_CHUNK)

    def body(_, inp):
        qc, qp = inp
        return None, _sdpa_chunk(qc, qp, k, v, kpos, cfg, kind)

    _, outs = jax.lax.scan(body, None, (qcs, qps))
    out = outs.swapaxes(0, 1).reshape(b, sp, hq, d)
    return out[:, :s]


def _sdpa(q, k, v, cfg: ModelConfig, kind: str, qpos=None, kpos=None,
          backend=None):
    """q: (B,S,Hq,D), k/v: (B,T,Hkv,D).  kind: global|local|bidir|cross.

    Dispatches to the resolved runtime attention backend: "ref" (chunked
    XLA composition) or "flash" (fused Pallas online-softmax kernel) — see
    :mod:`repro.runtime.attention`.  Resolution happens at trace time, so
    jitted callers that want to switch backends must key their compiled
    steps on the resolved name (``ServeEngine`` does)."""
    from ..runtime.attention import resolve_attn_backend

    if resolve_attn_backend(backend) == "flash":
        return _sdpa_flash(q, k, v, cfg, kind, qpos, kpos)
    return _sdpa_ref(q, k, v, cfg, kind, qpos, kpos)


def attention(p, x, cfg: ModelConfig, kind: str, positions=None, enc_out=None):
    """Full-sequence attention. kind: global|local|bidir|cross."""
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    if kind == "cross":
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
        k = jnp.einsum("bsd,dhk->bshk", enc_out, p["wk"])
        v = jnp.einsum("bsd,dhk->bshk", enc_out, p["wv"])
    else:
        use_rope = kind in ("global", "local")
        q, k, v = _qkv(p, x, cfg, use_rope, positions)
    out = _sdpa(q, k, v, cfg, kind)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"])


def _sdpa_batch_masked(q, k, v, mask, cfg: ModelConfig):
    """Decode-path attention with a per-batch key mask.

    mask: (B, T) — one key-validity row shared by every query (the classic
    single-token decode step) — or (B, S, T) — one row per query, as the
    speculative-decode verify pass needs (each of the S verified positions
    has its own causal frontier)."""
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    qr = q.reshape(b, s, hkv, g, d).astype(jnp.float32)
    logits = jnp.einsum("bshgd,bthd->bhgst", qr, k.astype(jnp.float32))
    logits = logits / math.sqrt(d)
    logits = softcap(logits, cfg.attn_logit_softcap)
    if mask is None:
        m = None
    elif mask.ndim == 3:
        m = mask[:, None, None, :, :]          # (B,1,1,S,T)
    else:
        m = mask[:, None, None, None, :]       # (B,1,1,1,T)
    probs = _masked_softmax(logits, m)
    out = jnp.einsum("bhgst,bthd->bshgd", probs.astype(v.dtype), v)
    return out.reshape(b, s, hq, d)


def _sdpa_decode(q, k, v, cfg: ModelConfig, kind: str, qpos, kpos,
                 backend=None):
    """Decode-step attention built from per-batch positions.

    q: (B,S,Hq,D) — S=1 for the classic single-token step, S=k+1 for the
    speculative-decode verify pass; k/v: (B,T,Hkv,D); qpos: (B,S) absolute
    query positions; kpos: (B,T) absolute position held by each cache slot,
    -1 for unwritten slots.  ``qpos``/``kpos`` None means bidirectional
    over the whole cache (cross-attention decode).  Dispatches like
    :func:`_sdpa`: the "ref" backend materializes the mask ((B,T) at S=1 —
    unchanged from the single-token step — or (B,S,T) per query row),
    "flash" hands the positions to the fused kernel, which already builds
    its causal mask per (B,S) query row.  Both mask non-causal AND
    unwritten (kpos < 0) slots; for the rolling-window cache causal +
    validity is the complete window predicate, because the buffer only
    ever holds the last ``window`` positions."""
    from ..runtime.attention import resolve_attn_backend

    if resolve_attn_backend(backend) == "flash":
        # "global" maps to the kernel's causal mask; the local rolling cache
        # needs no window predicate (see above), so it is causal too
        fkind = "bidir" if kind in ("bidir", "cross") else "global"
        return _sdpa_flash(q, k, v, cfg, fkind, qpos, kpos)
    mask = None
    if kind not in ("bidir", "cross"):
        if qpos.shape[1] == 1:
            mask = (kpos >= 0) & (kpos <= qpos)                    # (B,T)
        else:
            mask = ((kpos[:, None, :] >= 0)
                    & (kpos[:, None, :] <= qpos[:, :, None]))      # (B,S,T)
    return _sdpa_batch_masked(q, k, v, mask, cfg)


def attention_decode(p, x, cache, pos, cfg: ModelConfig, kind: str, enc_out=None,
                     block_table=None):
    """Decode-step attention.  x: (B, S, D) — S=1 for the classic
    one-token step, S=k+1 for the speculative-decode verify pass, whose
    tokens occupy consecutive positions pos..pos+S-1; cache: {"k","v"}:
    (B, T, Hkv, D); pos: (B,) int32 current position.  Returns
    (out, new_cache).

    With ``block_table`` ((B, nblk) int32) the cache is the PAGED pool —
    {"k","v"}: (NB, block_size, Hkv, D), no batch dim — and the table maps
    each request's logical block j to pool block id ``block_table[b, j]``.
    The step scatters the new K/V into the owning pool blocks and gathers
    the table into a (B, nblk*block_size, Hkv, D) view, which is exactly
    the contiguous cache's shape and, at every VALID position, its values —
    stale lanes (unwritten tail blocks point at the scratch block, and
    rolled-back speculative rows are rewritten before any query may attend
    them) are masked by the ``kpos <= qpos`` predicate and contribute
    exact zeros (see ``_masked_softmax``), so paged decode is
    bit-identical to contiguous decode.  Positions at or beyond the
    table's coverage are routed to pool id NB and dropped (``mode="drop"``,
    the same idiom as :func:`paged_prefill_update`); the caller caps
    emission before those rows could ever be consumed.  Only "global"
    attention pages (the engine gates on pure-global decoders)."""
    b = x.shape[0]
    s = x.shape[1]
    if kind == "cross":
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
        k, v = cache["k"], cache["v"]  # precomputed from enc_out
        out = _sdpa_decode(q, k, v, cfg, "cross", None, None)
        return jnp.einsum("bshk,hkd->bsd", out, p["wo"]), cache

    positions = pos[:, None] + jnp.arange(s)[None, :]  # (B, S)
    q, k, v = _qkv(p, x, cfg, True, positions)
    if block_table is not None:
        bs = cache["k"].shape[1]
        nblk = block_table.shape[1]
        nb = cache["k"].shape[0]
        bidx = jnp.arange(b)
        pb = jnp.clip(positions // bs, 0, nblk - 1)
        blk = jnp.where(positions < nblk * bs,
                        block_table[bidx[:, None], pb], nb)  # (B,S) pool ids
        off = positions % bs
        # retired slots all map to the scratch block; duplicate (blk, off)
        # targets race there, which is harmless — scratch lanes are never
        # unmasked for any live request
        ck = cache["k"].at[blk, off].set(k.astype(cache["k"].dtype),
                                         mode="drop")
        cv = cache["v"].at[blk, off].set(v.astype(cache["v"].dtype),
                                         mode="drop")
        gk = ck[block_table].reshape(b, nblk * bs, *ck.shape[2:])
        gv = cv[block_table].reshape(b, nblk * bs, *cv.shape[2:])
        kpos = jnp.broadcast_to(jnp.arange(nblk * bs)[None, :],
                                (b, nblk * bs))
        out = _sdpa_decode(q, gk, gv, cfg, kind, positions, kpos)
        return (jnp.einsum("bshk,hkd->bsd", out, p["wo"]),
                {"k": ck, "v": cv})
    t = cache["k"].shape[1]
    if kind == "local" and 0 < cfg.window_size <= t:
        # rolling window cache: slot = pos % window (t == window).
        # Single-token only — the serve engine never routes multi-token
        # verify through local layers (paged mode gates on pure-global).
        slot = (pos % t)[:, None]
        ck = _scatter_time(cache["k"], k, slot)
        cv = _scatter_time(cache["v"], v, slot)
        kpos = _window_positions(pos, t, t)  # absolute pos held by each slot
    else:
        ck = _scatter_time(cache["k"], k, positions)
        cv = _scatter_time(cache["v"], v, positions)
        kpos = jnp.broadcast_to(jnp.arange(ck.shape[1])[None, :],
                                (b, ck.shape[1]))
    out = _sdpa_decode(q, ck, cv, cfg, kind, positions, kpos)
    new_cache = {"k": ck, "v": cv}
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"]), new_cache


def _scatter_time(cache, new, slot):
    """cache: (B,T,H,D); new: (B,1,H,D); slot: (B,1) -> write per batch."""
    b = cache.shape[0]
    bidx = jnp.arange(b)[:, None]
    return cache.at[bidx, slot].set(new.astype(cache.dtype))


def _window_positions(pos, window, t):
    """Absolute position stored in each rolling-cache slot (B, T)."""
    slots = jnp.arange(t)[None, :]
    cur_slot = (pos % window)[:, None]
    # slot s holds position: largest p' <= pos with p' % window == s
    delta = (cur_slot - slots) % window
    return pos[:, None] - delta


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int, kind: str):
    t = min(max_len, cfg.window_size) if kind == "local" else max_len
    shape = (batch, t, cfg.phys_kv_heads, cfg.head_dim)
    return {
        "k": jnp.zeros(shape, _dtype(cfg)),
        "v": jnp.zeros(shape, _dtype(cfg)),
    }


def init_paged_kv_cache(cfg: ModelConfig, num_blocks: int, block_size: int):
    """One layer's paged KV pool: (NB, block_size, Hkv, D), no batch dim —
    requests own pool blocks through their block tables (serve.kvpool)."""
    shape = (num_blocks, block_size, cfg.phys_kv_heads, cfg.head_dim)
    return {
        "k": jnp.zeros(shape, _dtype(cfg)),
        "v": jnp.zeros(shape, _dtype(cfg)),
    }


def paged_prefill_update(kv, k, v, block_table, start, real_end):
    """Scatter a B=1 prefill chunk's K/V into the paged pool and gather the
    request's full contiguous view back.

    kv: {"k","v"}: (NB, bs, Hkv, D); k/v: (1, C, Hkv, D) chunk projections;
    block_table: (nblk,) int32 pool ids for the request's logical blocks
    (unallocated tail entries = scratch); start / real_end: scalar absolute
    positions — chunk row j holds position ``start + j`` and rows at
    positions >= real_end are bucket padding, whose writes are DROPPED
    (their block index is forced out of range with ``mode="drop"``) so pad
    garbage can never land in a block another request shares.

    Returns (new_kv, gathered_k, gathered_v) with gathered shapes
    (1, nblk*bs, Hkv, D)."""
    nb, bs = kv["k"].shape[:2]
    nblk = block_table.shape[0]
    c = k.shape[1]
    p = start + jnp.arange(c)
    pb = jnp.clip(p // bs, 0, nblk - 1)
    blk = jnp.where(p < real_end, block_table[pb], nb)  # nb => dropped
    off = p % bs
    ck = kv["k"].at[blk, off].set(k[0].astype(kv["k"].dtype), mode="drop")
    cv = kv["v"].at[blk, off].set(v[0].astype(kv["v"].dtype), mode="drop")
    gk = ck[block_table].reshape(1, nblk * bs, *ck.shape[2:])
    gv = cv[block_table].reshape(1, nblk * bs, *cv.shape[2:])
    return {"k": ck, "v": cv}, gk, gv


# ----------------------------------------------------------------------------
# FFN: SwiGLU / GeLU / KAN
# ----------------------------------------------------------------------------


def init_ffn(key, cfg: ModelConfig):
    d, f = cfg.d_model, cfg.d_ff
    dt = _dtype(cfg)
    ks = jax.random.split(key, 3)
    sc_in, sc_out = 1.0 / math.sqrt(d), 1.0 / math.sqrt(f)
    if cfg.ffn_kind == "swiglu":
        return {
            "wi": jax.random.normal(ks[0], (d, f), dt) * sc_in,
            "wg": jax.random.normal(ks[1], (d, f), dt) * sc_in,
            "wo": jax.random.normal(ks[2], (f, d), dt) * sc_out,
        }
    if cfg.ffn_kind == "gelu":
        return {
            "wi": jax.random.normal(ks[0], (d, f), dt) * sc_in,
            "wo": jax.random.normal(ks[2], (f, d), dt) * sc_out,
        }
    if cfg.ffn_kind == "kan":
        nb = cfg.kan_grid + cfg.kan_order
        h = kan_ffn_hidden(cfg)
        # KANLinear pair: d -> h -> d; c:(in, nb, out), w_b:(in, out)
        return {
            "c1": jax.random.normal(ks[0], (d, nb, h), dt) * (0.1 / math.sqrt(d)),
            "wb1": jax.random.normal(ks[1], (d, h), dt) * sc_in,
            "c2": jax.random.normal(ks[2], (h, nb, d), dt) * (0.1 / math.sqrt(h)),
            "wb2": jax.random.normal(ks[0], (h, d), dt) * (1.0 / math.sqrt(h)),
        }
    if cfg.ffn_kind == "none":
        return {}
    raise ValueError(cfg.ffn_kind)


def kan_ffn_specs(cfg: ModelConfig) -> tuple:
    """Per-half ASPQuantSpecs of a KAN-FFN block (the d -> h -> d pair).

    ``cfg.kan_layer_bits`` (when set: one width per half) overrides the
    uniform ``cfg.kan_n_bits`` — KANtize-style mixed precision, PowerGap-
    validated per half; each half's lut_bits is clipped to its input width.
    """
    from ..core.asp_quant import resolve_layer_bits

    bits = resolve_layer_bits(
        cfg.kan_layer_bits if cfg.kan_layer_bits else cfg.kan_n_bits,
        2, cfg.kan_grid,
    )
    return tuple(
        ASPQuantSpec(
            grid_size=cfg.kan_grid, order=cfg.kan_order, n_bits=b,
            lut_bits=min(cfg.kan_n_bits, b), lo=-1.0, hi=1.0,
        )
        for b in bits
    )


def kan_ffn_spec(cfg: ModelConfig) -> ASPQuantSpec:
    """First-half spec (uniform deployments: THE spec; kept for callers
    that only need the bit-independent grid geometry)."""
    return kan_ffn_specs(cfg)[0]


def kan_ffn_hidden(cfg: ModelConfig) -> int:
    """KANLinear hidden width of a KAN-FFN block — the ONE place the rule
    lives; init_ffn and every geometry lookup (e.g. the serving engine's
    tuned-plan-source check) must agree on it."""
    nb = cfg.kan_grid + cfg.kan_order
    return cfg.kan_d_hidden or max(1, cfg.d_ff // nb)


def _bump_basis_and_grad(z, lo, hi, grid_size, order):
    """Cardinal-bump basis AND d(basis)/dz at z, both (..., G+K) f32."""
    from ..core.bspline import _cardinal_bump_coeffs

    h = (hi - lo) / grid_size
    tau = jnp.clip((z - lo) / h, 0.0, grid_size * (1 - 1e-7))
    interior = ((z - lo) / h > 0.0) & ((z - lo) / h < grid_size)
    g = jnp.floor(tau)
    u = tau - g
    g = g.astype(jnp.int32)
    coeffs = _cardinal_bump_coeffs(order)
    nb = grid_size + order
    iota = jnp.arange(nb, dtype=jnp.int32)
    basis = jnp.zeros(z.shape + (nb,), jnp.float32)
    dbasis = jnp.zeros(z.shape + (nb,), jnp.float32)
    for d in range(order + 1):
        seg = order - d
        val = jnp.zeros_like(u)
        dval = jnp.zeros_like(u)
        for p in reversed(range(order + 1)):  # simultaneous Horner: p, p'
            dval = dval * u + val
            val = val * u + float(coeffs[seg, p])
        hit = iota == (g + d)[..., None]
        basis = basis + jnp.where(hit, val[..., None], 0.0)
        dbasis = dbasis + jnp.where(hit, dval[..., None], 0.0)
    dbasis = dbasis * (interior[..., None] / h)  # clip grad + chain rule
    return basis, dbasis


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _spline_mm(x, c, lo, hi_g_k, _tag):
    hi, g, k = hi_g_k
    basis = bspline_basis_fast(jnp.tanh(x.astype(jnp.float32)), lo, hi, g, k)
    return jnp.einsum("bsfn,fno->bso", basis.astype(c.dtype), c)


def _spline_mm_fwd(x, c, lo, hi_g_k, _tag):
    return _spline_mm(x, c, lo, hi_g_k, _tag), (x, c)


def _spline_mm_bwd(lo, hi_g_k, _tag, res, dy):
    """Backward that contracts the basis dim LOCALLY before any cross-shard
    reduction: the default autodiff all-reduces the (B,S,F,G+K) basis
    cotangent across the TP axis (measured 1.17 TB/dev on the KAN-FFN train
    cell); contracting to (B,S,F) first shrinks that 11x (§Perf cell 3)."""
    hi, g, k = hi_g_k
    x, c = res
    z = jnp.tanh(x.astype(jnp.float32))
    basis, dbasis = _bump_basis_and_grad(z, lo, hi, g, k)
    dc = jnp.einsum("bsfn,bso->fno", basis.astype(dy.dtype), dy)
    # NOTE: XLA still all-reduces this partial dot's (B,S,F,G+K) output
    # across the TP axis before our local n-contraction (eager AR placement;
    # bf16-casting the dot was also tried and changed nothing) — a shard_map
    # rewrite with explicit deferred psum is the remaining lever (§Perf).
    t = jnp.einsum("bso,fno->bsfn", dy, c).astype(jnp.float32)
    dz = jnp.sum(t * dbasis, axis=-1)             # local contraction over n
    dx = dz * (1.0 - z * z)                       # tanh chain
    return dx.astype(x.dtype), dc.astype(c.dtype)


_spline_mm.defvjp(_spline_mm_fwd, _spline_mm_bwd)


def _kan_linear(c, wb, x, cfg: ModelConfig):
    """Float KANLinear over (B, S, in): banded basis matmul + ReLU branch.

    Uses the ASP cardinal-bump basis builder (bspline_basis_fast, 4x less
    HBM traffic than Cox-de Boor) and a TP-aware custom VJP (§Perf cell 3)."""
    spec = kan_ffn_spec(cfg)
    y = _spline_mm(x, c, spec.lo, (spec.hi, spec.grid_size, spec.order),
                   "kanffn")
    return y + jax.nn.relu(x) @ wb


def ffn(p, x, cfg: ModelConfig):
    if cfg.ffn_kind == "swiglu":
        return (jax.nn.silu(x @ p["wg"]) * (x @ p["wi"])) @ p["wo"]
    if cfg.ffn_kind == "gelu":
        return jax.nn.gelu(x @ p["wi"]) @ p["wo"]
    if cfg.ffn_kind == "kan":
        if "l1" in p:
            # ASP-quantized deployed block (see core.kan_ffn_deploy.
            # quantize_kan_ffn_params_tree): both halves run through the
            # fused kan_spline Pallas pipeline, int codes across the boundary.
            from ..core.kan_ffn_deploy import kan_ffn_apply_quantized

            return kan_ffn_apply_quantized(p, x, cfg)
        h = _kan_linear(p["c1"], p["wb1"], x, cfg)
        return _kan_linear(p["c2"], p["wb2"], h, cfg)
    if cfg.ffn_kind == "none":
        return jnp.zeros_like(x)
    raise ValueError(cfg.ffn_kind)


# ----------------------------------------------------------------------------
# MoE (top-k, sort-based dispatch, capacity drop)
# ----------------------------------------------------------------------------


def init_moe(key, cfg: ModelConfig):
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    dt = _dtype(cfg)
    ks = jax.random.split(key, 4)
    sc_in, sc_out = 1.0 / math.sqrt(d), 1.0 / math.sqrt(f)
    return {
        "router": jax.random.normal(ks[0], (d, e), jnp.float32) * sc_in,
        "wi": jax.random.normal(ks[1], (e, d, f), dt) * sc_in,
        "wg": jax.random.normal(ks[2], (e, d, f), dt) * sc_in,
        "wo": jax.random.normal(ks[3], (e, f, d), dt) * sc_out,
    }


def moe(p, x, cfg: ModelConfig):
    """Top-k MoE with sort-based dispatch into (E, C, D) expert batches."""
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.num_experts_per_tok
    t = b * s
    xt = x.reshape(t, d)
    logits = (xt.astype(jnp.float32)) @ p["router"]
    topv, topi = jax.lax.top_k(logits, k)            # (T, k)
    gates = jax.nn.softmax(topv, axis=-1)            # normalize over chosen

    cap = int(max(1, math.ceil(t * k * cfg.moe_capacity_factor / e)))
    flat_e = topi.reshape(t * k)
    flat_g = gates.reshape(t * k)
    tok_id = jnp.repeat(jnp.arange(t), k)

    # Position-within-expert, two lowerings (cfg.moe_dispatch, §Perf):
    #  * "cumsum": one-hot prefix sums — avoids the GLOBAL token sort that
    #    XLA lowers to an all-gather of every token (8.6 GB f32 all-reduces
    #    per layer measured on olmoe's 64-expert dispatch);
    #  * "sort": argsort-based ranking — measured better for few-expert
    #    models (mixtral, E=8) where the sort is cheap and cumsum's
    #    (t·k, E) prefix chain serializes.
    if cfg.moe_dispatch == "sort":
        order = jnp.argsort(flat_e, stable=True)
        sorted_e = flat_e[order]
        first = jnp.searchsorted(sorted_e, sorted_e, side="left")
        pos_sorted = jnp.arange(t * k) - first
        inv = jnp.zeros_like(order).at[order].set(jnp.arange(t * k))
        pos = pos_sorted[inv]
    else:
        onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)  # (t*k, E)
        pos = jnp.cumsum(onehot, axis=0) - 1                 # rank per expert
        pos = jnp.take_along_axis(pos, flat_e[:, None], axis=1)[:, 0]
    keep = pos < cap
    dest = jnp.where(keep, flat_e * cap + pos, e * cap)      # drop slot at end

    xe = jnp.zeros((e * cap + 1, d), x.dtype).at[dest].set(xt[tok_id])
    xe = xe[: e * cap].reshape(e, cap, d)

    h = jnp.einsum("ecd,edf->ecf", xe, p["wi"])
    g = jnp.einsum("ecd,edf->ecf", xe, p["wg"])
    ye = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * h, p["wo"])

    ye_flat = jnp.concatenate([ye.reshape(e * cap, d),
                               jnp.zeros((1, d), ye.dtype)], axis=0)
    contrib = ye_flat[dest] * flat_g[:, None].astype(ye.dtype)
    out = jnp.zeros((t, d), x.dtype).at[tok_id].add(contrib)
    return out.reshape(b, s, d)


# ----------------------------------------------------------------------------
# RG-LRU recurrent block (RecurrentGemma / Griffin)
# ----------------------------------------------------------------------------


def init_rglru(key, cfg: ModelConfig):
    d = cfg.d_model
    w = cfg.rnn_width or d
    dt = _dtype(cfg)
    ks = jax.random.split(key, 6)
    sc = 1.0 / math.sqrt(d)
    return {
        "w_in": jax.random.normal(ks[0], (d, w), dt) * sc,
        "w_gate_in": jax.random.normal(ks[1], (d, w), dt) * sc,
        "conv": jax.random.normal(ks[2], (4, w), dt) * 0.3,
        "w_rg": jax.random.normal(ks[3], (w, w), dt) * (1.0 / math.sqrt(w)),
        "w_ig": jax.random.normal(ks[4], (w, w), dt) * (1.0 / math.sqrt(w)),
        "lam": jnp.full((w,), 2.0, jnp.float32),  # softplus-param of decay
        "w_out": jax.random.normal(ks[5], (w, d), dt) * (1.0 / math.sqrt(w)),
    }


def _causal_conv1d(x, w, state=None):
    """Depthwise causal conv. x: (B,S,W), w: (K,W).  state: (B,K-1,W)."""
    k = w.shape[0]
    if state is None:
        pad = jnp.zeros((x.shape[0], k - 1, x.shape[2]), x.dtype)
    else:
        pad = state
    xp = jnp.concatenate([pad, x], axis=1)
    out = sum(xp[:, i : i + x.shape[1]] * w[i][None, None] for i in range(k))
    new_state = xp[:, -(k - 1) :] if k > 1 else None
    return out, new_state


def _rglru_scan(a, bx):
    """Associative linear recurrence h_t = a_t * h_{t-1} + b_t over axis 1."""

    def combine(c1, c2):
        a1, b1 = c1
        a2, b2 = c2
        return a1 * a2, a2 * b1 + b2

    aa, bb = jax.lax.associative_scan(combine, (a, bx), axis=1)
    return bb


def rglru(p, x, cfg: ModelConfig, state=None, pos=None):
    """x: (B,S,D). state: {"conv": (B,3,W), "h": (B,W)} for decode (S==1).
    Returns (out, new_state)."""
    decode = state is not None
    u = x @ p["w_in"]
    gate_in = jax.nn.gelu(x @ p["w_gate_in"])
    u, conv_state = _causal_conv1d(
        u, p["conv"], state["conv"] if decode else None
    )
    r = jax.nn.sigmoid(u @ p["w_rg"])
    i = jax.nn.sigmoid(u @ p["w_ig"])
    c = 8.0
    log_a = -c * jax.nn.softplus(p["lam"])[None, None, :] * r.astype(jnp.float32)
    a = jnp.exp(log_a)
    gated = (i * u).astype(jnp.float32) * jnp.sqrt(
        jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-6)
    )
    if decode:
        h = a[:, 0] * state["h"] + gated[:, 0]
        out_seq = h[:, None, :]
        new_state = {"conv": conv_state, "h": h}
    else:
        out_seq = _rglru_scan(a, gated)
        new_state = None
    y = (out_seq.astype(x.dtype) * gate_in) @ p["w_out"]
    return y, new_state


def init_rglru_state(cfg: ModelConfig, batch: int):
    w = cfg.rnn_width or cfg.d_model
    return {
        "conv": jnp.zeros((batch, 3, w), _dtype(cfg)),
        "h": jnp.zeros((batch, w), jnp.float32),
    }


def rglru_prefill(p, x, cfg: ModelConfig):
    """Full-sequence RG-LRU that also returns the final recurrent state."""
    u = x @ p["w_in"]
    gate_in = jax.nn.gelu(x @ p["w_gate_in"])
    u_conv, _ = _causal_conv1d(u, p["conv"])
    r = jax.nn.sigmoid(u_conv @ p["w_rg"])
    i = jax.nn.sigmoid(u_conv @ p["w_ig"])
    c = 8.0
    log_a = -c * jax.nn.softplus(p["lam"])[None, None, :] * r.astype(jnp.float32)
    a = jnp.exp(log_a)
    gated = (i * u_conv).astype(jnp.float32) * jnp.sqrt(
        jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-6)
    )
    h_seq = _rglru_scan(a, gated)
    y = (h_seq.astype(x.dtype) * gate_in) @ p["w_out"]
    k = p["conv"].shape[0]
    state = {
        "conv": u[:, -(k - 1):, :].astype(_dtype(cfg)),
        "h": h_seq[:, -1, :],
    }
    return y, state


# ----------------------------------------------------------------------------
# Mamba-2 SSD block
# ----------------------------------------------------------------------------


def init_mamba2(key, cfg: ModelConfig):
    d = cfg.d_model
    din = cfg.ssm_expand * d
    hd = cfg.ssm_head_dim
    nh = din // hd
    n = cfg.ssm_state
    dt = _dtype(cfg)
    ks = jax.random.split(key, 5)
    sc = 1.0 / math.sqrt(d)
    return {
        "w_in": jax.random.normal(ks[0], (d, 2 * din + 2 * n + nh), dt) * sc,
        "conv": jax.random.normal(ks[1], (cfg.ssm_conv, din + 2 * n), dt) * 0.3,
        "a_log": jnp.zeros((nh,), jnp.float32),
        "d_skip": jnp.ones((nh,), jnp.float32),
        "dt_bias": jnp.zeros((nh,), jnp.float32),
        "norm": jnp.zeros((din,), jnp.float32),
        "w_out": jax.random.normal(ks[4], (din, d), dt) * (1.0 / math.sqrt(din)),
    }


def _ssd_chunked(x, dtv, a_log, b, c, chunk: int):
    """SSD (state-space duality) chunked scan.

    x: (B,S,H,P) values; dtv: (B,S,H) step sizes (softplus'd);
    b,c: (B,S,N) input/output projections (single group);
    Returns y: (B,S,H,P).
    """
    bsz, s, h, p = x.shape
    n = b.shape[-1]
    nc = s // chunk
    assert nc * chunk == s, (s, chunk)
    # decay per step: da = dt * A  (A = -exp(a_log) < 0)
    a = -jnp.exp(a_log)[None, None, :]            # (1,1,H)
    da = dtv * a                                   # (B,S,H) negative
    xz = (x * dtv[..., None]).astype(jnp.float32)  # fold dt into input

    da_c = da.reshape(bsz, nc, chunk, h)
    x_c = xz.reshape(bsz, nc, chunk, h, p)
    b_c = b.astype(jnp.float32).reshape(bsz, nc, chunk, n)
    c_c = c.astype(jnp.float32).reshape(bsz, nc, chunk, n)

    cums = jnp.cumsum(da_c, axis=2)                # (B,NC,Q,H)
    # --- intra-chunk (diagonal blocks)
    # L[q, t] = exp(cums[q] - cums[t]) for t <= q.
    # (Storing L in bf16 was tried and REFUTED: XLA upcasts for the f32 dot,
    # traffic unchanged — §Perf mamba2 iteration log.)
    rel = cums[:, :, :, None, :] - cums[:, :, None, :, :]  # (B,NC,Q,Q,H)
    tri = jnp.tril(jnp.ones((chunk, chunk), jnp.float32))
    l_mat = jnp.exp(rel) * tri[None, None, :, :, None]
    cb = jnp.einsum("bcqn,bctn->bcqt", c_c, b_c)   # (B,NC,Q,Q)
    y_diag = jnp.einsum("bcqt,bcqth,bcthp->bcqhp", cb, l_mat, x_c)

    # --- chunk states: state_c = sum_t exp(cums[last]-cums[t]) * b_t x_t
    decay_to_end = jnp.exp(cums[:, :, -1:, :] - cums)        # (B,NC,Q,H)
    states = jnp.einsum("bctn,bcth,bcthp->bchnp", b_c, decay_to_end, x_c)

    # --- inter-chunk recurrence over NC (sequential scan, NC is small)
    chunk_decay = jnp.exp(cums[:, :, -1, :])                 # (B,NC,H)

    def scan_fn(carry, inp):
        dec, st = inp                                        # (B,H), (B,H,N,P)
        new = carry * dec[..., None, None] + st
        return new, carry                                    # emit PREVIOUS

    init = jnp.zeros((bsz, h, n, p), jnp.float32)
    final_state, prev_states = jax.lax.scan(
        scan_fn,
        init,
        (chunk_decay.swapaxes(0, 1), states.swapaxes(0, 1)),
    )
    prev_states = prev_states.swapaxes(0, 1)                 # (B,NC,H,N,P)

    # --- inter-chunk contribution
    decay_from_start = jnp.exp(cums)                         # (B,NC,Q,H)
    y_off = jnp.einsum(
        "bcqn,bcqh,bchnp->bcqhp", c_c, decay_from_start, prev_states
    )
    y = (y_diag + y_off).reshape(bsz, s, h, p)
    return y, final_state


def mamba2(p, x, cfg: ModelConfig, state=None):
    """Mamba-2 block. x: (B,S,D). state (decode): {"conv": (B,K-1,Cw),
    "ssm": (B,H,N,P)}. Returns (y, new_state)."""
    bsz, s, d = x.shape
    din = cfg.ssm_expand * d
    hd = cfg.ssm_head_dim
    nh = din // hd
    n = cfg.ssm_state
    decode = state is not None

    zxbcdt = x @ p["w_in"]
    z, xin, bc, dtv = jnp.split(zxbcdt, [din, 2 * din, 2 * din + 2 * n], axis=-1)
    conv_in = jnp.concatenate([xin, bc], axis=-1)
    conv_out, conv_state = _causal_conv1d(
        conv_in, p["conv"], state["conv"] if decode else None
    )
    conv_out = jax.nn.silu(conv_out)
    xin, b, c = jnp.split(conv_out, [din, din + n], axis=-1)
    dtv = jax.nn.softplus(dtv.astype(jnp.float32) + p["dt_bias"])  # (B,S,H)

    xh = xin.reshape(bsz, s, nh, hd)
    if decode:
        a = -jnp.exp(p["a_log"])[None, :]                     # (1,H)
        da = jnp.exp(dtv[:, 0] * a)                           # (B,H)
        xz = (xh[:, 0] * dtv[:, 0, :, None]).astype(jnp.float32)
        new_ssm = state["ssm"] * da[..., None, None] + jnp.einsum(
            "bn,bhp->bhnp", b[:, 0].astype(jnp.float32), xz
        )
        y = jnp.einsum("bn,bhnp->bhp", c[:, 0].astype(jnp.float32), new_ssm)
        y = y[:, None]                                        # (B,1,H,P)
        new_state = {"conv": conv_state, "ssm": new_ssm}
    else:
        chunk = min(cfg.ssm_chunk, s)
        pad = (-s) % chunk
        if pad:
            xh_p = jnp.pad(xh, ((0, 0), (0, pad), (0, 0), (0, 0)))
            dtv_p = jnp.pad(dtv, ((0, 0), (0, pad), (0, 0)))
            b_p = jnp.pad(b, ((0, 0), (0, pad), (0, 0)))
            c_p = jnp.pad(c, ((0, 0), (0, pad), (0, 0)))
        else:
            xh_p, dtv_p, b_p, c_p = xh, dtv, b, c
        y, final_ssm = _ssd_chunked(xh_p, dtv_p, p["a_log"], b_p, c_p, chunk)
        y = y[:, :s]
        new_state = {
            "conv": conv_in[:, -(cfg.ssm_conv - 1):, :].astype(x.dtype),
            "ssm": final_ssm,
        }
    y = y + xh.astype(jnp.float32) * p["d_skip"][None, None, :, None]
    y = y.reshape(bsz, s, din)
    # gated RMSNorm (mamba2 style)
    yf = y * jax.nn.silu(z.astype(jnp.float32))
    var = jnp.mean(yf * yf, axis=-1, keepdims=True)
    yf = yf * jax.lax.rsqrt(var + 1e-6) * (1.0 + p["norm"])
    return yf.astype(x.dtype) @ p["w_out"], new_state


def mamba2_prefill(p, x, cfg: ModelConfig):
    """Full-sequence Mamba-2 that also returns the final SSD/conv state."""
    return mamba2(p, x, cfg, state=None)


def init_mamba2_state(cfg: ModelConfig, batch: int):
    din = cfg.ssm_expand * cfg.d_model
    nh = din // cfg.ssm_head_dim
    return {
        "conv": jnp.zeros((batch, cfg.ssm_conv - 1, din + 2 * cfg.ssm_state), _dtype(cfg)),
        "ssm": jnp.zeros((batch, nh, cfg.ssm_state, cfg.ssm_head_dim), jnp.float32),
    }
