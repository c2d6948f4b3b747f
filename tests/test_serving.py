"""Serving-path correctness: prefill + decode must agree with full forward.

This is the invariant the decode_32k / long_500k dry-run cells rely on: the
rolling-window KV cache, recurrent states, and SSD states all reproduce the
full-sequence computation token by token.
"""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.configs.registry import smoke_config
from repro.models.model import decode_step, forward, init_params, prefill

# top-3 slowest tier-1 suite: kept in CI, deselectable locally
pytestmark = pytest.mark.slow

ARCHS_TO_CHECK = [
    "llama3-405b", "qwen2.5-14b", "gemma2-27b", "mixtral-8x7b",
    "recurrentgemma-9b", "mamba2-370m", "whisper-base", "pixtral-12b",
    "olmoe-1b-7b",
]


def _batch(cfg, key, b, s):
    batch = {"tokens": jax.random.randint(key, (b, s), 0, cfg.vocab_size)}
    if cfg.family == "audio":
        batch["enc_embeds"] = jax.random.normal(key, (b, cfg.enc_seq, cfg.d_model))
    if cfg.family == "vlm":
        batch["patch_embeds"] = jax.random.normal(
            key, (b, cfg.num_patches, cfg.patch_embed_dim)
        )
    return batch


@pytest.mark.parametrize("name", ARCHS_TO_CHECK)
def test_prefill_then_decode_matches_forward(name):
    cfg = smoke_config(name)
    if cfg.num_experts:  # disable capacity drops for the equivalence check
        cfg = dataclasses.replace(cfg, moe_capacity_factor=16.0)
    key = jax.random.PRNGKey(0)
    params = init_params(key, cfg)
    b, s = 2, 24
    batch = _batch(cfg, key, b, s)
    full = forward(params, batch, cfg)

    pb = dict(batch)
    pb["tokens"] = batch["tokens"][:, : s - 3]
    logits_p, cache = prefill(params, pb, cfg, max_len=s + 8)
    scale = float(jnp.abs(full).max())
    assert float(jnp.abs(logits_p - full[:, s - 4]).max()) < 2e-3 * scale + 1e-4

    npfx = cfg.num_patches if cfg.family == "vlm" else 0
    for i in range(s - 3, s):  # decode the last 3 tokens
        pos = jnp.full((b,), i + npfx, jnp.int32)
        logits_d, cache = decode_step(params, cache, batch["tokens"][:, i], pos, cfg)
        err = float(jnp.abs(logits_d - full[:, i]).max())
        assert err < 2e-3 * scale + 1e-4, (name, i, err)


def test_serve_engine_kan_ffn_fused_path_matches_float_tokens():
    """--kan-ffn serving regression: along the float engine's greedy token
    streams, the logits of the ASP-quantized KAN-FFN path (kan_deploy=True,
    the fused Pallas pipeline — interpret mode on CPU) stay within the
    quantization error of the float path's logits, prefill and every decode
    step.  Tokens are not compared: on random weights an argmax can sit
    inside that error."""
    from repro.runtime.parity import assert_close
    from repro.serve.engine import Request, ServeEngine

    cfg = smoke_config("qwen2.5-14b").kan_variant()
    key = jax.random.PRNGKey(0)
    params = init_params(key, cfg)

    def make_reqs():
        rng = jax.random.PRNGKey(42)
        reqs = []
        for rid in range(3):
            rng, k = jax.random.split(rng)
            prompt = jax.random.randint(k, (6,), 3, cfg.vocab_size).tolist()
            reqs.append(Request(rid=rid, prompt=prompt, max_new_tokens=4))
        return reqs

    float_engine = ServeEngine(params, cfg, slots=2, max_len=32)
    served = float_engine.run(make_reqs())
    fused_engine = ServeEngine(params, cfg, slots=2, max_len=32,
                               kan_deploy=True)
    for r in served:
        assert r.status == "done" and len(r.output) == 4
        want = float_engine.teacher_forced_logits(r.prompt, r.output[:-1])
        got = fused_engine.teacher_forced_logits(r.prompt, r.output[:-1])
        # 8-bit ASP activation codes and int8 per-channel weights each
        # carry up to half an LSB of 2**-7 relative error per term, which
        # reaches the logits as ~1% of their scale (measured 0.7-1.0%);
        # dropping the FFN altogether moves them by ~58%.
        assert_close(f"request {r.rid} logits", got, want, 3e-2,
                     "8-bit ASP quantization error")


def test_serve_engine_kan_deploy_rejects_non_kan_config():
    from repro.serve.engine import ServeEngine

    cfg = smoke_config("qwen2.5-14b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError):
        ServeEngine(params, cfg, slots=2, max_len=32, kan_deploy=True)


def test_serve_engine_rejects_unknown_kan_backend():
    from repro.serve.engine import ServeEngine

    cfg = smoke_config("qwen2.5-14b").kan_variant()
    params = init_params(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError):
        ServeEngine(params, cfg, slots=2, max_len=32, kan_deploy=True,
                    kan_backend="tpu-magic")


def test_prefill_length_buckets_compile_once_per_bucket_same_tokens():
    """Prompt padding to power-of-two buckets: a mixed-length request stream
    compiles O(log L) prefill variants instead of one per distinct length,
    and (masked cache splice + true-last-token logits) decodes the SAME
    tokens as exact-length prefill."""
    from repro.serve.engine import Request, ServeEngine, \
        prefill_bucketing_supported

    cfg = smoke_config("qwen2.5-14b")
    assert prefill_bucketing_supported(cfg)  # pure global attention
    params = init_params(jax.random.PRNGKey(0), cfg)
    lengths = [3, 5, 6, 7, 9, 12]

    def make_reqs():
        rng = jax.random.PRNGKey(7)
        reqs = []
        for rid, ln in enumerate(lengths):
            rng, k = jax.random.split(rng)
            prompt = jax.random.randint(k, (ln,), 3, cfg.vocab_size).tolist()
            reqs.append(Request(rid=rid, prompt=prompt, max_new_tokens=3))
        return reqs

    bucketed = ServeEngine(params, cfg, slots=2, max_len=64)
    assert bucketed.prefill_buckets
    out_b = {r.rid: r.output for r in bucketed.run(make_reqs())}

    exact = ServeEngine(params, cfg, slots=2, max_len=64,
                        prefill_buckets=False)
    out_e = {r.rid: r.output for r in exact.run(make_reqs())}

    assert out_b == out_e
    # lengths {3,5,6,7} -> bucket 8; {9,12} -> bucket 16
    assert bucketed.prefill_traces == 2, bucketed.compile_stats()
    assert exact.prefill_traces == len(set(lengths))
    assert bucketed.decode_traces == 1


def test_prefill_bucketing_auto_disabled_for_stateful_stacks():
    """Recurrent/SSM/windowed stacks integrate pad tokens into their state —
    the engine must fall back to exact-length prefill for them."""
    from repro.serve.engine import prefill_bucketing_supported
    from repro.serve.engine import ServeEngine

    for name in ("mamba2-370m", "recurrentgemma-9b", "gemma2-27b"):
        cfg = smoke_config(name)
        assert not prefill_bucketing_supported(cfg), name
    cfg = smoke_config("mamba2-370m")
    params = init_params(jax.random.PRNGKey(0), cfg)
    eng = ServeEngine(params, cfg, slots=2, max_len=32)
    assert not eng.prefill_buckets  # even though the default asks for it


def test_serve_engine_kan_backend_ref_matches_pallas_tokens():
    """kan_backend plumbs through repro.runtime: the layered "ref" executor
    and the fused "pallas" executor serve identical greedy tokens."""
    from repro.serve.engine import Request, ServeEngine

    cfg = smoke_config("qwen2.5-14b").kan_variant()
    params = init_params(jax.random.PRNGKey(0), cfg)

    def make_reqs():
        rng = jax.random.PRNGKey(11)
        reqs = []
        for rid in range(2):
            rng, k = jax.random.split(rng)
            prompt = jax.random.randint(k, (5,), 3, cfg.vocab_size).tolist()
            reqs.append(Request(rid=rid, prompt=prompt, max_new_tokens=3))
        return reqs

    outs = {}
    for backend in ("ref", "pallas"):
        eng = ServeEngine(params, cfg, slots=2, max_len=32, kan_deploy=True,
                          kan_backend=backend)
        outs[backend] = {r.rid: r.output for r in eng.run(make_reqs())}
    assert outs["ref"] == outs["pallas"]


@pytest.mark.skipif(
    len(jax.devices()) < 2,
    reason="needs >= 2 devices "
           "(XLA_FLAGS=--xla_force_host_platform_device_count=8)",
)
def test_serve_engine_mesh_sharded_same_tokens():
    """ServeEngine(mesh=...) — slot pool/KV on "data", KAN-FFN channels on
    "model" — must serve exactly the tokens of the single-device engine on
    the same request stream (the PR-4 acceptance criterion)."""
    from repro import runtime
    from repro.launch.mesh import make_local_mesh
    from repro.serve.engine import Request, ServeEngine

    cfg = smoke_config("qwen2.5-14b").kan_variant()
    params = init_params(jax.random.PRNGKey(0), cfg)

    def make_reqs():
        rng = jax.random.PRNGKey(21)
        reqs = []
        for rid in range(3):
            rng, k = jax.random.split(rng)
            prompt = jax.random.randint(k, (6,), 3, cfg.vocab_size).tolist()
            reqs.append(Request(rid=rid, prompt=prompt, max_new_tokens=3))
        return reqs

    runtime.reset_cache()
    e0 = ServeEngine(params, cfg, slots=2, max_len=32, kan_deploy=True)
    out0 = {r.rid: r.output for r in e0.run(make_reqs())}

    n = len(jax.devices())
    mesh = make_local_mesh(2, 2) if n >= 4 else make_local_mesh(2, 1)
    e1 = ServeEngine(params, cfg, slots=2, max_len=32, kan_deploy=True,
                     mesh=mesh)
    out1 = {r.rid: r.output for r in e1.run(make_reqs())}
    assert out0 == out1
    layout = e1.compile_stats()["mesh"]
    assert layout["axes"] == ["data", "model"]
    assert layout["devices"] == layout["shape"][0] * layout["shape"][1]
    assert e0.compile_stats()["mesh"] is None


def test_rolling_window_cache_exceeding_window():
    """Decode past the window: rolling cache must equal full SWA attention."""
    cfg = smoke_config("mixtral-8x7b")
    cfg = dataclasses.replace(cfg, window_size=8, moe_capacity_factor=16.0)
    key = jax.random.PRNGKey(1)
    params = init_params(key, cfg)
    b, s = 1, 20  # s >> window
    batch = _batch(cfg, key, b, s)
    full = forward(params, batch, cfg)
    pb = {"tokens": batch["tokens"][:, :4]}
    _, cache = prefill(params, pb, cfg, max_len=s)
    scale = float(jnp.abs(full).max())
    for i in range(4, s):
        pos = jnp.full((b,), i, jnp.int32)
        logits_d, cache = decode_step(params, cache, batch["tokens"][:, i], pos, cfg)
        err = float(jnp.abs(logits_d - full[:, i]).max())
        assert err < 2e-3 * scale + 1e-4, (i, err)
