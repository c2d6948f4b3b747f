"""Async scheduler correctness (the PR-5 acceptance contract).

The load-bearing invariant: greedy token streams produced by the
event-driven scheduler are BIT-IDENTICAL to ``ServeEngine.run()`` on the
same request set — per runtime backend (``ref`` / ``pallas`` / quiet
``acim``) and on a 1x1 mesh — because the scheduler drives exactly the
engine's compiled prefill/decode internals and ``run()`` is a thin driver
over the scheduler.  On top of that: streaming callbacks must replay the
final outputs token for token, seeded sampling must reproduce, and the
admission-policy edges (bounded queue, deadline expiry, pool-full _admit)
must fail loudly instead of silently.
"""

import dataclasses

import jax
import pytest

from repro import runtime
from repro.configs.registry import smoke_config
from repro.models.model import init_params
from repro.runtime.executor import ACIMExecutor
from repro.serve.engine import Request, ServeEngine
from repro.serve.scheduler import (
    ManualClock,
    QueueFull,
    SamplingParams,
    Scheduler,
    sample_token,
)

# a zero-noise acim executor: must trace the exact same program as "pallas",
# so its greedy serving streams are part of the bit-identity acceptance
runtime.register_executor(
    "acim-quiet", ACIMExecutor(cim=runtime.quiet_cim_config())
)


@pytest.fixture(scope="module")
def float_setup():
    cfg = smoke_config("qwen2.5-14b")
    return cfg, init_params(jax.random.PRNGKey(0), cfg)


@pytest.fixture(scope="module")
def kan_setup():
    cfg = smoke_config("qwen2.5-14b").kan_variant()
    return cfg, init_params(jax.random.PRNGKey(0), cfg)


def make_reqs(cfg, n=2, plen=5, max_new=3, seed=42, **kw):
    rng = jax.random.PRNGKey(seed)
    reqs = []
    for rid in range(n):
        rng, k = jax.random.split(rng)
        prompt = jax.random.randint(k, (plen,), 3, cfg.vocab_size).tolist()
        reqs.append(Request(rid=rid, prompt=prompt, max_new_tokens=max_new,
                            **kw))
    return reqs


def scheduler_streams(engine, reqs):
    """Run reqs through an explicit Scheduler, collecting streamed tokens."""
    sched = Scheduler(engine)
    streams = {}
    for r in reqs:
        sched.submit(
            r, on_token=lambda req, t: streams.setdefault(req.rid, []).append(t)
        )
    finished = sched.run_until_idle()
    return {r.rid: r.output for r in finished}, streams, sched


@pytest.mark.parametrize("backend", ["ref", "pallas", "acim-quiet"])
def test_scheduler_greedy_stream_bit_identical_to_run(kan_setup, backend):
    """Acceptance: scheduler == run() token streams per backend, and the
    on_token stream replays the final outputs exactly."""
    cfg, params = kan_setup
    eng = ServeEngine(params, cfg, slots=2, max_len=32, kan_deploy=True,
                      kan_backend=backend)
    ref_out = {r.rid: r.output for r in eng.run(make_reqs(cfg))}

    eng2 = ServeEngine(params, cfg, slots=2, max_len=32, kan_deploy=True,
                       kan_backend=backend)
    out, streams, sched = scheduler_streams(eng2, make_reqs(cfg))
    assert out == ref_out
    assert streams == ref_out
    s = sched.stats()
    assert s["completed"] == len(ref_out) and s["expired"] == 0


def test_scheduler_greedy_mesh_1x1_matches_unmeshed_run(kan_setup):
    """A 1x1 mesh serves the same tokens as no mesh at all, through the
    scheduler (shard_map wrapping must stay bit-invisible)."""
    from repro.launch.mesh import make_local_mesh

    cfg, params = kan_setup
    eng = ServeEngine(params, cfg, slots=2, max_len=32, kan_deploy=True)
    ref_out = {r.rid: r.output for r in eng.run(make_reqs(cfg))}

    mesh = make_local_mesh(1, 1)
    eng2 = ServeEngine(params, cfg, slots=2, max_len=32, kan_deploy=True,
                       mesh=mesh)
    out, streams, _ = scheduler_streams(eng2, make_reqs(cfg))
    assert out == ref_out
    assert streams == ref_out


def test_seeded_sampling_reproducible_and_seed_sensitive(float_setup):
    cfg, params = float_setup
    sp = SamplingParams(temperature=0.9, top_k=16, top_p=0.95, seed=7)

    def serve(sampling):
        eng = ServeEngine(params, cfg, slots=2, max_len=32)
        out, _, _ = scheduler_streams(
            eng, make_reqs(cfg, n=3, max_new=4, sampling=sampling)
        )
        return out

    a, b = serve(sp), serve(sp)
    assert a == b  # same seed -> byte-identical streams
    c = serve(dataclasses.replace(sp, seed=8))
    assert c != a  # a different seed draws a different stream
    greedy = serve(None)
    assert a != greedy  # temperature actually samples


def test_sampling_top_k_one_collapses_to_greedy(float_setup):
    """top_k=1 keeps only the argmax token: any temperature must emit the
    greedy stream (sampling reduces to selection, bit-identical)."""
    cfg, params = float_setup
    eng = ServeEngine(params, cfg, slots=2, max_len=32)
    greedy = {r.rid: r.output for r in eng.run(make_reqs(cfg, n=2))}
    eng2 = ServeEngine(params, cfg, slots=2, max_len=32)
    out, _, _ = scheduler_streams(
        eng2,
        make_reqs(cfg, n=2, sampling=SamplingParams(temperature=3.0, top_k=1)),
    )
    assert out == greedy


def test_sample_token_validates_params():
    with pytest.raises(ValueError):
        SamplingParams(temperature=-1.0)
    with pytest.raises(ValueError):
        SamplingParams(top_p=0.0)
    with pytest.raises(ValueError):
        SamplingParams(top_k=-2)
    # pure function: same (logits, params, rid, pos) -> same token
    import numpy as np

    logits = np.linspace(-1.0, 1.0, 32).astype(np.float32)
    sp = SamplingParams(temperature=1.0, top_p=0.9, seed=3)
    assert sample_token(logits, sp, 5, 2) == sample_token(logits, sp, 5, 2)


def test_queue_full_admission_rejected(float_setup):
    cfg, params = float_setup
    eng = ServeEngine(params, cfg, slots=1, max_len=32)
    sched = Scheduler(eng, max_queue=1)
    r0, r1 = make_reqs(cfg, n=2)
    sched.submit(r0)
    with pytest.raises(QueueFull):
        sched.submit(r1)
    assert sched.stats()["rejected"] == 1
    sched.run_until_idle()  # the admitted request still completes
    assert r0.status == "done" and len(r0.output) == r0.max_new_tokens


def test_deadline_expiry_while_queued(float_setup):
    """With one slot busy, a queued request whose deadline lapses is expired
    unserved: empty output, status 'expired', on_done fired, counted."""
    cfg, params = float_setup
    clock = ManualClock()
    eng = ServeEngine(params, cfg, slots=1, max_len=32)
    sched = Scheduler(eng, clock=clock)
    r0, r1 = make_reqs(cfg, n=2, max_new=6)
    r1.deadline_s = 0.5
    done_order = []
    sched.submit(r0, on_done=lambda r: done_order.append(r.rid))
    sched.submit(r1, on_done=lambda r: done_order.append(r.rid))
    sched.step()            # admits r0; r1 queued behind the single slot
    clock.advance(1.0)      # r1's queued wait now exceeds its deadline
    sched.step()
    assert r1.status == "expired" and r1.done and r1.output == []
    assert done_order == [1]
    sched.run_until_idle()
    assert r0.status == "done" and len(r0.output) == 6
    s = sched.stats()
    assert s["expired"] == 1 and s["completed"] == 1
    assert done_order == [1, 0]


def test_future_arrivals_wait_and_stats_snapshot(float_setup):
    """A request with a future arrival_s stays invisible to admission until
    its offset; run_until_idle advances a ManualClock across the gap."""
    cfg, params = float_setup
    clock = ManualClock()
    eng = ServeEngine(params, cfg, slots=2, max_len=32)
    sched = Scheduler(eng, clock=clock)
    r0, r1 = make_reqs(cfg, n=2)
    r1.arrival_s = 5.0
    sched.submit(r0)
    sched.submit(r1)
    sched.run_until_idle()
    assert r0.status == "done" and r1.status == "done"
    assert sched.elapsed() >= 5.0          # the loop waited for the arrival
    assert r1.ttft_s <= 0.5                # TTFT from arrival, not submit
    s = sched.stats()
    assert s["submitted"] == 2 and s["completed"] == 2
    assert s["tokens"] == len(r0.output) + len(r1.output)
    assert s["ttft_s"]["n"] == 2 and s["ttft_s"]["p95"] is not None
    assert s["queue_depth"]["rounds"] > 0


def test_engine_admit_without_free_slot_raises(float_setup):
    cfg, params = float_setup
    eng = ServeEngine(params, cfg, slots=1, max_len=32)
    r0, r1 = make_reqs(cfg, n=2)
    eng._admit(r0)
    with pytest.raises(RuntimeError, match="free slot"):
        eng._admit(r1)


def test_scheduler_adopts_slots_admitted_directly_on_engine(float_setup):
    """A request admitted via ServeEngine._admit (direct engine use) before
    the scheduler takes over must be adopted, not crash the decode round:
    run() drains it alongside scheduler-admitted requests."""
    cfg, params = float_setup
    eng = ServeEngine(params, cfg, slots=2, max_len=32)
    r0, r1 = make_reqs(cfg, n=2, max_new=4)
    eng._admit(r0)                # behind the scheduler's back
    results = eng.run([r1])       # run() wraps a fresh Scheduler
    assert {r.rid for r in results} == {0, 1}
    assert r0.status == "done" and len(r0.output) == 4
    assert r1.status == "done" and len(r1.output) == 4


def test_long_lived_scheduler_memory_stays_bounded(float_setup):
    """Memory-bounds regression for a long-lived scheduler: hundreds of
    requests through ONE scheduler (paged engine, chunked prefill) must
    leave only capped/scalar state behind — stat tails capped at 4096,
    per-request records bounded by the slot count, callback maps emptied
    on retire, finished drained by the caller, and the KV pool back to
    empty with a consistent free/ref/evictable partition."""
    cfg, params = float_setup
    eng = ServeEngine(params, cfg, slots=2, max_len=32,
                      kv_block_size=8, prefill_chunk=4)
    sched = Scheduler(eng)
    total, wave = 520, 65
    for start in range(0, total, wave):
        for rid in range(start, start + wave):
            # fixed-shape prompts: one prefill trace, the loop stays fast
            sched.submit(Request(rid=rid, prompt=[3 + rid % 29] * 5,
                                 max_new_tokens=1),
                         on_token=lambda r, t: None,
                         on_done=lambda r: None)
        sched.run_until_idle()
        drained = sched.drain_finished()
        assert len(drained) == wave and not sched.finished
        # per-request state lives only while a request is active
        assert len(sched._rec) <= eng.slots
        assert not sched._on_token and not sched._on_done
    s = sched.stats()
    assert s["submitted"] == total and s["completed"] == total
    # prefill token + the decode round that observes len >= max_new
    assert s["tokens"] == 2 * total
    # stat tails are capped deques — a long-lived scheduler's footprint
    # does not grow with total requests served
    for tail in (sched._ttfts, sched._itls):
        assert tail.maxlen == 4096 and len(tail) <= 4096
    assert s["ttft_s"]["n"] == min(total, 4096)
    # the paged pool drained clean: no leaked blocks, invariants hold
    assert eng.pool.blocks_in_use() == 0
    assert eng._free_slots == list(range(eng.slots))
    eng.pool.check_consistent()


def test_stats_snapshot_safe_with_zero_requests(float_setup):
    """stats() on a fresh scheduler: every field defined, no div-zero, no
    NaN anywhere (the snapshot must stay strict-JSON serializable)."""
    import json

    cfg, params = float_setup
    sched = Scheduler(ServeEngine(params, cfg, slots=2, max_len=32))
    s = sched.stats()
    assert s["submitted"] == 0 and s["completed"] == 0
    assert s["tokens"] == 0 and s["tokens_per_s"] is None
    assert s["ttft_s"] is None
    assert s["itl_s"] == {"n": 0, "mean": None, "p50": None, "p95": None}
    assert s["queue_depth"]["mean"] == 0.0
    json.dumps(s, allow_nan=False)  # raises on any NaN/Inf


def test_stats_expired_only_workload_reports_null_ttft(float_setup):
    """Every request expires in the queue (no first token ever): ttft_s is
    None — not an empty summary, not garbage — and nothing divides by
    zero."""
    import json

    cfg, params = float_setup
    clock = ManualClock()
    sched = Scheduler(ServeEngine(params, cfg, slots=1, max_len=32),
                      clock=clock)
    for r in make_reqs(cfg, n=2):
        r.deadline_s = 0.5
        sched.submit(r)
    clock.advance(1.0)      # both deadlines lapse before any admission
    sched.step()
    s = sched.stats()
    assert s["expired"] == 2 and s["completed"] == 0
    assert s["ttft_s"] is None and s["tokens_per_s"] is None
    assert s["tokens"] == 0
    json.dumps(s, allow_nan=False)


def test_request_defaults_keep_old_call_sites_working():
    """Pre-scheduler construction (rid/prompt/max_new_tokens only) must keep
    working: arrival 'now', no deadline, greedy."""
    r = Request(rid=0, prompt=[1, 2, 3], max_new_tokens=4)
    assert r.arrival_s == 0.0 and r.deadline_s is None and r.sampling is None
    assert r.status == "pending" and r.ttft_s == 0.0


def _serve_chunked(params, cfg, trace: bool):
    eng = ServeEngine(params, cfg, slots=2, max_len=32, kv_block_size=8,
                      prefill_chunk=4)
    sched = Scheduler(eng, clock=ManualClock(), trace=trace)
    for r in make_reqs(cfg, n=3, plen=11, max_new=4):
        sched.submit(r)
    sched.run_until_idle()
    return {r.rid: r.output for r in sched.finished}, sched


def test_untraced_round_records_nothing(float_setup, monkeypatch):
    """Tracing and annotations off: a round opens no span, counts into no
    tracer (not even one installed around it) and keeps no per-round
    sample; greedy streams match a traced run token for token."""
    from repro import obs
    from repro.obs import trace as T

    cfg, params = float_setup
    obs.disable_profiler_annotations()
    opened = []
    monkeypatch.setattr(T, "_Scope", lambda *a: opened.append(a))
    outer = obs.Tracer(clock=ManualClock().now)
    with obs.recording(outer):
        plain, sched = _serve_chunked(params, cfg, trace=False)
    assert sched.tracer is None and outer.records() == [] and opened == []
    assert T._RECORDING is None
    assert not hasattr(sched, "_depth_samples")
    assert set(sched.stats()["queue_depth"]) == {"rounds", "max", "mean"}
    monkeypatch.undo()
    traced, tsched = _serve_chunked(params, cfg, trace=True)
    assert traced == plain
    assert any(r["name"] == "serve.round" for r in tsched.tracer.records())
