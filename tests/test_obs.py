"""Observability layer acceptance (the PR-8 contract).

Three load-bearing invariants:

  * **bit-identity** — greedy token streams are unchanged by observability:
    recording (metrics + tracing) never feeds back into execution;
  * **determinism** — an identical workload under ``ManualClock`` exports a
    byte-identical JSONL trace run to run, and ref/pallas backends produce
    the same span skeleton (ids, parents, timestamps — attrs may differ);
  * **zero-cost when off** — with the registry disabled (the default),
    instrument record calls are no-ops and leave no series behind.

Plus the mechanics: registry semantics (get-or-create, kind mismatch,
labels, collectors, reset), Prometheus text round-trip through the strict
parser, the /metrics HTTP server, structured-logger level filtering and
the bare-lambda back-compat path.
"""

import json
import urllib.request

import jax
import pytest

from repro import obs
from repro.configs.registry import smoke_config
from repro.models.model import init_params
from repro.obs.metrics import MetricsRegistry
from repro.serve.engine import Request, ServeEngine
from repro.serve.scheduler import ManualClock, Scheduler


@pytest.fixture(scope="module")
def kan_setup():
    cfg = smoke_config("qwen2.5-14b").kan_variant()
    return cfg, init_params(jax.random.PRNGKey(0), cfg)


@pytest.fixture(scope="module")
def float_setup():
    cfg = smoke_config("qwen2.5-14b")
    return cfg, init_params(jax.random.PRNGKey(0), cfg)


@pytest.fixture
def obs_on():
    """Enable recording for one test; leave the process as it was found."""
    obs.REGISTRY.reset()
    obs.enable()
    yield
    obs.disable()
    obs.REGISTRY.reset()


def make_reqs(cfg, n=2, plen=5, max_new=3, seed=42, **kw):
    rng = jax.random.PRNGKey(seed)
    reqs = []
    for rid in range(n):
        rng, k = jax.random.split(rng)
        prompt = jax.random.randint(k, (plen,), 3, cfg.vocab_size).tolist()
        reqs.append(Request(rid=rid, prompt=prompt, max_new_tokens=max_new,
                            **kw))
    return reqs


# -- metrics registry ---------------------------------------------------------


def test_registry_instruments_and_labels(obs_on):
    r = MetricsRegistry()
    r.counter("c").inc()
    r.counter("c").inc(2)
    r.counter("d").inc(backend="pallas")
    r.gauge("g").set(7.5)
    r.histogram("h", edges=(1.0, 2.0, 4.0)).observe(1.5)
    snap = r.snapshot()["metrics"]
    assert snap["c"] == {"kind": "counter", "value": 3}
    assert snap["d{backend=pallas}"]["value"] == 1
    assert snap["g"]["value"] == 7.5
    h = snap["h"]["value"]
    # fixed edges, value 1.5 lands in the (1, 2] bucket
    assert h["edges"] == [1.0, 2.0, 4.0]
    assert h["counts"] == [0, 1, 0, 0] and h["count"] == 1 and h["sum"] == 1.5
    # get-or-create: same name -> same instrument; kind mismatch refuses
    assert r.counter("c") is r.counter("c")
    with pytest.raises(ValueError, match="already registered"):
        r.gauge("c")


def test_disabled_recording_is_a_noop():
    obs.disable()
    r = MetricsRegistry()
    r.counter("c").inc()
    r.gauge("g").set(1.0)
    r.histogram("h").observe(0.5)
    assert r.snapshot()["metrics"] == {}
    # a bound label view is equally inert
    r.counter("c").labels(backend="ref").inc()
    assert r.snapshot()["metrics"] == {}


def test_histogram_rejects_unsorted_edges(obs_on):
    with pytest.raises(ValueError, match="strictly increase"):
        MetricsRegistry().histogram("h", edges=(2.0, 1.0))


def test_collectors_feed_snapshots_and_survive_reset(obs_on):
    r = MetricsRegistry()
    fn = lambda: {"pool.depth": 4,
                  ("disp.count", (("backend", "ref"),)): 9}
    r.register_collector(fn)
    snap = r.snapshot()["metrics"]
    assert snap["pool.depth"] == {"kind": "gauge", "value": 4}
    assert snap["disp.count{backend=ref}"]["value"] == 9
    r.reset()  # collectors survive a plain reset (import-time registrations)
    assert r.snapshot()["metrics"]["pool.depth"]["value"] == 4
    r.unregister_collector(fn)
    assert r.snapshot()["metrics"] == {}


def test_plan_cache_collector_registered_on_global_registry(obs_on):
    snap = obs.REGISTRY.snapshot()["metrics"]
    for k in ("plan_cache.hits", "plan_cache.misses", "plan_cache.traces"):
        assert k in snap and snap[k]["kind"] == "gauge"


# -- exposition ---------------------------------------------------------------


def test_prometheus_text_round_trips_strict_parser(obs_on):
    r = MetricsRegistry()
    r.counter("serve.tokens").inc(12)
    r.counter("runtime.backend_dispatch").inc(3, backend="pallas")
    r.histogram("serve.ttft_s", edges=(0.1, 1.0)).observe(0.05)
    r.histogram("serve.ttft_s", edges=(0.1, 1.0)).observe(5.0)
    text = obs.prometheus_text(r)
    parsed = obs.parse_prometheus_text(text)
    assert parsed["serve_tokens"] == 12
    assert parsed['runtime_backend_dispatch{backend="pallas"}'] == 3
    # cumulative buckets: 0.05 <= 0.1; 5.0 overflows to +Inf only
    assert parsed['serve_ttft_s_bucket{le="0.1"}'] == 1
    assert parsed['serve_ttft_s_bucket{le="1"}'] == 1
    assert parsed['serve_ttft_s_bucket{le="+Inf"}'] == 2
    assert parsed["serve_ttft_s_count"] == 2
    assert parsed["serve_ttft_s_sum"] == pytest.approx(5.05)
    with pytest.raises(ValueError, match="not a valid prometheus sample"):
        obs.parse_prometheus_text("this is { not a sample\n")


def test_dump_metrics_json_and_prom(tmp_path, obs_on):
    obs.REGISTRY.counter("serve.tokens").inc(5)
    pj, pp = tmp_path / "m.json", tmp_path / "m.prom"
    obs.dump_metrics(pj)
    obs.dump_metrics(pp)
    assert json.loads(pj.read_text())["metrics"]["serve.tokens"]["value"] == 5
    assert obs.parse_prometheus_text(pp.read_text())["serve_tokens"] == 5


def test_metrics_http_server_serves_both_formats(obs_on):
    obs.REGISTRY.counter("serve.tokens").inc(7)
    srv = obs.start_metrics_server(0)  # ephemeral port
    try:
        base = f"http://127.0.0.1:{srv.server_port}"
        text = urllib.request.urlopen(f"{base}/metrics").read().decode()
        assert obs.parse_prometheus_text(text)["serve_tokens"] == 7
        snap = json.loads(
            urllib.request.urlopen(f"{base}/metrics.json").read())
        assert snap["metrics"]["serve.tokens"]["value"] == 7
    finally:
        srv.shutdown()


# -- structured logging -------------------------------------------------------


def test_logger_level_filtering_and_formatting(monkeypatch):
    lines = []
    lg = obs.Logger("sched", sink=lines.append, level="info")
    lg.debug("dropped", rid=1)
    lg.info("request done", rid=3, latency_s=0.0421)
    lg.warning("backpressure", queued=4)
    assert lines == [
        "sched: request done rid=3 latency_s=0.0421",
        "sched: [warning] backpressure queued=4",
    ]
    # level=None re-reads REPRO_LOG_LEVEL per record
    lines.clear()
    envlg = obs.Logger("s", sink=lines.append)
    monkeypatch.setenv(obs.ENV_LOG_LEVEL_VAR, "error")
    envlg.info("hidden")
    monkeypatch.setenv(obs.ENV_LOG_LEVEL_VAR, "debug")
    envlg.debug("shown")
    assert lines == ["s: [debug] shown"]


def test_as_logger_back_compat_paths():
    # bare callable: DEBUG threshold, every record forwarded (legacy log=)
    got = []
    lg = obs.as_logger(got.append)
    lg.debug("admitted request", rid=0)
    lg("request done", rid=0)        # __call__ keeps the old lambda shape
    assert got == ["[debug] admitted request rid=0", "request done rid=0"]
    # None -> the named process logger; Logger -> itself
    assert obs.as_logger(None, "x") is obs.get_logger("x")
    assert obs.as_logger(lg) is lg
    with pytest.raises(TypeError):
        obs.as_logger(42)


# -- tracer -------------------------------------------------------------------


def test_tracer_records_events_spans_and_trims():
    clk = ManualClock()
    tr = obs.Tracer(clock=clk.now, max_records=3)
    root = tr.begin("request", rid=5)
    clk.advance(1.0)
    tr.event("first_token", parent=root)
    child = tr.begin("decode", parent=root)
    clk.advance(0.5)
    tr.end(child, tokens=2)
    tr.end(root, status="done")
    with pytest.raises(ValueError, match="already ended"):
        tr.end(root)
    recs = tr.records()
    assert len(recs) == 3 and tr.dropped == 0
    assert [r["id"] for r in recs] == [0, 1, 2]  # sequence-number ids
    ev = next(r for r in recs if r["type"] == "event")
    assert ev["rid"] == 5 and ev["t0"] == 1.0  # rid inherits from parent
    # past the cap the oldest CLOSED record is dropped; export notes it
    tr.event("extra")
    assert tr.dropped == 1
    assert [r["name"] for r in tr.records()] == [
        "first_token", "decode", "extra"]


def _serve_traced(params, cfg, backend, path):
    """One deterministic 2-request workload (one future arrival) under
    ManualClock, traced; exports JSONL to ``path`` and returns outputs."""
    clock = ManualClock()
    eng = ServeEngine(params, cfg, slots=2, max_len=32, kan_deploy=True,
                      kan_backend=backend)
    sched = Scheduler(eng, clock=clock, trace=True)
    reqs = make_reqs(cfg, n=2, max_new=3)
    reqs[1].arrival_s = 2.5
    for r in reqs:
        sched.submit(r)
    sched.run_until_idle()
    sched.tracer.export_jsonl(path)
    return {r.rid: r.output for r in sched.finished}, sched


def test_trace_jsonl_byte_identical_across_runs(kan_setup, tmp_path):
    cfg, params = kan_setup
    out1, _ = _serve_traced(params, cfg, "pallas", tmp_path / "a.jsonl")
    out2, _ = _serve_traced(params, cfg, "pallas", tmp_path / "b.jsonl")
    assert out1 == out2
    a, b = (tmp_path / "a.jsonl").read_bytes(), \
        (tmp_path / "b.jsonl").read_bytes()
    assert a == b and a  # identical and non-empty


def test_trace_skeleton_identical_across_backends(kan_setup, tmp_path):
    """ref and pallas serve the same schedule -> same span tree (ids,
    parents, rids, ManualClock timestamps); attrs are allowed to differ."""
    cfg, params = kan_setup
    out_r, sch_r = _serve_traced(params, cfg, "ref", tmp_path / "r.jsonl")
    out_p, sch_p = _serve_traced(params, cfg, "pallas", tmp_path / "p.jsonl")
    assert out_r == out_p                  # greedy bit-identity, per PR-5
    sk_r, sk_p = sch_r.tracer.skeleton(), sch_p.tracer.skeleton()
    assert sk_r == sk_p and len(sk_r) > 0


def test_trace_span_taxonomy_complete_timeline(float_setup):
    """A served request leaves the full documented span tree: request >
    queued/prefill/decode spans (all closed) + a first_token event."""
    cfg, params = float_setup
    clock = ManualClock()
    eng = ServeEngine(params, cfg, slots=2, max_len=32)
    sched = Scheduler(eng, clock=clock, trace=True)
    for r in make_reqs(cfg, n=1, max_new=3):
        sched.submit(r)
    sched.run_until_idle()
    recs = sched.tracer.records()
    by_name = {}
    for r in recs:
        by_name.setdefault(r["name"], []).append(r)
    for name in ("request", "queued", "prefill", "decode"):
        (span,) = by_name[name]
        assert span["type"] == "span" and span["t1"] is not None
        assert span["rid"] == 0
    (ft,) = by_name["first_token"]
    assert ft["type"] == "event" and ft["parent"] == by_name["request"][0]["id"]
    assert by_name["request"][0]["attrs"]["status"] == "done"
    assert by_name["decode"][0]["attrs"]["tokens"] == 3
    # expired-while-queued requests close their tree too
    sched2 = Scheduler(ServeEngine(params, cfg, slots=1, max_len=32),
                       clock=clock, trace=True)
    (rq,) = make_reqs(cfg, n=1)
    rq.deadline_s = 0.5
    sched2.submit(rq)
    clock.advance(1.0)
    sched2.step()
    (root,) = [r for r in sched2.tracer.records() if r["name"] == "request"]
    assert root["attrs"]["status"] == "expired" and root["t1"] is not None


def test_chrome_export_shape(float_setup, tmp_path):
    cfg, params = float_setup
    clock = ManualClock()
    eng = ServeEngine(params, cfg, slots=2, max_len=32)
    sched = Scheduler(eng, clock=clock, trace=True)
    for r in make_reqs(cfg, n=1, max_new=2):
        sched.submit(r)
    sched.run_until_idle()
    path = tmp_path / "t.json"
    sched.tracer.export_chrome(path)
    doc = json.loads(path.read_text())
    evs = doc["traceEvents"]
    assert {e["ph"] for e in evs} <= {"X", "i"}
    # one timeline row per request; the rounds' spans on the global row
    assert all(e["tid"] == (-1 if e["name"].startswith("serve.") else 0)
               for e in evs)
    assert {e["tid"] for e in evs} == {-1, 0}


# -- lexical spans: scheduler rounds on the profiler's clock -----------------


class TickClock(ManualClock):
    """Every reading moves time on by one tick, so spans have widths and a
    run stays deterministic (the same reads give the same times)."""

    def now(self) -> float:
        self._t += 1e-3
        return self._t


ROUND_CHILDREN = {"serve.admit", "serve.prefill_chunk", "serve.readback",
                  "serve.emit", "serve.decode_step"}
ROUND_COUNTERS = {"chunks", "chunk_tokens", "decode_slots", "admitted",
                  "queued"}


def _serve_rounds(params, cfg, clock, path=None):
    """Three 11-token prompts through 2 slots in chunks of 4, traced
    (``clock`` None: the wall clock)."""
    eng = ServeEngine(params, cfg, slots=2, max_len=32, kv_block_size=8,
                      prefill_chunk=4)
    sched = Scheduler(eng, clock=clock, trace=True)
    for r in make_reqs(cfg, n=3, plen=11, max_new=3):
        sched.submit(r)
    sched.run_until_idle()
    if path is not None:
        sched.tracer.export_jsonl(path)
    return sched


def test_profile_scope_nests_counts_and_is_free_when_off():
    clk = ManualClock()
    tr = obs.Tracer(clock=clk.now)
    obs.disable_profiler_annotations()
    with obs.recording(tr):
        with obs.profile_scope("serve.round", chunks=0) as rnd:
            with obs.profile_scope("serve.admit", k=1) as adm:
                clk.advance(0.5)
                rnd.attrs["chunks"] += 2       # counted through the span
            with obs.profile_scope("kan_spline.ref", record=False) as ann:
                assert ann is None
    assert (adm.parent, adm.attrs, adm.t1) == (rnd.id, {"k": 1}, 0.5)
    assert rnd.attrs == {"chunks": 2}
    assert [r["name"] for r in tr.records()] == ["serve.round", "serve.admit"]
    # nothing recording, annotations off: one shared null context
    assert obs.profile_scope("serve.round") is obs.profile_scope("x")
    with obs.profile_scope("serve.round") as off:
        assert off is None
    assert len(tr.records()) == 2 and not obs.trace.scopes_on()


def test_round_spans_tile_the_round_and_count_its_work(float_setup, tmp_path):
    """A chunked-prefill workload: one serve.round per round, its children
    in order inside it, and counters that add up to the work."""
    cfg, params = float_setup
    sched = _serve_rounds(params, cfg, TickClock(), tmp_path / "a.jsonl")
    recs = sched.tracer.records()
    spans = {r["id"]: r for r in recs if r["type"] == "span"}
    rounds = [r for r in recs if r["name"] == "serve.round"]
    assert rounds and all(r["parent"] is None and r["rid"] is None
                          for r in rounds)
    n_chunks = n_decode = 0
    for rnd in rounds:
        assert set(rnd["attrs"]) == ROUND_COUNTERS
        kids = sorted((s for s in spans.values() if s["parent"] == rnd["id"]),
                      key=lambda s: s["t0"])
        assert {k["name"] for k in kids} <= ROUND_CHILDREN
        assert kids[0]["name"] == "serve.admit"
        # the children lie in the round, one after another; with the
        # round's self time they tile it
        edges = [rnd["t0"]] + [x for k in kids for x in (k["t0"], k["t1"])]
        assert edges == sorted(edges) and kids[-1]["t1"] <= rnd["t1"]
        busy = sum(k["t1"] - k["t0"] for k in kids)
        assert (rnd["t1"] - rnd["t0"]) - busy > 0          # the self time
        names = [k["name"] for k in kids]
        assert rnd["attrs"]["chunks"] == names.count("serve.prefill_chunk")
        assert (rnd["attrs"]["decode_slots"] > 0) == (
            "serve.decode_step" in names)
        n_chunks += rnd["attrs"]["chunks"]
        n_decode += rnd["attrs"]["decode_slots"]
    # per-round chunks add up to the chunks each request's prefill took
    prefills = [r for r in recs if r["name"] == "prefill"]
    assert n_chunks == sum(p["attrs"]["chunks"] for p in prefills) == 3 * 3
    assert sum(r["attrs"]["chunk_tokens"] for r in rounds) == 3 * 11
    assert sum(r["attrs"]["admitted"] for r in rounds) == 3
    assert rounds[0]["attrs"]["queued"] == 1          # 3 requests, 2 slots
    assert n_decode == sum(len(r.output) - 1 for r in sched.finished)
    # and the export is byte-identical run to run
    _serve_rounds(params, cfg, TickClock(), tmp_path / "b.jsonl")
    a = (tmp_path / "a.jsonl").read_bytes()
    b = (tmp_path / "b.jsonl").read_bytes()
    assert a == b and b"serve.round" in a


def test_idle_round_reads_no_work(float_setup):
    """A round before the first arrival dispatches nothing: its span holds
    only the admission scan, and its work counters read 0."""
    cfg, params = float_setup
    eng = ServeEngine(params, cfg, slots=2, max_len=32, kv_block_size=8,
                      prefill_chunk=4)
    sched = Scheduler(eng, clock=TickClock(), trace=True)
    (req,) = make_reqs(cfg, n=1, plen=11, max_new=2)
    req.arrival_s = 0.5
    sched.submit(req)
    assert sched.step() is False
    recs = sched.tracer.records()
    (rnd,) = [r for r in recs if r["name"] == "serve.round"]
    assert rnd["attrs"] == {"chunks": 0, "chunk_tokens": 0,
                            "decode_slots": 0, "admitted": 0, "queued": 1}
    assert [r["name"] for r in recs if r["parent"] == rnd["id"]] == [
        "serve.admit"]
    sched.run_until_idle()
    worked = [r for r in sched.tracer.records() if r["name"] == "serve.round"
              and (r["attrs"]["chunks"] or r["attrs"]["decode_slots"])]
    assert sum(r["attrs"]["chunk_tokens"] for r in worked) == 11


def test_check_obs_accepts_round_spans_and_refuses_bad_counters(
        float_setup, tmp_path):
    import importlib.util
    import pathlib

    spec = importlib.util.spec_from_file_location(
        "check_obs", pathlib.Path(__file__).parents[1] / "scripts"
        / "check_obs.py")
    check = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(check)
    cfg, params = float_setup
    path = tmp_path / "t.jsonl"
    _serve_rounds(params, cfg, TickClock(), path)
    assert check.check_trace(str(path)) == []
    lines = [json.loads(x) for x in path.read_text().splitlines()]
    for bad, want in ((lambda d: d["attrs"].update(chunks=99), "chunks=99"),
                      (lambda d: d["attrs"].pop("queued"), "counters"),
                      (lambda d: d.update(name="round"), "must be serve.")):
        out = [dict(d, attrs=dict(d["attrs"])) for d in lines]
        bad(next(d for d in out if d["name"] == "serve.round"
                 and d["attrs"]["chunks"]))
        path.write_text("".join(json.dumps(d) + "\n" for d in out))
        errors = check.check_trace(str(path))
        assert any(want in e for e in errors), errors


def test_profiler_offset_maps_anchored_spans_on_cpu(float_setup, tmp_path):
    """Rounds traced on the wall clock under a CPU jax.profiler capture:
    every lexical span is anchored, and the fitted offset puts each one
    within 0.5 ms of its event; the offset export lands on the events."""
    import glob

    cfg, params = float_setup
    _serve_rounds(params, cfg, None)           # compile outside the capture
    obs.enable_profiler_annotations()
    jax.profiler.start_trace(str(tmp_path))
    try:
        sched = _serve_rounds(params, cfg, None)
    finally:
        jax.profiler.stop_trace()
        obs.disable_profiler_annotations()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = obs.anchored_events(path)
    lexical = {r["id"]: r for r in sched.tracer.records()
               if r["type"] == "span" and r["rid"] is None}
    fit = sched.tracer.profiler_offset(events)
    assert fit.n == len(lexical) == len(events)
    assert fit.spread_s < 0.5e-3
    shifted = {r["id"]: r for r in sched.tracer.records(fit.offset_s)}
    for e in events:
        sid = int(e.stats["id"])
        assert e.name == lexical[sid]["name"]
        assert abs(e.start_ns * 1e-9 - shifted[sid]["t0"]) < 0.5e-3
    assert "serve.round" in {e.name for e in events}
    assert sched.tracer.profiler_offset([]) is None


# -- end-to-end: bit-identity + metrics coverage ------------------------------


def test_greedy_streams_bit_identical_with_obs_enabled(float_setup):
    """The headline acceptance: observability on (metrics + tracing) must
    not change a single emitted token."""
    cfg, params = float_setup
    eng = ServeEngine(params, cfg, slots=2, max_len=32)
    baseline = {r.rid: r.output for r in eng.run(make_reqs(cfg))}
    obs.enable()
    try:
        eng2 = ServeEngine(params, cfg, slots=2, max_len=32)
        sched = Scheduler(eng2, trace=True)
        for r in make_reqs(cfg):
            sched.submit(r)
        sched.run_until_idle()
        assert {r.rid: r.output for r in sched.finished} == baseline
    finally:
        obs.disable()
        obs.REGISTRY.reset()


def test_served_workload_covers_documented_metric_names(kan_setup, obs_on):
    """A served request on a paged KAN engine populates the documented
    dotted names across all three subsystems (the acceptance snapshot)."""
    cfg, params = kan_setup
    eng = ServeEngine(params, cfg, slots=2, max_len=32, kan_deploy=True,
                      kan_backend="ref", kv_block_size=8)
    sched = Scheduler(eng)
    for r in make_reqs(cfg, n=2, max_new=2):
        sched.submit(r)
    sched.run_until_idle()
    snap = obs.REGISTRY.snapshot()["metrics"]
    assert snap["serve.submitted"]["value"] == 2
    assert snap["serve.completed"]["value"] == 2
    assert snap["serve.tokens"]["value"] == sched.stats()["tokens"]
    assert snap["serve.ttft_s"]["kind"] == "histogram"
    assert snap["serve.ttft_s"]["value"]["count"] == 2
    assert "kv.blocks_in_use" in snap and "kv.prefix_hits" in snap
    assert "plan_cache.hits" in snap
    assert snap["runtime.backend_dispatch{backend=ref}"]["value"] > 0
    # and the whole snapshot survives strict Prometheus exposition
    obs.parse_prometheus_text(obs.prometheus_text())
