"""The readers of the program's own spans: by hand on a record list, and
in whole traced runs of the tiny chat and long-document cells."""

import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src"), str(BENCH / "tests")]

import pytest  # noqa: E402

import bench_tiny as B  # noqa: E402
import repro.launch.compile_cache as cache  # noqa: E402
import run  # noqa: E402
from harness import common as C  # noqa: E402
from harness import spans as S  # noqa: E402

# bench_tiny.run_cell replaces these for the rest of the process; taken
# here, while the test files are collected and before any run
HOOKS = (run.accelerators, cache.use_compile_cache)

NEW = {"round_ms.chat": "qwen2.5-14b-kanffn.chat",
       "round_host_ms.chat": "qwen2.5-14b-kanffn.chat",
       "prefill_wait_p90_ms.chat": "qwen2.5-14b-kanffn.chat"}


def span(sid, name, t0, t1, parent=None, rid=None, **attrs):
    if name == "serve.round" and not attrs:
        attrs = {"chunks": 0, "decode_slots": 2, "admitted": 0}
    return {"type": "span", "name": name, "id": sid, "parent": parent,
            "rid": rid, "t0": t0, "t1": t1, "attrs": attrs}


def ctx_of(records, window=(10.0, 20.0), end=20.5, due=None):
    return {"spans": records, "window": window, "end": end,
            "due": due or {}}


def test_round_means_by_hand():
    recs = [
        span(0, "serve.round", 9.0, 10.5),              # began before
        span(1, "serve.round", 10.0, 10.4),
        span(2, "serve.readback", 10.1, 10.2, parent=1),
        span(3, "serve.round", 12.0, 12.8),
        span(4, "serve.admit", 12.0, 12.3, parent=3),
        span(5, "serve.readback", 12.1, 12.2, parent=4),  # below a child
        span(6, "serve.readback", 12.5, 12.7, parent=3),
        span(7, "serve.round", 19.9, 20.6),             # ends after the close
        span(8, "serve.round", 20.0, 20.1),             # begins after
        {"type": "event", "name": "first_token", "id": 9, "parent": None,
         "rid": 0, "t0": 11.0, "attrs": {}},
        span(10, "serve.round", 13.0, 13.1, chunks=0, decode_slots=0,
             admitted=0),                               # found no work
        span(11, "serve.round", 14.0, 14.2, chunks=1, decode_slots=0,
             admitted=0),
        span(12, "serve.round", 15.0, 15.2, chunks=0, decode_slots=0,
             admitted=1),
    ]
    ctx = ctx_of(recs)
    assert [r["id"] for r in S.rounds(ctx)] == [1, 3, 11, 12]
    ctx = ctx_of(recs[:-2])
    assert [r["id"] for r in S.rounds(ctx)] == [1, 3]
    assert S.round_ms(ctx) == pytest.approx(1e3 * (0.4 + 0.8) / 2)
    assert S.round_host_ms(ctx) == pytest.approx(1e3 * (0.3 + 0.5) / 2)


def test_no_rounds_read_nothing():
    recs = [span(0, "serve.decode_step", 11.0, 11.1),
            span(1, "serve.round", 21.0, 21.5),
            span(2, "serve.round", 12.0, None)]
    for read in (S.round_ms, S.round_host_ms):
        assert read(ctx_of(recs)) is None
        assert read(ctx_of([])) is None
    assert S.prefill_wait_p90_ms(ctx_of([])) is None


def test_prefill_wait_p90_censored_at_the_close():
    due = {rid: 10.0 + rid for rid in range(10)}
    due.update({10: 9.0, 11: 21.0})                  # outside the window
    recs = [span(100 + rid, "prefill", due[rid] + 0.1,
                 due[rid] + 0.1 + 0.01 * (rid + 1), rid=rid)
            for rid in due]
    recs[9] = span(109, "prefill", 19.5, None, rid=9)   # no token yet
    recs[8] = span(108, "prefill", 18.5, 21.0, rid=8)   # token after close
    recs.append(span(200, "prefill", 20.6, 20.7, rid=12))
    due[12] = 19.9                                      # admitted after close
    waits = sorted([0.01 * (rid + 1) for rid in range(8)] + [2.0, 1.0])
    got = S.prefill_wait_p90_ms(ctx_of(recs, due=due))
    assert got == pytest.approx(1e3 * C.nearest_rank(waits, 0.90))
    assert got == pytest.approx(1e3 * 1.0)


def test_the_new_entries_are_found_by_name():
    b = C.load_benchmark(BENCH.parent)
    entries = {m["name"]: m for m in b["per_layer"]}
    for name, cell in NEW.items():
        m = entries[name]
        assert m["source"] == "program_span" and m["workloads"] == [cell]
        assert callable(C.metric_reader(name))


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return B.make_root(tmp_path_factory.mktemp("checkout"))


@pytest.fixture
def cpu_trace(monkeypatch):
    """A traced run on the CPU: the CPU client's op events stand in for a
    device's, and the chip's peaks for the CPU's (the rooflines are not
    read here).  After it the annotations are off again, and the harness
    looks for a chip again, for the test files that run next."""
    import roofline
    from harness import trace as T
    from repro import obs

    load = T.load
    monkeypatch.setattr(T, "load", lambda path: load(
        path, op_line=lambda p, l: l.startswith("tf_XLAPjRtCpuClient")))
    monkeypatch.setattr(roofline, "peaks",
                        lambda kind: roofline.PEAKS["TPU v5 lite"])
    yield
    obs.disable_profiler_annotations()
    run.accelerators, cache.use_compile_cache = HOOKS


@pytest.mark.parametrize("cell", ["qwen2.5-14b-kanffn.chat",
                                  "qwen2.5-14b-kanffn.longdoc"])
def test_traced_cells_report_the_span_metrics(root, cpu_trace, cell):
    res = B.run_cell(root, cell, seconds=2.0, trace=1)
    assert res["correct"], res["checks"]
    want = {n for n, c in NEW.items() if c == cell}
    got = res["metrics"]
    assert want <= set(got), sorted(got)
    for name in want:
        assert got[name]["unit"] == "ms" and got[name]["value"] > 0
    if cell.endswith(".chat"):
        assert (got["round_host_ms.chat"]["value"]
                <= got["round_ms.chat"]["value"])
        assert "queue_wait_p90_ms.chat" in got
    else:
        # the control cell reads no span metric (its dispatches block on
        # the busy device, so a host time there is not host work)
        assert not any(n.startswith("round") for n in got), sorted(got)
        assert "idle_share.longdoc" in got
