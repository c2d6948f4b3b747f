"""Window arithmetic and the traffic generator."""

import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from harness import common as C  # noqa: E402
from harness import traffic as T  # noqa: E402
from harness.lm import Window, window_metrics  # noqa: E402

CHAT = C.read_data(BENCH / "traffic" / "chat.json")


def test_nearest_rank_and_support():
    xs = list(range(1, 101))
    assert C.nearest_rank(xs, 0.90) == 90
    assert C.nearest_rank(xs, 0.95) == 95
    assert C.beyond(100, 0.90) == 10
    assert C.nearest_rank([], 0.5) is None


def test_ttft_is_from_due_time_and_censored():
    due = {0: 0.5, 1: 1.0, 2: 2.0, 3: 9.0, 4: 11.0}
    first = {0: 0.7, 1: 1.5, 3: 12.0}
    # request 0 is due before the window, 4 after it; 2 never got a token
    # and 3 got it only after the window: both enter at (end - due)
    got = C.ttft_samples(due, first, 1.0, 10.0)
    assert sorted(got) == pytest.approx(sorted([0.5, 8.0, 1.0]))


def test_open_loop_tails_cover_the_planned_span():
    """The measured window opens a round late; the requests due in the
    planned span are still the window's, censored at its close."""
    win = Window(w0=10.3, w1=20.4, due_span=(10.0, 20.0))
    win.due = {0: 10.1, 1: 15.0, 2: 20.2}
    win.first = {0: 10.6, 2: 20.3}
    m = window_metrics(win, CHAT)
    assert sorted(m["ttft_s"]) == pytest.approx([0.5, 5.4])
    assert C.ttft_samples(win.due, win.first, 10.0, 20.0, 20.4) == \
        pytest.approx([0.5, 5.4])


def test_whole_blocks_fill_the_chat_window():
    """The chat mix's window holds whole blocks: every seed's window has
    the same requests' sizes."""
    lead, sec = CHAT["lead_s"], 51.0
    seen = set()
    for seed in (1, 2**31 + 77, 2**40 + 3):
        ps = T.plan(CHAT, seed, T.requests_needed(CHAT, sec))
        win = [p for p in ps if lead <= p.arrival_s < lead + sec]
        assert len(win) % CHAT["block"] == 0
        seen.add((tuple(sorted(p.prompt_len for p in win)),
                  tuple(sorted(p.output_len for p in win))))
    assert len(seen) == 1


def test_tails_cover_all_requests_and_rates_the_whole_window():
    win = Window(w0=10.0, w1=20.0)
    win.times = {0: [9.0, 10.5, 11.0], 1: [12.0, 19.0, 21.0]}
    win.first = {0: 9.0, 1: 12.0}
    win.due = {0: 8.0, 1: 11.0}
    win.prefilled0, win.prefilled1 = 100, 600
    m = window_metrics(win, CHAT)
    # gaps ending inside [10, 20): 1.5, 0.5 (request 0), 7.0 (request 1)
    assert sorted(m["itl_s"]) == pytest.approx([0.5, 1.5, 7.0])
    assert m["itl_p95_ms"] == pytest.approx(7000.0)
    assert m["ttft_s"] == pytest.approx([1.0])
    # 4 output tokens and 500 prompt tokens inside a 10 s window
    assert m["tokens_per_s"] == pytest.approx(50.4)


def test_every_seed_gets_the_same_sizes_in_its_own_order():
    """Without ``order_seed`` the run's seed draws each block's order."""
    seeded = {k: v for k, v in CHAT.items() if k != "order_seed"}
    n = CHAT["block"]
    a = T.plan(seeded, 1, 2 * n)
    b = T.plan(seeded, 2**31 + 12345, 2 * n)
    for lo in (0, n):
        sa = sorted(p.prompt_len for p in a[lo:lo + n])
        sb = sorted(p.prompt_len for p in b[lo:lo + n])
        assert sa == sb
        assert sorted(p.output_len for p in a[lo:lo + n]) == sorted(
            p.output_len for p in b[lo:lo + n])
    assert [p.prompt_len for p in a] != [p.prompt_len for p in b]
    lens = [p.prompt_len for p in a]
    assert min(lens) >= 64 and max(lens) <= 2048
    # same arrivals in another order: the span of a block is fixed
    assert a[n].arrival_s == pytest.approx(b[n].arrival_s)
    assert a[n].arrival_s - a[0].arrival_s == pytest.approx(
        n / CHAT["rate_per_s"])
    gaps = np.diff([p.arrival_s for p in T.plan(seeded, 3, 320)])
    assert np.mean(gaps) == pytest.approx(1 / CHAT["rate_per_s"], rel=0.05)


@pytest.mark.parametrize("order_seed", [0, 2**40 + 9])
def test_order_seed_fixes_the_schedule(order_seed):
    """With ``order_seed`` every run's seed serves the same sizes and due
    times in the same order; only the token ids follow the run's seed."""
    t = dict(CHAT, order_seed=order_seed)
    n = 2 * CHAT["block"]
    a, b = T.plan(t, 1, n), T.plan(t, 2**31 + 12345, n)
    assert [(p.prompt_len, p.output_len, p.arrival_s) for p in a] == \
        [(p.prompt_len, p.output_len, p.arrival_s) for p in b]
    assert T.prompt_tokens(t, 1, a[0], 1000) != \
        T.prompt_tokens(t, 2**31 + 12345, b[0], 1000)
    # the same sizes as any seeded block, in the order_seed's order
    seeded = {k: v for k, v in CHAT.items() if k != "order_seed"}
    c = T.plan(seeded, order_seed, n)
    assert [p.prompt_len for p in a] == [p.prompt_len for p in c]


def test_bursts_keep_the_mean_rate():
    t = dict(CHAT, burst={"period_s": 10, "on_s": 2, "factor": 4})
    due = np.array([p.arrival_s for p in T.plan(t, 5, 400)])
    assert len(due) / (due[-1] - due[0]) == pytest.approx(
        CHAT["rate_per_s"], rel=0.05)
    phase = (due - due[0]) % 10
    on = np.mean(phase < 2) / 0.2
    assert on > 2.0          # the first 2 s of each period get more


def test_shared_prefix_and_vocabulary():
    t = dict(CHAT, shared_prefix={"tokens": 100, "groups": 2})
    ps = T.plan(t, 9, 6)
    toks = [T.prompt_tokens(t, 9, p, 1000) for p in ps]
    assert toks[0][:100] == toks[2][:100] != toks[1][:100]
    assert all(len(x) == p.prompt_len for x, p in zip(toks, ps))
    assert all(3 <= v < 1000 for x in toks for v in x)


def test_big_seeds():
    a, b = C.seed_words(2**31 + 5)
    assert 0 <= a < 2**31 and 0 <= b < 2**31
    assert C.seed_words(2**31 + 5) != C.seed_words(2**31 + 6)
