#!/usr/bin/env python3
"""Find the knee of an open-loop LM cell: one process, one engine, several
arrival rates, each a window of its own after a lead-in.

    python3 bench/sweep.py --workload qwen2.5-14b-kanffn.chat --seed 7 \
        --rates 2,3,4,5,6,8 --seconds 20

For each rate it prints TTFT p50/p90, ITL p50/p95, tokens/s, the queue
left at the window's end and the share of requests due in the window that
got their first token inside it, as one JSON line per rate.  The knee is
the highest rate that is sustained (see ``sustained``); the cell's
traffic file holds 0.8 of it as a number.  Rates are swept in the order
given, and the sweep stops ``--stop-after`` rates past the knee.
"""

from __future__ import annotations

import argparse
import json
import sys

import run  # noqa: F401  (puts the benchmark and the program on the path)
from harness import common, lm
from harness.common import log, nearest_rank


def sustained(row: dict) -> bool:
    """The rate is sustained where at least 95% of the requests due in the
    window got their first token inside it and at most 4 were still
    queued at its end."""
    return row["first_token_share"] >= 0.95 and row["queue_at_end"] <= 4


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--stop-after", type=int, default=1,
                    help="stop after this many rates past the knee")
    args = ap.parse_args()
    _, cell, conf, traffic = common.load_cell(args.workload, run.REPO)
    devices = run.accelerators(int(cell["chips"]))
    run.use_cache()
    engine, cfg, _ = lm.setup(conf, traffic, args.seed, len(devices))
    past = 0
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        t = dict(traffic, rate_per_s=rate)
        # prompts of a seed of their own at each rate: the same prompts
        # again would hit the engine's prefix cache
        win = lm.serve(engine, cfg, t, args.seed + 1 + i, args.seconds)
        m = lm.window_metrics(win, t)
        d0, d1 = win.due_window()
        started = sum(1 for rid, d in win.due.items()
                      if d0 <= d < d1 and rid in win.first)
        row = {"rate_per_s": rate, "due": m["due_in_window"],
               "finished": m["finished"],
               "first_token_share": started / max(1, m["due_in_window"]),
               "queue_at_end": sum(1 for r in win.sched.queue
                                   if r.arrival_s < win.w1),
               "ttft_p50_ms": 1e3 * (nearest_rank(m["ttft_s"], 0.5) or 0),
               "ttft_p90_ms": m["ttft_p90_ms"],
               "itl_p50_ms": 1e3 * (nearest_rank(m["itl_s"], 0.5) or 0),
               "itl_p95_ms": m["itl_p95_ms"],
               "tokens_per_s": m["tokens_per_s"],
               "output_tokens_per_s": m["output_tokens"] / m["window_s"]}
        row["sustained"] = sustained(row)
        print(json.dumps(row), flush=True)
        past += not row["sustained"]
        if past >= args.stop_after:
            break
        # drain: drop what is still queued, finish what is in the slots
        win.sched.queue.clear()
        win.sched.run_until_idle()
        log(f"rate {rate}: drained")
    return 0


if __name__ == "__main__":
    sys.exit(main())
