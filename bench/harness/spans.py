"""Per-layer quantities read from the program's own spans: the traced
Scheduler's records (``ctx["spans"]``, on the scheduler's clock).

Each scheduling round is one ``serve.round`` span, with the host's waits
for device results as ``serve.readback`` spans below it.  A round counts
if it did work (its ``chunks``, ``decode_slots`` or ``admitted`` counter
is above 0), begins in ``ctx["window"]`` and ends by ``ctx["end"]`` (the
window's close).  A reader returns None where nothing counts, as in
a program that records no rounds, and the harness then leaves the metric
out of the line.
"""

from __future__ import annotations

from .common import nearest_rank

ROUND, READBACK, PREFILL = "serve.round", "serve.readback", "prefill"


def _spans(ctx, name) -> list:
    return [r for r in ctx["spans"] if r["type"] == "span"
            and r["name"] == name and r["t1"] is not None]


def _worked(r) -> bool:
    a = r["attrs"]
    return bool(a.get("chunks") or a.get("decode_slots") or a.get("admitted"))


def rounds(ctx) -> list:
    """The ``serve.round`` spans that did work, begin in the window and
    end by its close."""
    w0, w1 = ctx["window"]
    return [r for r in _spans(ctx, ROUND)
            if _worked(r) and w0 <= r["t0"] < w1 and r["t1"] <= ctx["end"]]


def round_ms(ctx) -> float | None:
    """Mean duration of a scheduling round."""
    rs = rounds(ctx)
    if not rs:
        return None
    return 1e3 * sum(r["t1"] - r["t0"] for r in rs) / len(rs)


def _merged_s(intervals) -> float:
    total, reach = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if e > reach:
            total += e - max(s, reach)
            reach = e
    return total


def round_host_ms(ctx) -> float | None:
    """Mean, per round, of its duration less the time its
    ``serve.readback`` spans (at any depth below it) cover: the host time
    a round spends without waiting on the device."""
    rs = rounds(ctx)
    if not rs:
        return None
    parent = {r["id"]: r["parent"] for r in ctx["spans"]
              if r["type"] == "span"}
    waits = {r["id"]: [] for r in rs}
    for rb in _spans(ctx, READBACK):
        p = rb["parent"]
        while p is not None and p not in waits:
            p = parent.get(p)
        if p is not None:
            waits[p].append((rb["t0"], rb["t1"]))
    host = [(r["t1"] - r["t0"]) - _merged_s(waits[r["id"]]) for r in rs]
    return 1e3 * sum(host) / len(host)


def prefill_wait_p90_ms(ctx) -> float | None:
    """p90 of the request's ``prefill`` span (admission to first token),
    over the requests due in the window and admitted before its close,
    censored at the close."""
    w0, w1 = ctx["window"]
    end = ctx["end"]
    spans = {r["rid"]: r for r in ctx["spans"]
             if r["type"] == "span" and r["name"] == PREFILL}
    waits = []
    for rid, due in ctx["due"].items():
        sp = spans.get(rid)
        if not (w0 <= due < w1) or sp is None or sp["t0"] >= end:
            continue
        t1 = end if sp["t1"] is None else min(sp["t1"], end)
        waits.append(t1 - sp["t0"])
    return None if not waits else 1e3 * nearest_rank(waits, 0.90)
