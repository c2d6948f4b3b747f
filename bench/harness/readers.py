"""The per-layer quantities, read from a traced run's context.

A reader returns None where it finds nothing to read, and the harness
then leaves the metric out of the line; a share of a roofline or a peak
is never reported as 0 for want of events.

The context (``run.py``): ``trace`` (a ``harness.trace.Reduced`` of the
traced part of the window), ``peaks`` (roofline/peaks.json for this
device), ``conf`` and ``traffic``; for LM cells ``steps`` (each decode
step with its live lengths and each prefill chunk with its start, length
and prompt length, dispatched inside the traced part), ``spans`` (the
Scheduler tracer's records) and ``due``/``window``/``end`` (due times, the
span whose due requests are the window's, and the window's close, on the
scheduler's clock).
"""

from __future__ import annotations

from roofline import bound_s
from roofline import ops as R

from .common import nearest_rank
from .kan import KERNEL as KAN_NET_KERNEL
from .lm import ATTN_KERNEL, DECODE_PROGRAM, KAN_KERNEL, PREFILL_PROGRAM


def _share(need_s: float, took_s: float) -> float | None:
    if need_s <= 0 or took_s <= 0:
        return None
    return 100.0 * need_s / took_s


def queue_wait_p90_ms(ctx) -> float | None:
    """Admission minus due time of every request due in the window (the
    Scheduler's ``queued`` spans), censored at the window's end."""
    w0, w1 = ctx["window"]
    end = ctx["end"]
    admit = {r["rid"]: r["t1"] for r in ctx["spans"]
             if r["type"] == "span" and r["name"] == "queued"
             and r["t1"] is not None}
    waits = [min(admit.get(rid, end), end) - t
             for rid, t in ctx["due"].items() if w0 <= t < w1]
    return None if not waits else 1e3 * nearest_rank(waits, 0.90)


def _program_ms(ctx, name) -> float | None:
    tr = ctx["trace"]
    n = tr.program_calls.get(name, 0)
    return 1e3 * tr.program_s[name] / n if n else None


def decode_step_ms(ctx):
    """Device time of the decode-step program per decode step."""
    return _program_ms(ctx, DECODE_PROGRAM)


def prefill_chunk_ms(ctx):
    """Device time of the prefill-chunk program per chunk."""
    return _program_ms(ctx, PREFILL_PROGRAM)


def _lm_rows(step) -> int:
    return len(step[3]) if step[0] == "decode" else step[3]


def kan_roofline(ctx) -> float | None:
    """Least time of the KAN work over ``kan_pipeline`` device time; the
    compute bound at the int8 peak (codes of 8 bits or fewer)."""
    pk = ctx["peaks"]
    b = lambda o, n: bound_s(o, n, pk["int8_ops_per_s"], pk["hbm_bytes_per_s"])
    tr = ctx["trace"]
    if ctx["kind"] == "kan":
        layers = R.kan_network(ctx["conf"], ctx["traffic"]["rows_per_call"])
        calls = tr.kernel_calls.get(KAN_NET_KERNEL, 0) / len(layers)
        need = calls * sum(b(o, n) for o, n in layers)
        return _share(need, tr.kernel_s.get(KAN_NET_KERNEL, 0.0))
    n_layers = ctx["conf"]["num_hidden_layers"]
    need = sum(n_layers * sum(b(o, n) for o, n in
                              R.kan_ffn_halves(ctx["conf"], _lm_rows(s)))
               for s in ctx["steps"])
    return _share(need, tr.kernel_s.get(KAN_KERNEL, 0.0))


def attn_roofline(ctx) -> float | None:
    """Least time of the causal attention work over ``flash_attention``
    device time, at the bf16 peak."""
    pk = ctx["peaks"]
    conf = ctx["conf"]
    need = 0.0
    for s in ctx["steps"]:
        o, n = (R.attention_decode(conf, s[3]) if s[0] == "decode"
                else R.attention_prefill(conf, s[2], s[3]))
        need += conf["num_hidden_layers"] * bound_s(
            o, n, pk["bf16_flops_per_s"], pk["hbm_bytes_per_s"])
    return _share(need, ctx["trace"].kernel_s.get(ATTN_KERNEL, 0.0))


def idle_share(ctx) -> float | None:
    """Share of the traced window in which no operation ran on the device."""
    return 100.0 * ctx["trace"].idle_share


def mfu(ctx) -> float | None:
    """Model operations of the work served in the traced window over the
    window times the bf16 peak."""
    tr = ctx["trace"]
    if ctx["kind"] == "kan":
        layers = R.kan_network(ctx["conf"], ctx["traffic"]["rows_per_call"])
        calls = tr.kernel_calls.get(KAN_NET_KERNEL, 0) / len(layers)
        ops = calls * sum(o for o, _ in layers)
    else:
        ops = sum(R.lm_step_ops(ctx["conf"], "decode", s[3])
                  if s[0] == "decode" else
                  R.lm_step_ops(ctx["conf"], "prefill",
                                (s[2], s[3], s[2] + s[3] >= s[4]))
                  for s in ctx["steps"])
    return _share(ops / ctx["peaks"]["bf16_flops_per_s"], tr.window_s)
