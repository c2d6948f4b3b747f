"""Obs-artifact validator: the CI `obs-smoke` gate.

Takes the files a served workload wrote (`launch.serve --metrics-dump` /
`--trace-out`) and refuses anything malformed:

  * **Prometheus dumps** — must parse under the STRICT
    ``repro.obs.parse_prometheus_text`` (every non-comment line a valid
    sample), and must cover the documented name families: at least one
    ``serve_*``, ``plan_cache_*`` sample (``kv_*`` too when the workload
    ran a paged engine — checked when present).
  * **JSON snapshots** — ``{"metrics": {series: {"kind", "value"}}}`` with
    every kind one of counter/gauge/histogram and histogram values
    carrying consistent edges/counts/count.
  * **JSONL traces** — every line schema-checked (type/name/id/parent/rid/
    t0/attrs; spans also t1), ids strictly increasing, parents resolving
    to earlier spans, every span closed (t1 >= t0), and each traced
    request carrying the full documented taxonomy: a ``request`` span with
    ``queued`` child, a terminal status, and — for served requests — a
    ``first_token`` event between ``prefill`` and ``decode``.  Records
    with no ``rid`` are the scheduler's lexical spans: ``serve.*`` spans
    nested inside their parent's interval, under a ``serve.round`` root
    whose counters (``chunks``, ``chunk_tokens``, ``decode_slots``,
    ``admitted``, ``queued``) are whole numbers, with ``chunks`` equal to
    the prefill dispatches below it and ``decode_slots`` > 0 exactly when a
    decode or verify step ran in it.

    PYTHONPATH=src python scripts/check_obs.py \
        --prom metrics.prom --json metrics.json --trace trace.jsonl
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))


def check_prom(path: str) -> list:
    from repro.obs import parse_prometheus_text

    errors = []
    try:
        samples = parse_prometheus_text(Path(path).read_text())
    except ValueError as e:
        return [f"{path}: {e}"]
    if not samples:
        return [f"{path}: no samples at all"]
    for family in ("serve_", "plan_cache_"):
        if not any(name.startswith(family) for name in samples):
            errors.append(f"{path}: no {family}* samples")
    # histogram exposition consistency: every _bucket family needs its
    # _count, and the +Inf bucket must equal it
    for name, v in samples.items():
        if '_bucket{le="+Inf"}' in name:
            base = name.split("_bucket{")[0]
            count = samples.get(f"{base}_count")
            if count is None:
                errors.append(f"{path}: {base}_bucket without {base}_count")
            elif v != count:
                errors.append(f"{path}: {base} +Inf bucket {v} != count "
                              f"{count}")
    print(f"{path}: {len(samples)} samples ok")
    return errors


def check_json(path: str) -> list:
    errors = []
    doc = json.loads(Path(path).read_text())
    metrics = doc.get("metrics")
    if not isinstance(metrics, dict) or not metrics:
        return [f"{path}: missing/empty 'metrics' object"]
    for series, entry in metrics.items():
        kind = entry.get("kind")
        if kind not in ("counter", "gauge", "histogram"):
            errors.append(f"{path}: {series}: bad kind {kind!r}")
            continue
        v = entry.get("value")
        if kind == "histogram":
            if (not isinstance(v, dict)
                    or len(v.get("counts", [])) != len(v.get("edges", [])) + 1
                    or sum(v["counts"]) != v.get("count")):
                errors.append(f"{path}: {series}: inconsistent histogram")
        elif not isinstance(v, (int, float)):
            errors.append(f"{path}: {series}: non-numeric value {v!r}")
    print(f"{path}: {len(metrics)} series ok")
    return errors


SPAN_KEYS = {"type", "name", "id", "parent", "rid", "t0", "t1", "attrs"}
EVENT_KEYS = {"type", "name", "id", "parent", "rid", "t0", "attrs"}
ROUND_COUNTERS = ("chunks", "chunk_tokens", "decode_slots", "admitted",
                  "queued")
PREFILL_DISPATCHES = ("serve.prefill_chunk", "serve.prefill")
STEP_SPANS = ("serve.decode_step", "serve.verify")


def check_lexical(path: str, spans: dict) -> list:
    """The rid-less spans: serve.* spans nested in their parent's interval
    under a serve.round root whose counters match its children."""
    errors = []
    below: dict = {}   # round id -> names of the spans under it
    for sid, rec in spans.items():
        if rec["rid"] is not None:
            continue
        where = f"{path}: span {rec['name']}#{sid}"
        if not rec["name"].startswith("serve."):
            errors.append(f"{where}: a span with no rid must be serve.*")
            continue
        root, par = rec, spans.get(rec["parent"])
        if par is not None:
            if par["rid"] is not None or not par["name"].startswith("serve."):
                errors.append(f"{where}: parent is not a serve.* span")
                continue
            if rec["t0"] < par["t0"] or rec["t1"] > par["t1"]:
                errors.append(f"{where}: outside its parent's interval")
        while root["parent"] is not None and root["parent"] in spans:
            root = spans[root["parent"]]
        if root["name"] != "serve.round":
            errors.append(f"{where}: not under a serve.round")
        elif root is not rec:
            below.setdefault(root["id"], []).append(rec["name"])
    for sid, rec in spans.items():
        if rec["rid"] is not None or rec["name"] != "serve.round":
            continue
        where = f"{path}: serve.round#{sid}"
        a = rec["attrs"]
        if not all(isinstance(a.get(k), int) and a[k] >= 0
                   for k in ROUND_COUNTERS):
            errors.append(f"{where}: counters {a} not whole numbers "
                          f"for each of {ROUND_COUNTERS}")
            continue
        names = below.get(sid, [])
        if a["chunks"] != sum(n in PREFILL_DISPATCHES for n in names):
            errors.append(f"{where}: chunks={a['chunks']} but "
                          f"{sum(n in PREFILL_DISPATCHES for n in names)} "
                          f"prefill dispatches below it")
        if (a["decode_slots"] > 0) != any(n in STEP_SPANS for n in names):
            errors.append(f"{where}: decode_slots={a['decode_slots']} "
                          f"disagrees with its decode/verify steps")
    return errors


def check_trace(path: str) -> list:
    errors = []
    spans: dict = {}
    by_rid: dict = {}
    last_id = -1
    for i, line in enumerate(Path(path).read_text().splitlines(), 1):
        try:
            rec = json.loads(line)
        except json.JSONDecodeError as e:
            errors.append(f"{path}:{i}: not JSON: {e}")
            continue
        t = rec.get("type")
        if t == "meta":
            continue
        want = SPAN_KEYS if t == "span" else EVENT_KEYS
        if t not in ("span", "event") or set(rec) != want:
            errors.append(f"{path}:{i}: bad record shape: {sorted(rec)}")
            continue
        if rec["id"] <= last_id:
            errors.append(f"{path}:{i}: ids not strictly increasing")
        last_id = rec["id"]
        if rec["parent"] is not None and rec["parent"] not in spans:
            errors.append(f"{path}:{i}: parent {rec['parent']} not an "
                          f"earlier span")
        if t == "span":
            if rec["t1"] is None or rec["t1"] < rec["t0"]:
                errors.append(f"{path}:{i}: span {rec['name']}#{rec['id']} "
                              f"not closed or negative ({rec['t1']})")
            spans[rec["id"]] = rec
        if rec["rid"] is not None:
            by_rid.setdefault(rec["rid"], {}).setdefault(
                rec["name"], []).append(rec)
    if not by_rid:
        errors.append(f"{path}: no per-request records at all")
    errors += check_lexical(path, spans)
    for rid, names in sorted(by_rid.items()):
        if "request" not in names or "queued" not in names:
            errors.append(f"{path}: rid {rid}: missing request/queued span")
            continue
        status = names["request"][0]["attrs"].get("status")
        if status not in ("done", "expired"):
            errors.append(f"{path}: rid {rid}: bad terminal status {status!r}")
        if status == "done":
            for name in ("prefill", "first_token", "decode"):
                if name not in names:
                    errors.append(f"{path}: rid {rid}: served request "
                                  f"missing {name}")
    rounds = sum(r["rid"] is None and r["name"] == "serve.round"
                 for r in spans.values())
    print(f"{path}: {last_id + 1} records, {len(by_rid)} requests, "
          f"{rounds} rounds ok")
    return errors


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--prom", action="append", default=[],
                    help="Prometheus text dump(s) to validate")
    ap.add_argument("--json", action="append", default=[], dest="json_",
                    help="JSON metrics snapshot(s) to validate")
    ap.add_argument("--trace", action="append", default=[],
                    help="JSONL trace file(s) to validate")
    args = ap.parse_args()
    if not (args.prom or args.json_ or args.trace):
        ap.error("nothing to check: pass --prom/--json/--trace")
    errors = []
    for p in args.prom:
        errors += check_prom(p)
    for p in args.json_:
        errors += check_json(p)
    for p in args.trace:
        errors += check_trace(p)
    if errors:
        print("\nFAIL:")
        for e in errors:
            print(f"  {e}")
        return 1
    print("obs artifacts OK")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
