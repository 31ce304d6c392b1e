#!/usr/bin/env python
"""Diff a bench session against the recorded trajectory.

A bench session log holds one JSON line per ``bench.py`` run;
``BENCH_HISTORY.json`` holds the best recorded accelerator number
per metric. This tool answers the question every post-session review
asks — *which metrics moved, and which rows are even comparable* — in
one pass:

- the NEWEST row per metric wins (a session that re-runs bert_base
  after pallas_tune diffs the tuned number);
- degraded rows are EXCLUDED, never diffed: ``backend_degraded`` and
  skipped rows (``skipped`` / ``cause``) — a row that measured nothing
  must never pollute on-chip deltas — matching the exclusion the
  regression sentinel applies;
- per-metric delta vs the history baseline (``metric`` key, then the
  ``metric@...`` variant tiers evaluate_against_history records under),
  higher-is-better (history keeps the max);
- exit 1 when any metric regressed past ``--threshold`` (default 10%,
  the recording contract's band) so a session wrap-up can gate on it.

Usage::

    python tools/bench_diff.py [session.log|-] [--history PATH]
        [--threshold 0.10] [--format text|json]

The positional default is ``bench_session.log`` in the repo root; ``-``
reads stdin. Non-JSON log lines are skipped.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Any, Dict, List, Optional

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def parse_lines(text: str) -> Dict[str, Dict[str, Any]]:
    """Newest bench row per metric from a session log (non-JSON lines
    and JSON lines without a metric/value shape are skipped)."""
    rows: Dict[str, Dict[str, Any]] = {}
    for line in text.splitlines():
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            row = json.loads(line)
        except ValueError:
            continue
        if isinstance(row, dict) and "metric" in row:
            rows[str(row["metric"])] = row  # last one wins
    return rows


def exclude_reason(row: Dict[str, Any]) -> Optional[str]:
    """Why this row must not be diffed (None = comparable)."""
    if row.get("backend_degraded"):
        return "backend_degraded"
    if row.get("skipped"):
        return f"skipped:{row.get('cause', 'unknown')}"
    if row.get("error"):
        return "error"
    if not isinstance(row.get("value"), (int, float)):
        return "no_value"
    return None


def baseline_for(metric: str, history: Dict[str, Any]
                 ) -> Optional[float]:
    """Best recorded value for ``metric``: the bare key first, else the
    best among its ``metric@...`` variant tiers (a sweep-only metric
    has no headline entry but still has a trajectory)."""
    def value_of(entry):
        if isinstance(entry, dict):
            v = entry.get("value")
            return float(v) if isinstance(v, (int, float)) else None
        return float(entry) if isinstance(entry, (int, float)) else None

    v = value_of(history.get(metric))
    if v is not None:
        return v
    variants = [value_of(e) for k, e in history.items()
                if k.startswith(f"{metric}@")]
    variants = [x for x in variants if x is not None]
    return max(variants) if variants else None


def diff(rows: Dict[str, Dict[str, Any]], history: Dict[str, Any],
         threshold: float) -> Dict[str, Any]:
    compared: List[Dict[str, Any]] = []
    excluded: List[Dict[str, Any]] = []
    fresh: List[str] = []
    for metric in sorted(rows):
        row = rows[metric]
        reason = exclude_reason(row)
        if reason is not None:
            excluded.append({"metric": metric, "reason": reason})
            continue
        base = baseline_for(metric, history)
        if base is None:
            fresh.append(metric)
            continue
        value = float(row["value"])
        delta = (value - base) / base if base else 0.0
        compared.append({
            "metric": metric, "value": value, "baseline": base,
            "unit": row.get("unit"), "delta_pct": round(delta * 100, 2),
            "regressed": delta < -threshold})
    return {"compared": compared, "excluded": excluded, "new": fresh,
            "regressions": [c["metric"] for c in compared
                            if c["regressed"]],
            "threshold_pct": round(threshold * 100, 2)}


def render(report: Dict[str, Any]) -> str:
    lines = []
    for c in report["compared"]:
        mark = " <-- REGRESSED" if c["regressed"] else ""
        lines.append(
            f"  {c['metric']}: {c['value']:.2f} vs {c['baseline']:.2f} "
            f"{c.get('unit') or ''} ({c['delta_pct']:+.2f}%){mark}")
    for e in report["excluded"]:
        lines.append(f"  {e['metric']}: EXCLUDED ({e['reason']})")
    for m in report["new"]:
        lines.append(f"  {m}: new metric (no recorded baseline)")
    lines.append(
        f"{len(report['compared'])} compared, "
        f"{len(report['excluded'])} excluded, "
        f"{len(report['new'])} new; "
        f"{len(report['regressions'])} regression(s) past "
        f"{report['threshold_pct']}%")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("session", nargs="?",
                    default=os.path.join(REPO, "bench_session.log"),
                    help="bench session log of JSON lines, or - for "
                         "stdin (default: bench_session.log)")
    ap.add_argument("--history",
                    default=os.path.join(REPO, "BENCH_HISTORY.json"))
    ap.add_argument("--threshold", type=float, default=0.10,
                    help="regression band as a fraction (default 0.10)")
    ap.add_argument("--format", choices=("text", "json"),
                    default="text")
    args = ap.parse_args(argv)

    if args.session == "-":
        text = sys.stdin.read()
    else:
        if not os.path.exists(args.session):
            print(f"bench_diff: no session log at {args.session}",
                  file=sys.stderr)
            return 2
        with open(args.session, encoding="utf-8") as f:
            text = f.read()
    history: Dict[str, Any] = {}
    if os.path.exists(args.history):
        try:
            with open(args.history, encoding="utf-8") as f:
                history = json.load(f)
        except ValueError:
            print(f"bench_diff: unreadable history {args.history}",
                  file=sys.stderr)
            return 2

    report = diff(parse_lines(text), history, args.threshold)
    if args.format == "json":
        print(json.dumps(report, indent=1))
    else:
        print(render(report))
    return 1 if report["regressions"] else 0


if __name__ == "__main__":
    sys.exit(main())
