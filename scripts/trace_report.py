#!/usr/bin/env python3
"""Trace analyzer: per-ticket latency breakdown from a JSONL span trace.

Reads a trace written by ``serve.py --trace-out out.jsonl`` (or any
:mod:`repro.obs.trace` JSONL export), schema-validates it, and prints

- the per-ticket latency breakdown — for every ticket, time (virtual
  seconds) from submit to final, split by phase (queue wait, plan share,
  scan/dispatch, stream delivery) plus the outcome, cache tier and the
  adopting owner when the ticket was served by lease adoption;
- a fleet-events section counting the failure-policy and single-flight
  vocabulary per front-end: ``policy_transition`` (by edge),
  ``rereplicate`` (copies), ``lease_adopt`` and ``lease_fallback``;
- the top-N slowest packets with their grid node, brick and size (the
  straggler view the paper's operators would start from), and the wall
  milliseconds of each packet's host phases: ``stage``, ``launch``,
  ``wait`` (SPMD kernel chunks) and ``merge`` (streamed windows).

Usage::

    python scripts/trace_report.py trace.jsonl [--top 10] [--tickets 20]

Exits non-zero when the trace fails schema validation (leaked open
spans, dangling parents, bad fields) so CI can gate on it.
"""
from __future__ import annotations

import argparse
import pathlib
import sys
from collections import defaultdict

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))

from repro.obs import trace as trace_lib  # noqa: E402


def span_dur(rec) -> float:
    t1 = rec.get("t1_virtual")
    return 0.0 if t1 is None else max(0.0, float(t1) - rec["t0_virtual"])


def ticket_breakdown(records):
    """Per-ticket phase timings: submit span, the window that served it,
    and its final event, keyed off the span taxonomy."""
    by_ticket = defaultdict(dict)
    windows = {}  # (process, span_id) -> window record
    children = defaultdict(list)  # (process, parent_id) -> records
    for rec in records:
        if rec["parent_id"] is not None:
            children[(rec["process"], rec["parent_id"])].append(rec)
        if rec["name"] == "window":
            windows[(rec["process"], rec["span_id"])] = rec
    for rec in records:
        t = rec["ticket"]
        if t is None:
            continue
        # ticket ids are per-front-end, so key on (process, ticket)
        info = by_ticket[(rec["process"], t)]
        if rec["name"] == "submit":
            info["submit"] = rec
        elif rec["name"] == "final":
            info["final"] = rec
        elif rec["name"] == "stream":
            info["stream"] = rec
        elif rec["name"] == "lease_adopt":
            info["adopt"] = rec
    rows = []
    # ticket keys may mix ints and lease-key strings: ints sort first
    order = lambda kv: (kv[0][0], isinstance(kv[0][1], str), str(kv[0][1]))
    for (_, t), info in sorted(by_ticket.items(), key=order):
        sub, fin = info.get("submit"), info.get("final")
        if sub is None:
            continue
        adopt = info.get("adopt")
        row = {
            "ticket": t,
            "process": sub["process"],
            "status": sub["status"],
            "cache_tier": sub["attrs"].get("cache_tier", "-"),
            "adopted_from": ("-" if adopt is None
                             else str(adopt["attrs"].get("owner", "?"))),
            "outcome": (fin or {}).get("attrs", {}).get("outcome", "-"),
            "submit_t": sub["t0_virtual"],
            "final_t": None if fin is None else fin["t0_virtual"],
            "total": None,
            "queue_wait": None,
            "plan": 0.0,
            "scan": 0.0,
        }
        if fin is not None:
            row["total"] = max(0.0, fin["t0_virtual"] - sub["t0_virtual"])
            batch = fin["attrs"].get("batch")
            # find the window that served this ticket and split its time
            for (proc, _), w in windows.items():
                if proc != sub["process"] or \
                        w["attrs"].get("batch") != batch or batch is None:
                    continue
                row["queue_wait"] = max(
                    0.0, w["t0_virtual"] - sub["t0_virtual"])
                for kid in children[(proc, w["span_id"])]:
                    if kid["name"] == "plan":
                        row["plan"] += span_dur(kid)
                    elif kid["name"] == "dispatch":
                        row["scan"] += span_dur(kid)
                break
        rows.append(row)
    return rows


#: a packet's host phases, as its child spans name them
PHASES = ("stage", "launch", "wait", "merge")


def wall_dur(rec) -> float:
    t1 = rec.get("t1_wall")
    return 0.0 if t1 is None else max(0.0, float(t1) - rec["t0_wall"])


def slowest_packets(records, top):
    """The ``top`` longest packets, each with the wall seconds of its
    host phases (``PHASES``) summed from its child spans."""
    pkts = [r for r in records if r["name"] == "packet"]
    pkts.sort(key=span_dur, reverse=True)
    pkts = pkts[:top]
    phases = {(p["process"], p["span_id"]): dict.fromkeys(PHASES, 0.0)
              for p in pkts}
    for rec in records:
        got = phases.get((rec["process"], rec["parent_id"]))
        if got is not None and rec["name"] in got:
            got[rec["name"]] += wall_dur(rec)
    return [(p, phases[(p["process"], p["span_id"])]) for p in pkts]


def fleet_events(records):
    """Per-process counts of the failure-policy / single-flight events:
    ``policy_transition`` edges, ``rereplicate`` copy totals, and lease
    adoption/fallback occurrences."""
    counts = defaultdict(lambda: defaultdict(int))
    for rec in records:
        name, a = rec["name"], rec.get("attrs", {})
        if name == "policy_transition":
            counts[rec["process"]][
                f"policy {a.get('old')}->{a.get('new')}"] += 1
        elif name == "rereplicate":
            counts[rec["process"]]["rereplicate copies"] += int(
                a.get("copies", 0))
        elif name == "lease_adopt":
            counts[rec["process"]]["lease adopts"] += 1
        elif name == "lease_fallback":
            counts[rec["process"]]["lease fallbacks"] += 1
    return counts


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("trace", help="JSONL trace file (serve.py --trace-out)")
    ap.add_argument("--top", type=int, default=10,
                    help="slowest packets to show")
    ap.add_argument("--tickets", type=int, default=20,
                    help="max tickets to list")
    args = ap.parse_args(argv)

    records = trace_lib.load_jsonl(args.trace)
    problems = trace_lib.validate_records(records)
    if problems:
        print(f"TRACE INVALID: {len(problems)} problem(s)")
        for p in problems[:20]:
            print("  -", p)
        return 1
    print(f"{args.trace}: {len(records)} records, schema ok")

    rows = ticket_breakdown(records)
    print(f"\nper-ticket latency (virtual seconds), "
          f"{min(len(rows), args.tickets)}/{len(rows)} tickets:")
    hdr = (f"{'ticket':>6} {'fe':>5} {'outcome':>8} {'tier':>4} "
           f"{'adopt':>6} {'total':>9} {'queued':>9} {'plan':>9} "
           f"{'scan':>9}")
    print(hdr)
    for row in rows[:args.tickets]:
        fmt = lambda v: "-" if v is None else f"{v:9.4f}"
        print(f"{str(row['ticket']):>6} {row['process']:>5} "
              f"{row['outcome']:>8} {row['cache_tier']:>4} "
              f"{row['adopted_from']:>6} "
              f"{fmt(row['total']):>9} {fmt(row['queue_wait']):>9} "
              f"{row['plan']:9.4f} {row['scan']:9.4f}")

    events = fleet_events(records)
    if events:
        print("\nfleet events (policy / leases):")
        for proc in sorted(events):
            for what in sorted(events[proc]):
                print(f"  {proc:>5} {what}: {events[proc][what]}")

    pkts = slowest_packets(records, args.top)
    if pkts:
        print(f"\ntop {len(pkts)} slowest packets "
              f"(phases in wall milliseconds):")
        print(f"{'dur_s':>9} {'fe':>5} {'node':>5} {'brick':>6} "
              f"{'events':>7}" + "".join(f" {n:>8}" for n in PHASES))
        for p, ph in pkts:
            a = p["attrs"]
            print(f"{span_dur(p):9.4f} {p['process']:>5} "
                  f"{a.get('node', '-'):>5} {a.get('brick', '-'):>6} "
                  f"{a.get('size', '-'):>7}"
                  + "".join(f" {1e3 * ph[n]:8.3f}" for n in PHASES))
    return 0


if __name__ == "__main__":
    sys.exit(main())
