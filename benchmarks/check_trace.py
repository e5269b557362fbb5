#!/usr/bin/env python
"""CI validator for --trace-out artifacts (DESIGN.md §15.5).

Checks the exact export shape `obs/export.py` promises: a
Perfetto-loadable Chrome trace-event object with named processes and
threads, positive-duration X events, flow events carrying string ids,
plus the two repo-specific keys — `reproCounters` (registry snapshot)
and `reproIdle` (idle attribution, whose tick-track buckets must sum to
ticks − busy EXACTLY and must be NON-EMPTY: a trace with no idle report
means the driver exported before attribution ran).

    python benchmarks/check_trace.py /tmp/trace.json \
        --expect-track g0 --expect-track chaos --expect-span prefill

Exits non-zero with one line per violation.
"""

import argparse
import json
import sys

IDLE_BUCKETS = ("queue-starved", "pool-OOM", "transfer-wait", "drain",
                "fault-stall")


def check(obj, expect_tracks=(), expect_spans=(), min_events=1):
    errs = []
    ev = obj.get("traceEvents")
    if not isinstance(ev, list) or len(ev) < min_events:
        return [f"traceEvents missing or < {min_events} events"]

    tracks = set()
    span_names = set()
    procs = set()
    for e in ev:
        ph = e.get("ph")
        if ph is None or "pid" not in e:
            errs.append(f"event without ph/pid: {e}")
            continue
        if ph == "M":
            if e["name"] == "thread_name":
                tracks.add(e["args"]["name"])
            elif e["name"] == "process_name":
                procs.add(e["args"]["name"])
        elif ph == "X":
            span_names.add(e["name"])
            if not (isinstance(e.get("dur"), (int, float)) and e["dur"] > 0):
                errs.append(f"X event with non-positive dur: {e['name']}")
            if "ts" not in e:
                errs.append(f"X event without ts: {e['name']}")
        elif ph in ("s", "t", "f"):
            if not isinstance(e.get("id"), str):
                errs.append(f"flow event with non-string id: {e}")
    if not procs:
        errs.append("no process_name metadata")
    if not tracks:
        errs.append("no thread_name metadata")
    for t in expect_tracks:
        if t not in tracks:
            errs.append(f"expected track {t!r} missing (have {sorted(tracks)})")
    for s in expect_spans:
        if s not in span_names:
            errs.append(f"expected span {s!r} missing "
                        f"(have {sorted(span_names)})")

    if not isinstance(obj.get("reproCounters"), dict):
        errs.append("reproCounters missing or not a dict")
    idle = obj.get("reproIdle")
    if not isinstance(idle, dict) or not idle:
        errs.append("reproIdle missing or EMPTY — idle attribution never ran")
        return errs
    for track, r in idle.items():
        if r.get("kind") == "tick":
            if set(r["buckets"]) - set(IDLE_BUCKETS):
                errs.append(f"{track}: unknown idle bucket(s) "
                            f"{set(r['buckets']) - set(IDLE_BUCKETS)}")
            if sum(r["buckets"].values()) != r["idle"] \
                    or r["idle"] != r["ticks"] - r["busy"]:
                errs.append(f"{track}: idle identity broken — "
                            f"sum(buckets)={sum(r['buckets'].values())} "
                            f"idle={r['idle']} ticks={r['ticks']} "
                            f"busy={r['busy']}")
        else:
            errs.append(f"{track}: unknown report kind {r.get('kind')!r}")
    return errs


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("trace", help="path to a --trace-out JSON artifact")
    ap.add_argument("--expect-track", action="append", default=[],
                    help="thread name that must exist (repeatable)")
    ap.add_argument("--expect-span", action="append", default=[],
                    help="X-event name that must exist (repeatable)")
    ap.add_argument("--min-events", type=int, default=1)
    args = ap.parse_args(argv)

    with open(args.trace) as f:
        obj = json.load(f)
    errs = check(obj, expect_tracks=args.expect_track,
                 expect_spans=args.expect_span, min_events=args.min_events)
    if errs:
        for e in errs:
            print(f"[check_trace] FAIL: {e}", file=sys.stderr)
        return 1
    idle = obj["reproIdle"]
    print(f"[check_trace] OK: {len(obj['traceEvents'])} events, "
          f"{len(idle)} idle-attributed tracks "
          f"({', '.join(sorted(idle))})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
