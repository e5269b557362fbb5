"""Idle-time attribution (§15): where did each group's ticks go?

HeterMoE's metric of merit is GPU idle time; this report walks a tick-clock
tracer's span timeline per track and accounts for every tick the track was
NOT inside a busy span, bucketed into the §15 idle taxonomy:

    queue-starved   nothing to run (empty queue)
    pool-OOM        work exists but the page pool cannot back it
    transfer-wait   decode group waiting on an inbound KV migration
    drain           group is draining toward a role flip / shutdown
    fault-stall     dead, stalled, or quarantined by a fault

Each tick in [0, ticks) is either busy (>= 1 span touched it) or idle; idle
ticks take the bucket of the ``mark_idle`` instant the engine emitted at
that tick, else default to queue-starved. Exactly one classification per
tick, so per track ``sum(buckets.values()) == ticks - busy`` EXACTLY — the
report can never under- or over-account (tests assert the identity).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple


def _span_ticks(tracer, track: str) -> List[Tuple[int, int]]:
    """(first tick, last tick) of each span on ``track``, pairing E events
    with their B via the explicit parent eid. A dangling open (a crash
    mid-span) still counts for the tick it opened in."""
    opens: Dict[int, object] = {}
    out = []
    for ev in tracer.events:
        if ev.track != track:
            continue
        if ev.ph == "B":
            opens[ev.eid] = ev
        elif ev.ph == "E" and ev.parent in opens:
            out.append((opens.pop(ev.parent).tick, ev.tick))
    out += [(b.tick, b.tick) for b in opens.values()]
    return out


def _tick_track(tracer, track: str, ticks: int) -> dict:
    busy_ticks = set()
    for k0, k1 in _span_ticks(tracer, track):
        busy_ticks.update(range(k0, k1 + 1))
    busy_ticks = {t for t in busy_ticks if t < ticks}
    marks: Dict[int, str] = {}
    for ev in tracer.events:
        if ev.track == track and ev.ph == "i" and ev.name == "idle":
            marks[ev.tick] = ev.args.get("bucket", "queue-starved")
    buckets: Dict[str, int] = {}
    for t in range(ticks):
        if t in busy_ticks:
            continue
        b = marks.get(t, "queue-starved")
        buckets[b] = buckets.get(b, 0) + 1
    return {"kind": "tick", "ticks": ticks, "busy": len(busy_ticks),
            "idle": ticks - len(busy_ticks), "buckets": buckets}


def idle_report(tracer, ticks: Optional[int] = None) -> dict:
    """Per-track idle attribution. ``ticks`` overrides the tick horizon
    for tick tracks (default: tracer.max_tick + 1 — the number of ticks
    the clock actually advanced through). Returns
    ``{track: {kind, ticks, busy, idle, buckets}}``; "meta" tracks (control
    plane: chaos, router) are excluded."""
    if not getattr(tracer, "enabled", False):
        return {}
    n_ticks = ticks if ticks is not None else tracer.max_tick + 1
    return {track: _tick_track(tracer, track, n_ticks)
            for track, meta in tracer.tracks.items()
            if meta["kind"] == "tick"}


def format_report(report: dict) -> str:
    """Human-readable one-line-per-track summary for the launch drivers."""
    lines = []
    for track in sorted(report):
        r = report[track]
        bk = " ".join(f"{k}={v}" for k, v in sorted(r["buckets"].items()))
        lines.append(f"  {track:<12} ticks={r['ticks']} busy={r['busy']} "
                     f"idle={r['idle']}" + (f" [{bk}]" if bk else ""))
    return "\n".join(lines)
