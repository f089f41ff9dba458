"""Fleet timeline: merge any set of run-trace JSONL streams into one.

A fleet run leaves N per-rank engine traces plus ``fleet.jsonl``; a
service run leaves ``service.jsonl`` plus per-job ``trace.jsonl`` /
``flight.jsonl`` artifacts. Each stream's event timestamps (``t``) are
seconds since *that trace object* was created — useless for joining
streams until the correlation header landed (PR 14): engine streams
stamp ``run_id`` / ``t0_unix`` / ``host`` / ``rank`` (and ``job`` /
``lane``) on ``run_start``, service/fleet streams carry the same
fields on a ``trace_header`` event. This module does the join:

* :func:`read_segments` splits one JSONL file into SEGMENTS — a fresh
  header starts a new segment (a resumed job appends a second run to
  the same ``trace.jsonl``; a restarted scheduler appends to
  ``service.jsonl``) — each carrying its identity and wall anchor;
* :func:`merge` flattens any set of files/segments into ONE timeline:
  every event annotated with its absolute ``wall`` time
  (``t0_unix + t``), the run-relative ``fleet_t`` (seconds since the
  earliest anchored event), and its resolved ``run_id`` / ``host`` /
  ``rank`` / ``job`` / ``lane``; events duplicated across streams of
  the same run (``flight.jsonl`` is a bounded subset of
  ``trace.jsonl``) are dropped once;
* ordering is by wall clock, which is CAUSAL only up to cross-host
  clock skew: the timeline carries ``skew_bound_s`` — the largest
  ``dcn_probe`` round trip any merged ``mesh_init`` observed — as the
  bound below which two events on different hosts are concurrent, not
  ordered (same-host/same-stream order is exact: one clock).

Anchor fallbacks, in order: a header's ``t0_unix``; else a
``run_start``'s legacy ``wall`` field minus its ``t`` (pre-PR-14
artifacts); else the segment is UNANCHORED — merged at relative time
with ``anchored=False`` so a consumer sees the gap instead of a
silently fabricated position.

``tools/trace_report.py --fleet`` renders the merged timeline as
per-host / per-job swimlanes with interventions inline.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, Iterable, List, Optional

#: events that (re)anchor a stream segment
_HEADER_EVENTS = ("run_start", "trace_header")

#: event kinds that count as interventions on the swimlane render
INTERVENTIONS = {
    "grow": "G", "hgrow": "G", "egrow": "G", "kovf": "K",
    "compile": "c", "retry": "R", "watchdog": "W", "autosave": "a",
    "failover": "F", "degrade": "D", "spill": "S", "evict": "S",
    "pause": "P", "recorder_dump": "!",
    "fused_unsupported": "f", "crash": "C", "restart": "C",
    "partition": "C", "violation": "V", "burnin_preempt": "B",
    "host_drop": "H", "mesh_init": "M",
    "host_join": "M", "job_submit": "j", "job_grant": "j",
    "job_start": "J", "job_first_chunk": "j", "job_pause": "P",
    "job_resume": "J", "job_done": "J", "bucket_flush": "b",
    "batch_form": "b", "lane_retire": "b", "error": "E",
    "discovery": "*",
}


class Segment:
    """One contiguous identity span of a JSONL stream."""

    __slots__ = ("src", "engine", "run_id", "t0_unix", "host", "rank",
                 "job", "lane", "anchored", "events")

    def __init__(self, src: str, first_event: Dict[str, Any]):
        self.src = src
        self.engine = first_event.get("engine", "?")
        self.run_id: Optional[str] = None
        self.t0_unix: Optional[float] = None
        self.host = None
        self.rank = None
        self.job = None
        self.lane = None
        self.anchored = False
        self.events: List[Dict[str, Any]] = []

    def adopt_header(self, ev: Dict[str, Any]) -> None:
        self.engine = ev.get("engine", self.engine)
        self.run_id = ev.get("run_id")
        self.host = ev.get("host")
        self.rank = ev.get("rank")
        self.job = ev.get("job")
        self.lane = ev.get("lane")
        t0 = ev.get("t0_unix")
        if t0 is None and ev.get("ev") == "run_start" \
                and ev.get("wall") is not None:
            # pre-header artifact: the run_start's emit-time wall clock
            # minus its relative t recovers the stream anchor
            t0 = float(ev["wall"]) - float(ev.get("t", 0.0))
        if t0 is not None:
            self.t0_unix = float(t0)
            self.anchored = True

    def label(self) -> str:
        """The swimlane key: a job when one owns the stream, else the
        host/rank of the emitting process, else the engine name."""
        if self.job is not None:
            return f"job:{self.job}"
        if self.rank is not None:
            return f"{self.host}/r{self.rank}:{self.engine}"
        return f"{self.engine}:{self.run_id or os.path.basename(self.src)}"


def read_segments(path) -> List[Segment]:
    """Split one JSONL trace file into identity segments. Junk lines
    (a partially-written tail) are skipped, never fatal — aggregation
    is a postmortem tool and must read what survived."""
    path = os.fspath(path)
    segments: List[Segment] = []
    current: Optional[Segment] = None
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                ev = json.loads(line)
            except json.JSONDecodeError:
                continue
            if not isinstance(ev, dict) or "ev" not in ev:
                continue
            if ev["ev"] in _HEADER_EVENTS or current is None:
                current = Segment(path, ev)
                if ev["ev"] in _HEADER_EVENTS:
                    current.adopt_header(ev)
                segments.append(current)
            current.events.append(ev)
    for seg in segments:
        if seg.run_id is None:
            # pre-header stream: synthesize a stable id from the file
            # so the event is still resolvable to its source
            seg.run_id = f"anon:{os.path.basename(seg.src)}"
    return segments


#: artifact filenames collect_artifacts looks for, at a root and in
#: job/rank subdirectories (the obs/artifacts.py + service layouts)
_ARTIFACT_NAMES = ("fleet.jsonl", "service.jsonl", "trace.jsonl",
                   "flight.jsonl")


def collect_artifacts(root) -> List[str]:
    """Every trace artifact under a run/service/fleet directory: the
    root's own streams plus one level of subdirectories (the service's
    per-job dirs, a fleet's per-rank outputs)."""
    root = os.fspath(root)
    found: List[str] = []
    for name in _ARTIFACT_NAMES:
        path = os.path.join(root, name)
        if os.path.isfile(path):
            found.append(path)
    try:
        entries = sorted(os.listdir(root))
    except OSError:
        entries = []
    for entry in entries:
        sub = os.path.join(root, entry)
        if not os.path.isdir(sub):
            continue
        for name in _ARTIFACT_NAMES:
            path = os.path.join(sub, name)
            if os.path.isfile(path):
                found.append(path)
    return found


class FleetTimeline:
    """The merged, annotated, wall-ordered event list."""

    def __init__(self, events: List[Dict[str, Any]],
                 segments: List[Segment], t0_wall: Optional[float],
                 skew_bound_s: float):
        self.events = events
        self.segments = segments
        self.t0_wall = t0_wall
        self.skew_bound_s = skew_bound_s

    @property
    def span_s(self) -> float:
        anchored = [e["fleet_t"] for e in self.events
                    if e.get("anchored")]
        return max(anchored) - min(anchored) if anchored else 0.0

    def lanes(self) -> List[str]:
        seen: List[str] = []
        for ev in self.events:
            lane = ev["lane_key"]
            if lane not in seen:
                seen.append(lane)
        return seen


def merge(sources: Iterable) -> FleetTimeline:
    """Merge files, directories, or pre-read segments into one
    timeline (directories expand via :func:`collect_artifacts`)."""
    segments: List[Segment] = []
    for src in sources:
        if isinstance(src, Segment):
            segments.append(src)
        elif os.path.isdir(os.fspath(src)):
            for path in collect_artifacts(src):
                segments.extend(read_segments(path))
        else:
            segments.extend(read_segments(src))

    anchors = [s.t0_unix for s in segments if s.anchored]
    t0_wall = min(anchors) if anchors else None
    skew = 0.0
    merged: List[Dict[str, Any]] = []
    seen: set = set()
    for seg in segments:
        for ev in seg.events:
            t = float(ev.get("t", 0.0))
            # exact-duplicate suppression: flight.jsonl replays a
            # bounded window of its run's trace.jsonl — one copy wins
            key = (seg.run_id,
                   json.dumps(ev, sort_keys=True, default=str))
            if key in seen:
                continue
            seen.add(key)
            if ev.get("ev") == "mesh_init" \
                    and ev.get("dcn_exchange_s"):
                skew = max(skew, float(ev["dcn_exchange_s"]))
            out = dict(ev)
            out["run_id"] = seg.run_id
            out["src"] = seg.src
            out["anchored"] = seg.anchored
            if seg.anchored:
                out["wall"] = seg.t0_unix + t
                out["fleet_t"] = round(
                    out["wall"] - (t0_wall if t0_wall is not None
                                   else seg.t0_unix), 6)
            else:
                out["wall"] = None
                out["fleet_t"] = round(t, 6)
            # identity resolution: the segment header wins; service
            # streams name the job per event instead
            out.setdefault("host", seg.host)
            out.setdefault("rank", seg.rank)
            job = ev.get("job", seg.job)
            if job is not None:
                out["job"] = job
            if seg.lane is not None:
                out.setdefault("lane", seg.lane)
            out["lane_key"] = (f"job:{job}" if job is not None
                               else seg.label())
            merged.append(out)
    merged.sort(key=lambda e: (0 if e["anchored"] else 1,
                               e["wall"] if e["anchored"]
                               else e["fleet_t"]))
    return FleetTimeline(merged, segments, t0_wall, skew)
