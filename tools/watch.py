"""Live terminal console for a checking run.

Usage:
    python tools/watch.py TRACE.jsonl            # tail a growing trace
    python tools/watch.py TRACE.jsonl --once     # render + exit at EOF
    python tools/watch.py --url http://host:port # attach to an Explorer

Renders the run-trace event stream (``stateright_tpu.obs.EVENT_SCHEMA``)
as a scrolling console: per-chunk progress lines with unique-state
throughput, dedup hit-rate, table load factor, queue depth and the
device/transfer time split, plus one line per intervention — growth and
kovf resizes, compiles, the resilience layer's
retry/watchdog/autosave/failover/degrade events, flight-recorder
dumps, and the soak harness's live
crash/restart/partition injections — and the discovery/done/error
endings.

Three attachment modes, one renderer:

* **tail mode** (a path): follows a growing JSONL file the way
  ``tail -f`` would, rendering each event as it lands; with ``--once``
  it renders the current contents and exits (postmortem reading);
* **Explorer mode** (``--url``): consumes ``GET /.events`` — the SSE
  stream replays the flight-recorder backlog first, so attaching late
  still shows the run so far;
* **in-process mode** (:func:`attach`): subscribes a console directly
  to a live checker's trace — the programmatic twin the tests (and
  notebooks) use: ``watch.attach(checker)`` blocks rendering until the
  run completes.
"""

from __future__ import annotations

import json
import os
import sys
import time
from typing import Any, Dict, Iterable, Optional

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

#: event kinds rendered as one-line interventions (everything that is
#: not periodic progress); unknown kinds also land here so a consumer
#: never silently swallows a new event type
_PROGRESS = ("chunk", "level", "progress")
_QUIET = ("run_start", "done", "error", "discovery", "ops")


class Console:
    """Stateful event-stream renderer: feed() it event dicts in order.

    ``interval`` throttles progress lines (seconds between renders;
    0 renders every progress event — what the tests use for
    determinism). Throughput is computed from the trace's own
    timestamps, so replaying a recorded file shows the run's real
    rates, not the replay speed."""

    def __init__(self, out=None, interval: float = 0.0):
        self.out = sys.stdout if out is None else out
        self.interval = interval
        self._last_render_t: Optional[float] = None
        self._last_unique = 0
        self._last_t = 0.0
        self._dev_total = 0.0
        self._xfer_total = 0.0
        # recent span events (obs/spans.py): bounded, folded into a
        # live top-stall fragment on progress lines rather than
        # rendered per-event (spans arrive several per chunk)
        from collections import deque
        self._spans: "deque" = deque(maxlen=512)
        self.rendered_progress = 0
        self.rendered_events = 0

    # --- rendering helpers ---------------------------------------------
    def _w(self, line: str) -> None:
        self.out.write(line + "\n")
        try:
            self.out.flush()
        except (ValueError, OSError):
            pass

    @staticmethod
    def _rate(n: float) -> str:
        if n >= 1e6:
            return f"{n / 1e6:.2f}M"
        if n >= 1e3:
            return f"{n / 1e3:.1f}k"
        return f"{n:.0f}"

    def _progress_line(self, ev: Dict[str, Any]) -> None:
        t = float(ev.get("t", 0.0))
        unique = ev.get("unique")
        parts = [f"t={t:8.2f}s"]
        if unique is not None:
            dt = max(t - self._last_t, 1e-9)
            rate = (unique - self._last_unique) / dt
            parts.append(f"uniq={unique:>10,}")
            parts.append(f"({self._rate(rate):>9} uniq/s)")
            self._last_unique, self._last_t = unique, t
        if "dedup_hit" in ev:
            parts.append(f"dedup={ev['dedup_hit']:.3f}")
        if "load" in ev:
            parts.append(f"load={ev['load']:.3f}")
        if "q_size" in ev:
            parts.append(f"q={ev['q_size']:>8,}")
        if self._dev_total and t > 0:
            # device / stats-transfer seconds from the span events
            parts.append(f"dev={self._dev_total / t:4.0%}")
            parts.append(f"xfer={self._xfer_total / t:4.0%}")
        if "shard_q" in ev:
            parts.append(f"shards={len(ev['shard_q'])}")
        stall = self._top_stall()
        if stall:
            parts.append(stall)
        self._w(" ".join(parts))
        self.rendered_progress += 1

    def _top_stall(self) -> Optional[str]:
        """Live top-stall fragment from the recent span window: the
        largest NON-overlap attribution bucket (overlap is the
        pipeline working — not a stall) plus the bubble fraction."""
        if not self._spans:
            return None
        from stateright_tpu.obs import spans as spans_mod
        attr = spans_mod.analyze(self._spans)
        rows = [r for r in spans_mod.ranked(attr) if r[0] != "overlap"]
        if not rows:
            return f"stall=none bubble={attr['bubble_frac']:.0%}"
        name, _secs, share = rows[0]
        return (f"stall={name}:{share:.0%} "
                f"bubble={attr['bubble_frac']:.0%}")

    def _event_line(self, ev: Dict[str, Any]) -> None:
        detail = " ".join(
            f"{k}={v}" for k, v in ev.items()
            if k not in ("t", "ev", "engine"))
        self._w(f"t={float(ev.get('t', 0.0)):8.2f}s !! "
                f"{ev.get('ev', '?'):<14} {detail}")
        self.rendered_events += 1

    # --- the consumer entry point --------------------------------------
    def feed(self, ev: Dict[str, Any]) -> None:
        kind = ev.get("ev")
        if kind == "span":
            # accumulated for the progress lines' top-stall fragment
            # and device/transfer shares, never rendered per-event
            # (several land per chunk)
            self._spans.append(ev)
            name = ev.get("name")
            if name == "device":
                self._dev_total += float(ev["t1"]) - float(ev["t0"])
            elif name == "pull":
                self._xfer_total += float(ev["t1"]) - float(ev["t0"])
            return
        if kind in _PROGRESS:
            now = time.monotonic()
            if (self.interval and self._last_render_t is not None
                    and now - self._last_render_t < self.interval):
                # throttled; rates recompute from the trace timestamps
                # at the next rendered event, so nothing is lost
                return
            self._last_render_t = now
            self._progress_line(ev)
        elif kind == "run_start":
            self._w(f"== run_start model={ev.get('model')} "
                    f"engine={ev.get('engine')} "
                    f"properties={ev.get('properties')}")
        elif kind == "discovery":
            self._w(f"t={float(ev.get('t', 0.0)):8.2f}s ** discovered "
                    f"{ev.get('property')!r}")
        elif kind == "done":
            self._w(f"== done gen={ev.get('gen')} "
                    f"unique={ev.get('unique')} "
                    f"discoveries={ev.get('discoveries')}")
        elif kind == "error":
            self._w(f"== ERROR {ev.get('error')}")
        elif kind == "ops":
            self._w(f"t={float(ev.get('t', 0.0)):8.2f}s ops "
                    f"invoked={ev.get('op_invoke')} "
                    f"returned={ev.get('op_return')} "
                    f"timeouts={ev.get('op_timeouts')}")
        else:
            # growth/resize, resilience, recorder, soak faults —
            # and any future event kind: always visible
            self._event_line(ev)


# --- event sources ---------------------------------------------------------

def follow_file(path, follow: bool = True,
                poll: float = 0.2) -> Iterable[Dict[str, Any]]:
    """Yield events from a JSONL trace; with ``follow`` keep tailing
    the growing file (stop after a ``done``/``error`` event has been
    seen and the file stops growing)."""
    ended = False
    with open(path) as f:
        while True:
            line = f.readline()
            if line:
                line = line.strip()
                if not line:
                    continue
                try:
                    ev = json.loads(line)
                except json.JSONDecodeError:
                    continue  # a partially-written trailing line
                if ev.get("ev") in ("done", "error"):
                    ended = True
                yield ev
                continue
            if not follow or ended:
                return
            time.sleep(poll)


def follow_url(url: str, reconnect: bool = True, retries: int = 5,
               base_delay: float = 0.5, max_delay: float = 8.0,
               _sleep=time.sleep,
               _rng=None) -> Iterable[Dict[str, Any]]:
    """Yield events from an SSE stream (Explorer ``GET /.events`` or a
    service job's ``/events``), surviving dropped connections.

    A dropped connection (server restart, proxy timeout, network blip)
    used to END the console mid-run. Now the client reconnects with
    jittered exponential backoff (up to ``retries`` consecutive
    failures); the server replays its flight-ring backlog on
    reconnect, and a bounded already-seen window (sized past the
    flight ring, so the whole replay is coverable) suppresses events
    this generator already yielded — the console resumes exactly where
    it left off, without duplicated lines. The retry counter resets
    whenever a connection delivers events, so a long flaky run is
    bounded per-outage, not per-lifetime.

    Ends when a terminal ``done``/``error`` event has been seen and
    the stream closes, when a clean close delivers nothing new (a
    finished trace replay), or when ``retries`` consecutive attempts
    fail. ``_sleep``/``_rng`` are test seams."""
    import http.client
    import random
    import urllib.error
    import urllib.request

    from collections import deque

    rng = random.Random() if _rng is None else _rng
    stripped = url.rstrip("/")
    if not (stripped.endswith("/.events")
            or stripped.endswith("/events")):
        url = stripped + "/.events"
    seen: set = set()
    order: deque = deque()
    seen_limit = 4096  # > the flight ring bound: full replay coverage
    ended = False
    failures = 0
    while True:
        fresh = 0
        try:
            with urllib.request.urlopen(url) as resp:
                for raw in resp:
                    line = raw.decode("utf-8", "replace").strip()
                    if not line.startswith("data:"):
                        continue  # keep-alive / drop-count comments
                    payload = line[len("data:"):].strip()
                    try:
                        ev = json.loads(payload)
                    except json.JSONDecodeError:
                        continue
                    key = json.dumps(ev, sort_keys=True, default=str)
                    if key in seen:
                        continue  # reconnect backlog replay
                    seen.add(key)
                    order.append(key)
                    if len(order) > seen_limit:
                        seen.discard(order.popleft())
                    fresh += 1
                    failures = 0
                    if ev.get("ev") in ("done", "error"):
                        ended = True
                    yield ev
            # clean close: finished run/replay, or a server going away
            if ended or not reconnect or fresh == 0:
                return
        except (OSError, urllib.error.URLError,
                http.client.HTTPException):
            if ended or not reconnect:
                return
            failures += 1
            if failures > retries:
                return
        delay = min(max_delay, base_delay * (2 ** max(failures - 1, 0)))
        _sleep(delay * (0.5 + rng.random() / 2))  # jittered backoff


def attach(checker, out=None, interval: float = 0.0,
           poll: float = 0.05) -> Console:
    """Subscribe a :class:`Console` to a live checker and render until
    the run completes (in-process mode). Returns the console (its
    ``rendered_*`` counters are what the tests assert on)."""
    import queue as _queue

    console = Console(out=out, interval=interval)
    q: "_queue.Queue" = _queue.Queue()
    checker.subscribe(q.put)
    checker._start_background()
    while True:
        try:
            console.feed(q.get(timeout=poll))
        except _queue.Empty:
            if checker.is_done():
                break
    while True:  # drain what landed between the last get and is_done
        try:
            console.feed(q.get_nowait())
        except _queue.Empty:
            break
    return console


def main(argv) -> int:
    if not argv or argv[0] in ("-h", "--help"):
        print(__doc__)
        return 0
    once = "--once" in argv
    interval = 0.5
    if "--interval" in argv:
        interval = float(argv[argv.index("--interval") + 1])
    if "--url" in argv:
        source = follow_url(argv[argv.index("--url") + 1])
    else:
        paths = [a for a in argv if not a.startswith("--")]
        if not paths:
            print("watch.py: need a trace path or --url",
                  file=sys.stderr)
            return 2
        source = follow_file(paths[0], follow=not once)
    console = Console(interval=0.0 if once else interval)
    try:
        for ev in source:
            console.feed(ev)
    except KeyboardInterrupt:
        pass
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
