"""Overlap-aware span profiler: intervals, not durations.

Every host phase of the device engines is ONE call,
``SpanRecorder.span(name)``, which records the phase as an **interval**
``[t0, t1)`` on the run trace's clock, adds its seconds to the
``span_s.<name>`` timer of the run's metrics registry, emits the
``span`` trace event when a sink is set, and wraps the block in a
``jax.profiler.TraceAnnotation("sr.<name>")`` — so any ``jax.profiler``
trace (``tpu_options(profile_dir=...)``, a benchmark's traced run)
shows the phase on the same clock as the device ops. Intervals the
host only learns after the fact (``device``: dispatch to stats-ready;
``race.decide``) are recorded with :meth:`SpanRecorder.record` and stay
out of the profiler trace, which shows the device side itself.

* :class:`SpanRecorder` — bridges the engines' ``time.perf_counter()``
  stamps onto the trace clock (``RunTrace`` events use
  ``monotonic() - trace._t0``), keeps a bounded in-memory ring (so
  ``profile()`` works traceless), and emits a ``span`` trace event per
  interval when a sink is configured.
* :func:`analyze` — the overlap-aware critical-path sweep. Wall time
  splits into exclusively-attributed buckets that **sum to wall**:
  device-only-busy segments are device-bound (named by the innermost
  device span: ``device``/``pull``/``exchange``), host-busy-while-a-
  chunk-is-in-flight is ``overlap`` (free — the pipeline working as
  designed), host-busy-with-nothing-dispatched is the pipeline bubble
  (``host:<phase>``), and nothing-active is ``idle``. The bubble
  fraction (``host:*`` + ``idle`` over wall) is the number the next
  perf PR attacks. :func:`attach_attribution` sweeps the chunk loop
  only (:func:`loop_spans`); the entry and verdict phases around it
  report through their ``span_s.*`` timers.
* :func:`spans_from_events` / :func:`shard_imbalance` — the consumer
  side used by ``tools/stall_report.py``: extract spans from a JSONL
  event stream (optionally wall-anchored for merged fleet timelines)
  and summarize per-shard work imbalance from ``chunk`` events'
  per-shard vectors.

See README.md § Observability, "How to read a stall".
"""

from __future__ import annotations

import sys
import threading
import time
from collections import deque
from contextlib import contextmanager, nullcontext
from typing import Any, Dict, Iterable, List, Optional

#: span names attributed to the DEVICE side of the pipeline; every
#: other name is host-side work ("idle" is neither — it marks a gap).
DEVICE_SPANS = frozenset({"device", "pull", "exchange"})

#: the gap span (scheduler queue-wait, engine drain): active on
#: neither side of the sweep
IDLE_SPAN = "idle"

#: spans in which the host only waits for the device
#: (``block_until_ready``): no work of their own, so the sweep skips
#: them as it skips ``idle`` — the ``device`` span covering them
#: carries the time
WAIT_SPANS = frozenset({"sync"})

#: phases around the chunk loop: engine set-up, the finish, the verdict
#: read and the host racer. The loop's attribution leaves them out
#: (their own ``span_s.*`` timers report them); ``race.host`` runs on
#: its own thread beside the loop.
OUTSIDE_LOOP = frozenset({"spawn", "init", "finish", "mirror.pull",
                          "mirror.index", "mirror.dict", "paths",
                          "race.host", "race.decide"})

#: the chunk loop's first and last phases: its attribution window runs
#: from the first ``dispatch`` to the end of the last ``decode``
LOOP_FIRST, LOOP_LAST = "dispatch", "decode"

#: prefix of the span names in a ``jax.profiler`` trace
ANNOTATION_PREFIX = "sr."


def annotate(name: str):
    """``jax.profiler.TraceAnnotation("sr.<name>")``, or a no-op when
    this process has not imported JAX (then no profiler session can be
    running, and a host-only engine never pays JAX's import)."""
    jax = sys.modules.get("jax")
    if jax is None:
        return nullcontext()
    return jax.profiler.TraceAnnotation(ANNOTATION_PREFIX + name)


class SpanRecorder:
    """Collect phase intervals from one engine and mirror them onto
    its run trace, its metrics registry and the profiler trace.

    Engines stamp phases with ``time.perf_counter()``; trace events
    carry ``t`` = seconds since the trace's ``monotonic()`` anchor. The
    recorder captures one paired reading of both clocks at
    construction and converts every stamp, so a span's ``t0``/``t1``
    land on the same axis as every other event in the stream (and on
    the fleet timeline via the run's wall anchor).

    The in-memory ring is bounded (``limit``) and always on — a
    traceless run still gets ``profile()['attribution']`` — while the
    ``span`` trace event is only emitted when the trace has a sink.
    With ``metrics``, each span's seconds accumulate at record time
    under ``span_s.<name>``, so a wrapped ring loses none of them
    (``dropped`` counts the spans the ring let go). With ``hold``, the
    ``span`` events of phases before the run starts (an engine's
    ``spawn``/``init``) wait for :meth:`release`, so the run's header
    still leads its trace.
    """

    __slots__ = ("_trace", "_off", "_spans", "_lock", "_metrics",
                 "_recorded", "_held")

    def __init__(self, trace: Any = None, limit: int = 4096,
                 metrics: Any = None, hold: bool = False):
        pc = time.perf_counter()
        mono = time.monotonic()
        base = getattr(trace, "_t0", None)
        if base is None:  # NullTrace / no trace: own zero point
            base = mono
        # rel(stamp) = stamp + _off maps perf_counter -> trace seconds
        self._off = (mono - base) - pc
        self._trace = trace
        self._spans: deque = deque(maxlen=int(limit))
        self._lock = threading.Lock()
        self._metrics = metrics
        self._recorded = 0
        self._held: Optional[List[Dict[str, Any]]] = [] if hold else None

    def rel(self, stamp: float) -> float:
        """A ``perf_counter()`` stamp as trace-relative seconds."""
        return stamp + self._off

    def record(self, name: str, t0: float, t1: float, **fields) -> None:
        """Record one span; ``t0``/``t1`` are ``perf_counter()``
        stamps (``t1`` is clamped to ``t0``). Optional identity fields
        (``chunk``, ``shard``, ``lane``, ``job``) ride along; ``None``
        values are dropped so absent identity never pads the stream."""
        t1 = max(t0, t1)
        span: Dict[str, Any] = {
            "name": name,
            "t0": round(t0 + self._off, 6),
            "t1": round(t1 + self._off, 6),
        }
        for key, value in fields.items():
            if value is not None:
                span[key] = value
        with self._lock:
            self._spans.append(span)
            self._recorded += 1
            if self._metrics is not None:
                self._metrics.add_time("span_s." + name, t1 - t0)
            if self._held is not None:
                self._held.append(span)
                return
        trace = self._trace
        if trace:
            trace.emit("span", **span)

    def release(self) -> None:
        """Emit the spans held since construction and stop holding."""
        with self._lock:
            held, self._held = self._held, None
        trace = self._trace
        if trace:
            for span in held or ():
                trace.emit("span", **span)

    @contextmanager
    def span(self, name: str, **fields):
        """Record the enclosed block as one span, annotated for the
        profiler trace; recorded even when the block raises or returns
        early, so the timeline never loses its tail."""
        t0 = time.perf_counter()
        try:
            with annotate(name):
                yield
        finally:
            self.record(name, t0, time.perf_counter(), **fields)

    def spans(self) -> List[Dict[str, Any]]:
        """Snapshot of the recorded spans (oldest first)."""
        with self._lock:
            return list(self._spans)

    @property
    def dropped(self) -> int:
        """Spans recorded but no longer in the ring (it wrapped)."""
        return self._recorded - len(self._spans)

    def __len__(self) -> int:
        return len(self._spans)


def analyze(spans: Iterable[Dict[str, Any]]) -> Dict[str, Any]:
    """Overlap-aware critical-path attribution over one span set.

    Sweeps the interval boundaries in time order and classifies every
    elementary segment by which side is active:

    * device + host active  -> ``overlap`` (free: pipeline working)
    * device only           -> the innermost device span's name
    * host only             -> ``host:<innermost host span name>``
    * neither               -> ``idle``

    Wait spans (:data:`WAIT_SPANS`) and ``idle`` count on neither side.

    "Innermost" is the active span with the latest start, so a
    ``device`` segment nested inside an umbrella span is attributed to
    the specific phase, not the umbrella. Buckets partition the wall
    interval ``[min t0, max t1)`` exactly, so they **sum to wall** by
    construction — the invariant the tests pin.
    """
    ivs: List[tuple] = []
    for s in spans:
        try:
            t0 = float(s["t0"])
            t1 = float(s["t1"])
        except (KeyError, TypeError, ValueError):
            continue
        if t1 < t0:
            t0, t1 = t1, t0
        ivs.append((t0, t1, str(s.get("name", "?"))))
    if not ivs:
        return {"wall_s": 0.0, "t0": 0.0, "t1": 0.0, "buckets": {},
                "overlap_s": 0.0, "idle_s": 0.0, "bubble_frac": 0.0,
                "spans": 0}

    # boundary events: (t, kind) with ends (0) sorted before starts
    # (1) at equal t, so a back-to-back handoff never double-activates
    events: List[tuple] = []
    for i, (t0, t1, _name) in enumerate(ivs):
        events.append((t0, 1, i))
        events.append((t1, 0, i))
    events.sort(key=lambda e: (e[0], e[1]))

    buckets: Dict[str, float] = {}
    active: set = set()
    prev: Optional[float] = None
    for t, kind, i in events:
        if prev is not None and t > prev:
            dev = [j for j in active if ivs[j][2] in DEVICE_SPANS]
            host = [j for j in active
                    if ivs[j][2] not in DEVICE_SPANS
                    and ivs[j][2] != IDLE_SPAN
                    and ivs[j][2] not in WAIT_SPANS]
            if dev and host:
                key = "overlap"
            elif dev:
                # innermost: latest start wins (ties -> later record)
                j = max(dev, key=lambda k: (ivs[k][0], k))
                key = ivs[j][2]
            elif host:
                j = max(host, key=lambda k: (ivs[k][0], k))
                key = "host:" + ivs[j][2]
            else:
                key = IDLE_SPAN
            buckets[key] = buckets.get(key, 0.0) + (t - prev)
        prev = t
        if kind == 1:
            active.add(i)
        else:
            active.discard(i)

    t_min = min(t0 for t0, _t1, _n in ivs)
    t_max = max(t1 for _t0, t1, _n in ivs)
    wall = t_max - t_min
    overlap_s = buckets.get("overlap", 0.0)
    idle_s = buckets.get(IDLE_SPAN, 0.0)
    host_only = sum(v for k, v in buckets.items()
                    if k.startswith("host:"))
    return {
        "wall_s": wall,
        "t0": t_min,
        "t1": t_max,
        "buckets": buckets,
        "overlap_s": overlap_s,
        "idle_s": idle_s,
        # the pipeline bubble: host blocked the critical path (nothing
        # on the device) plus dead air — the addressable stall mass
        "bubble_frac": ((host_only + idle_s) / wall) if wall > 0
        else 0.0,
        "spans": len(ivs),
    }


def ranked(attribution: Dict[str, Any]) -> List[tuple]:
    """The stall table: ``(bucket, seconds, share-of-wall)`` rows,
    largest first. Rows sum to ``wall_s`` (shares to 1.0)."""
    wall = float(attribution.get("wall_s") or 0.0)
    rows = sorted(attribution.get("buckets", {}).items(),
                  key=lambda kv: (-kv[1], kv[0]))
    return [(name, secs, (secs / wall) if wall > 0 else 0.0)
            for name, secs in rows]


def top_stalls(attribution: Dict[str, Any], n: int = 3) -> List[list]:
    """The top-``n`` stall buckets as JSON-ready ``[name, seconds]``
    pairs — what ``bench.py`` embeds in workload context metrics."""
    return [[name, round(secs, 6)]
            for name, secs, _share in ranked(attribution)[:n]]


def loop_spans(spans: Iterable[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """The chunk loop's spans: those inside the window from the first
    ``dispatch`` start to the last ``decode`` end, clipped to it, less
    the phases around the loop (:data:`OUTSIDE_LOOP`). Empty when the
    spans hold no complete chunk."""
    spans = list(spans)
    starts = [s["t0"] for s in spans if s.get("name") == LOOP_FIRST]
    ends = [s["t1"] for s in spans if s.get("name") == LOOP_LAST]
    if not starts or not ends:
        return []
    lo, hi = min(starts), max(ends)
    out = []
    for s in spans:
        if s.get("name") in OUTSIDE_LOOP:
            continue
        t0, t1 = max(s["t0"], lo), min(s["t1"], hi)
        if t0 < t1:
            out.append(dict(s, t0=t0, t1=t1))
    return out


def attach_attribution(snapshot: Dict[str, Any],
                       recorder: Optional[SpanRecorder]) -> Dict[str, Any]:
    """Fold the attribution of a recorder's chunk loop
    (:func:`loop_spans`) into a ``profile()`` snapshot: ``attribution``
    (bucket -> seconds, largest first), ``idle_s`` and ``bubble_frac``.
    Mutates and returns ``snapshot``; a run with no complete chunk is
    left untouched (keys stay absent, not zero)."""
    spans = loop_spans(recorder.spans()) if recorder is not None else []
    if not spans:
        return snapshot
    attr = analyze(spans)
    snapshot["attribution"] = {
        name: round(secs, 6) for name, secs, _share in ranked(attr)}
    snapshot["idle_s"] = round(attr["idle_s"], 6)
    snapshot["bubble_frac"] = round(attr["bubble_frac"], 6)
    return snapshot


def spans_from_events(events: Iterable[Dict[str, Any]],
                      wall: bool = False) -> List[Dict[str, Any]]:
    """Extract span records from a trace event stream.

    With ``wall=True`` (merged fleet timelines from
    ``obs/aggregate.py``), each span's ``t0``/``t1`` are re-anchored to
    absolute wall seconds via the event's ``wall``/``t`` annotations,
    so spans from different runs/hosts share one axis."""
    out: List[Dict[str, Any]] = []
    for ev in events:
        if ev.get("ev") != "span":
            continue
        try:
            t0 = float(ev["t0"])
            t1 = float(ev["t1"])
        except (KeyError, TypeError, ValueError):
            continue
        if wall:
            try:
                anchor = float(ev["wall"]) - float(ev["t"])
            except (KeyError, TypeError, ValueError):
                continue  # unanchored stream: no wall axis to join
            t0 += anchor
            t1 += anchor
        span = dict(ev)
        span["t0"] = t0
        span["t1"] = t1
        out.append(span)
    return out


def shard_imbalance(events: Iterable[Dict[str, Any]]
                    ) -> Optional[Dict[str, Any]]:
    """Per-shard work imbalance from ``chunk`` events' ``shard_new``
    vectors (sharded runs only; ``None`` otherwise). ``imbalance`` is
    max-over-mean of per-shard discovered-state totals: 1.0 is a
    perfectly balanced mesh; 2.0 means the hottest shard did twice the
    mean and the collective waits for it every exchange."""
    totals: Optional[List[int]] = None
    for ev in events:
        if ev.get("ev") != "chunk":
            continue
        per_shard = ev.get("shard_new")
        if not isinstance(per_shard, (list, tuple)) or not per_shard:
            continue
        if totals is None:
            totals = [0] * len(per_shard)
        if len(per_shard) != len(totals):
            continue  # mesh width changed mid-run (degradation rung)
        for i, v in enumerate(per_shard):
            try:
                totals[i] += int(v)
            except (TypeError, ValueError):
                pass
    if not totals or sum(totals) <= 0:
        return None
    mean = sum(totals) / len(totals)
    return {
        "per_shard_new": totals,
        "max": max(totals),
        "mean": mean,
        "imbalance": (max(totals) / mean) if mean > 0 else 0.0,
    }
