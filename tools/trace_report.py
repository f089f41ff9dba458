"""Summarize a run-trace JSONL file into human-readable per-phase tables.

Usage:
    python tools/trace_report.py TRACE.jsonl [--validate]
    python tools/trace_report.py --job JOB_DIR [--validate]
    python tools/trace_report.py --fleet DIR_OR_TRACES... [--validate]

``--fleet`` merges ANY set of trace artifacts (directories expand to
their ``fleet.jsonl`` / ``service.jsonl`` / per-job and per-rank
``trace.jsonl`` / ``flight.jsonl`` — ``stateright_tpu.obs.aggregate``)
into ONE wall-anchored timeline and renders per-host / per-job
swimlanes: one row per lane, ~64 time buckets, progress density as
``.``/``:``/``#`` and interventions as letter marks (G row, R etry,
D egrade, H ost_drop, P ause, S pill, E rror, * discovery, ...),
followed by the merged intervention list with fleet-relative
timestamps and the cross-host skew bound (the ``dcn_probe`` round
trip) below which cross-host ordering is not meaningful.

``--job`` accepts a job directory (the service's per-job layout, or any
``tpu_options(artifact_dir=...)`` run) and auto-locates its artifacts:
``trace.jsonl``, the ``flight.jsonl`` postmortem dump, and — for a
service ROOT directory — ``service.jsonl`` plus every job
subdirectory's traces.

Consumes the event stream written by ``tpu_options(trace="...")``
(schema: ``stateright_tpu.obs.EVENT_SCHEMA``) and prints, per engine
found in the trace:

  * the run header (model, wall start, properties, fault injection);
  * a per-event-type table (count, first/last timestamp);
  * a chunk/level timeline in ~12 buckets — unique-states rate, dedup
    hit-rate, table load factor, queue depth — the view that makes a
    pipeline stall or a growth storm visible after the fact;
  * interventions (grow/hgrow/egrow/kovf/compile, the resilience
    layer's retry/watchdog/autosave/failover/degrade and the tiering
    layer's spill/evict events, flight-recorder dumps, and the soak
    harness's live crash/restart/partition injections) with
    timestamps — on a flaky round this table says *where* the device
    dropped, what the engine did about it, and whether an autosave
    landed;
  * a fleet summary line (process/host counts, the DCN round-trip
    probe, ranks joined, hosts dropped by the ladder's host rung) when
    the trace came from a multi-host mesh or the fleet launcher
    (``tools/mesh_launch.py``);
  * a memory-tiering summary line (spills, keys evicted to the host
    tier, the tier population and hot-set size after the last spill)
    when the run hit its HBM budget;
  * a soak summary line (ops, op timeouts, fault-injection counts,
    the history cross-check verdict) when the trace came from
    ``tools/soak.py``;
  * a resilience summary line (retries/watchdogs/failovers/degrades/
    corruptions/quarantines, the blamed device indices, and the mesh
    width a degraded run finished on);
  * an audit summary line (chunks sampled by the silent-corruption
    auditor, frontier rows re-executed, mismatches, and which devices
    lied) when the run enabled ``tpu_options(audit=...)``;
  * discoveries and the final counts.

``--validate`` additionally schema-checks every event and exits
non-zero on the first violation.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def load_events(path):
    events = []
    with open(path) as f:
        for lineno, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            try:
                events.append(json.loads(line))
            except json.JSONDecodeError as exc:
                raise SystemExit(
                    f"{path}:{lineno}: not JSONL ({exc})")
    return events


def _fmt_row(cols, widths):
    return "  ".join(str(c).rjust(w) for c, w in zip(cols, widths))


def _bucketize(rows, n_buckets=12):
    """Group progress rows (dicts with 't') into ~n_buckets spans."""
    if not rows:
        return []
    t0, t1 = rows[0]["t"], rows[-1]["t"]
    span = max(t1 - t0, 1e-9)
    step = span / n_buckets
    buckets = []
    for row in rows:
        idx = min(int((row["t"] - t0) / step), n_buckets - 1)
        if not buckets or buckets[-1][0] != idx:
            buckets.append([idx, []])
        buckets[-1][1].append(row)
    return buckets


def chunk_timeline(rows, out):
    """The stall view: per time bucket, the unique-state rate plus the
    mean dedup hit-rate / load factor / queue depth. A rate collapsing
    while load climbs toward grow_at reads as a growth storm; a flat
    rate with dedup_hit -> 1.0 means the frontier is re-generating
    explored states (raise capacity or rethink the model bounds)."""
    buckets = _bucketize(rows)
    if not buckets:
        return
    widths = (9, 9, 10, 10, 9, 10)
    out.write(_fmt_row(("t_start", "events", "uniq/s", "dedup_hit",
                        "load", "q_size"), widths) + "\n")
    prev_t = 0.0
    prev_uniq = 0
    for _idx, rs in buckets:
        t_end = rs[-1]["t"]
        uniq = rs[-1].get("unique")
        dt = max(t_end - prev_t, 1e-9)
        rate = ("-" if uniq is None
                else f"{(uniq - prev_uniq) / dt:,.0f}")
        dh = [r["dedup_hit"] for r in rs if "dedup_hit" in r]
        ld = [r["load"] for r in rs if "load" in r]
        qs = [r["q_size"] for r in rs if "q_size" in r]
        out.write(_fmt_row((
            f"{rs[0]['t']:.2f}", len(rs), rate,
            f"{sum(dh) / len(dh):.3f}" if dh else "-",
            f"{max(ld):.4f}" if ld else "-",
            max(qs) if qs else "-"), widths) + "\n")
        prev_t, prev_uniq = t_end, uniq if uniq is not None else prev_uniq


def report(events, out=None):
    # late-bind stdout: a default argument would freeze whatever stream
    # was installed at import time (pytest capture, redirections)
    out = sys.stdout if out is None else out
    by_engine = {}
    for ev in events:
        by_engine.setdefault(ev.get("engine", "?"), []).append(ev)
    for engine, evs in by_engine.items():
        out.write(f"=== engine: {engine} ({len(evs)} events, "
                  f"{evs[-1]['t'] - evs[0]['t']:.3f}s) ===\n")
        for ev in evs:
            if ev["ev"] == "run_start":
                out.write(f"model={ev.get('model')} "
                          f"properties={ev.get('properties')}\n")
            elif ev["ev"] == "fault_injection":
                out.write(f"fault injection: max_crashes="
                          f"{ev.get('max_crashes')} "
                          f"actors={ev.get('actors', 'all')}\n")

        # per-event-type table
        kinds = {}
        for ev in evs:
            kinds.setdefault(ev["ev"], []).append(ev["t"])
        widths = (14, 7, 10, 10)
        out.write("\n" + _fmt_row(("event", "count", "first_t",
                                   "last_t"), widths) + "\n")
        for kind in sorted(kinds, key=lambda k: kinds[k][0]):
            ts = kinds[kind]
            out.write(_fmt_row((kind, len(ts), f"{ts[0]:.3f}",
                                f"{ts[-1]:.3f}"), widths) + "\n")

        progress = [e for e in evs
                    if e["ev"] in ("chunk", "level", "progress")]
        if progress:
            out.write("\ntimeline:\n")
            chunk_timeline(progress, out)

        inters = [e for e in evs if e["ev"] in
                  ("grow", "hgrow", "egrow", "kovf", "compile",
                   "retry", "watchdog", "autosave", "failover",
                   "degrade", "promote", "host_promote",
                   "recorder_dump",
                   "corruption", "quarantine",
                   "spill", "evict", "pause",
                   "crash", "restart", "partition",
                   "soak_start", "violation", "burnin_preempt",
                   "job_submit", "job_start", "job_pause",
                   "job_resume", "job_done",
                   "job_promote", "job_demote",
                   "bucket_flush", "batch_form", "lane_retire",
                   "mesh_init", "host_join", "host_drop")]
        if inters:
            out.write("\ninterventions:\n")
            for ev in inters:
                detail = {k: v for k, v in ev.items()
                          if k not in ("t", "ev", "engine")}
                out.write(f"  t={ev['t']:9.3f}  {ev['ev']:8} {detail}\n")

        # resilience summary: how the run survived (and on how many
        # chips it finished) — retries/failovers alongside the ladder's
        # degrades, with every chip the faults were blamed on
        resil = [e for e in evs
                 if e["ev"] in ("retry", "failover", "degrade",
                                "promote", "watchdog",
                                "corruption", "quarantine")]
        if resil:
            counts = {}
            for ev in resil:
                counts[ev["ev"]] = counts.get(ev["ev"], 0) + 1
            plural = {"retry": "retries", "watchdog": "watchdogs",
                      "failover": "failovers", "degrade": "degrades",
                      "promote": "promotes",
                      "corruption": "corruptions",
                      "quarantine": "quarantines"}
            parts = [f"{plural[kind]}={counts[kind]}"
                     for kind in ("retry", "watchdog", "failover",
                                  "degrade", "promote",
                                  "corruption", "quarantine")
                     if kind in counts]
            blamed = sorted({ev["device"] for ev in resil
                             if ev.get("device") is not None})
            if blamed:
                parts.append(f"blamed_devices={blamed}")
            # the ladder runs BOTH ways now: the final width is the
            # last rung taken in either direction
            rungs = [e for e in resil
                     if e["ev"] in ("degrade", "promote")]
            if rungs:
                parts.append(
                    f"final_mesh={rungs[-1]['to_shards']}")
            out.write("\nresilience: " + " ".join(parts) + "\n")

        # audit summary: the silent-corruption defense's verdict —
        # chunks sampled, frontier rows re-executed on a second
        # device (or the host oracle), and how many disagreed
        audits = [e for e in evs if e["ev"] == "audit"]
        if audits:
            bad = sum(e.get("mismatches", 0) or 0 for e in audits)
            parts = [f"audits={len(audits)}",
                     f"rows={sum(e.get('rows', 0) or 0 for e in audits)}",
                     f"mismatches={bad}"]
            liars = sorted({e["device"] for e in audits
                            if e.get("mismatches")
                            and e.get("device") is not None})
            if liars:
                parts.append(f"lying_devices={liars}")
            out.write("\naudit: " + " ".join(parts) + "\n")

        # fleet summary (stateright_tpu/cluster + multi-host meshes):
        # the mesh's host/process decomposition, the DCN round-trip
        # probe, which ranks joined, and any hosts the degradation
        # ladder dropped mid-run
        mesh_evs = [e for e in evs if e["ev"] == "mesh_init"]
        joins = [e for e in evs if e["ev"] == "host_join"]
        drops = [e for e in evs if e["ev"] == "host_drop"]
        hpromotes = [e for e in evs if e["ev"] == "host_promote"]
        if mesh_evs or joins or drops or hpromotes:
            parts = []
            if mesh_evs:
                last = mesh_evs[-1]
                parts += [f"procs={last.get('procs')}",
                          f"hosts={last.get('hosts')}",
                          f"shards={last.get('shards')}"]
                if last.get("dcn_exchange_s") is not None:
                    parts.append(
                        f"dcn_exchange_s={last['dcn_exchange_s']}")
            if joins:
                parts.append(
                    f"joined={sorted(e.get('host') for e in joins)}")
            if drops:
                parts.append(
                    "host_drops="
                    f"{sorted((str(e.get('host')) for e in drops))}")
            if hpromotes:
                parts.append(
                    "host_promotes="
                    f"{sorted((str(e.get('host')) for e in hpromotes))}")
            out.write("\nfleet: " + " ".join(parts) + "\n")

        # memory-tiering summary: how the run survived its HBM budget —
        # spills taken, keys evicted to the host tier, and the tier
        # population after the last spill (rediscoveries re-promote)
        spills = [e for e in evs if e["ev"] == "spill"]
        if spills:
            evicts = [e for e in evs if e["ev"] == "evict"]
            parts = [f"spills={len(spills)}",
                     f"evicted_keys={sum(e.get('keys', 0) for e in evicts)}",
                     f"host_tier_keys={spills[-1].get('host_tier_keys')}",
                     f"hot={spills[-1].get('hot')}"]
            reasons = sorted({e.get("reason", "?") for e in spills})
            parts.append(f"reasons={reasons}")
            out.write("\ntiering: " + " ".join(parts) + "\n")

        # soak summary: a chaos soak postmortem reads like a checker
        # postmortem — op throughput, the live faults injected, and
        # whether the recorded history survived the consistency
        # cross-check
        soak_done = [e for e in evs if e["ev"] == "soak_done"]
        if soak_done:
            last = soak_done[-1]
            counts = {}
            for ev in evs:
                if ev["ev"] in ("crash", "restart", "partition"):
                    counts[ev["ev"]] = counts.get(ev["ev"], 0) + 1
            ops_evs = [e for e in evs if e["ev"] == "ops"]
            timeouts = ops_evs[-1].get("op_timeouts", 0) \
                if ops_evs else 0
            plural = {"crash": "crashes", "restart": "restarts",
                      "partition": "partitions"}
            parts = [f"ops={last.get('ops')}",
                     f"op_timeouts={timeouts}"]
            parts += [f"{plural[k]}={v}"
                      for k, v in sorted(counts.items())]
            parts.append(f"history_ok={last.get('history_ok')}")
            # the online cross-check's finds: which tester rejected,
            # and — when the incremental checker flagged it mid-run —
            # at which operation the history went bad
            viols = [e for e in evs if e["ev"] == "violation"]
            if viols:
                parts.append(f"violations={len(viols)}")
                pinned = [e["op_index"] for e in viols
                          if e.get("op_index") is not None]
                if pinned:
                    parts.append(f"violation_op={pinned[0]}")
            out.write("\nsoak: " + " ".join(parts) + "\n")

        # job-service summary (engine="service"): per-job lifecycle —
        # when it was submitted/started, pauses (with reasons:
        # user/preempt/shutdown), resumes, and how it ended
        job_evs = [e for e in evs if e["ev"].startswith("job_")]
        if job_evs:
            per_job = {}
            for ev in job_evs:
                per_job.setdefault(ev.get("job", "?"), []).append(ev)
            done = sum(1 for e in job_evs if e["ev"] == "job_done"
                       and e.get("state") == "done")
            failed = sum(1 for e in job_evs if e["ev"] == "job_done"
                         and e.get("state") == "failed")
            preempts = sum(1 for e in job_evs
                           if e["ev"] == "job_pause"
                           and e.get("reason") == "preempt")
            line = (f"\njobs: submitted="
                    f"{sum(1 for e in job_evs if e['ev'] == 'job_submit')} "
                    f"done={done} failed={failed} "
                    f"preemptions={preempts}")
            # burn-in lane visibility: background soak/fuzz jobs
            # synthesized by the scheduler, and their op-boundary
            # hand-offs to real work
            burn = sum(1 for e in job_evs if e["ev"] == "job_submit"
                       and e.get("burnin"))
            bp = [e for e in evs if e["ev"] == "burnin_preempt"]
            if burn or bp:
                line += (f"  burnin: jobs={burn} "
                         f"preempts={len(bp)}")
            out.write(line + "\n")
            for jid in sorted(per_job):
                parts = []
                for ev in per_job[jid]:
                    kind = ev["ev"][4:]  # strip "job_"
                    extra = ""
                    if ev["ev"] == "job_start" \
                            or ev["ev"] == "job_resume":
                        extra = f"(w={ev.get('width')})"
                    elif ev["ev"] == "job_pause":
                        extra = f"({ev.get('reason')})"
                    elif ev["ev"] == "job_done":
                        extra = f"({ev.get('state')})"
                        # soak/fuzz jobs carry the cross-check verdict
                        if ev.get("history_ok") is False:
                            extra += "(VIOLATION)"
                    parts.append(f"{kind}{extra}@{ev['t']:.2f}")
                out.write(f"  {jid}: " + " -> ".join(parts) + "\n")

        # batch-lane summary (service/batch.py): how many small jobs
        # rode the compile-amortized lane engine, how batches formed,
        # and why lanes retired (done vs solo fallback vs pause)
        batches = [e for e in evs if e["ev"] == "batch_form"]
        retires = [e for e in evs if e["ev"] == "lane_retire"]
        if batches or retires:
            reasons = {}
            for ev in retires:
                r = ev.get("reason", "?")
                reasons[r] = reasons.get(r, 0) + 1
            flushes = [e for e in evs if e["ev"] == "bucket_flush"]
            buckets = sorted({e.get("bucket", "?") for e in batches})
            parts = [f"batches={len(batches)}",
                     f"flushes={len(flushes)}",
                     f"lane_retires={len(retires)}"]
            if reasons:
                parts.append("reasons=" + ",".join(
                    f"{k}:{v}" for k, v in sorted(reasons.items())))
            out.write("\nbatching: " + " ".join(parts) + "\n")
            for b in buckets:
                lanes = [e.get("lanes") for e in batches
                         if e.get("bucket") == b]
                out.write(f"  bucket {b}: lanes={lanes[0]}\n")

        for ev in evs:
            if ev["ev"] == "discovery":
                out.write(f"\ndiscovered {ev.get('property')!r} at "
                          f"t={ev['t']:.3f}\n")
        for ev in evs:
            if ev["ev"] == "done":
                out.write(f"done: gen={ev.get('gen')} "
                          f"unique={ev.get('unique')} "
                          f"discoveries={ev.get('discoveries')}\n")
            elif ev["ev"] == "error":
                out.write(f"ERROR: {ev.get('error')}\n")
        out.write("\n")


def render_fleet(timeline, out=None, width: int = 64):
    """Per-host / per-job swimlanes over one merged fleet timeline."""
    from stateright_tpu.obs.aggregate import INTERVENTIONS
    out = sys.stdout if out is None else out
    events = timeline.events
    if not events:
        out.write("fleet timeline: no events\n")
        return
    lanes = timeline.lanes()
    span = max(timeline.span_s, 1e-9)
    t_min = min(e["fleet_t"] for e in events if e.get("anchored")) \
        if any(e.get("anchored") for e in events) else 0.0
    out.write(
        f"=== fleet timeline: {len(timeline.segments)} streams, "
        f"{len(events)} events, span {span:.3f}s, "
        f"skew_bound={timeline.skew_bound_s * 1e3:.3f}ms ===\n")
    unanchored = sum(1 for e in events if not e.get("anchored"))
    if unanchored:
        out.write(f"(!) {unanchored} events from pre-header streams "
                  "have no wall anchor; placed at relative time\n")
    # per-lane bucket rows: progress density beneath intervention marks
    label_w = max(len(lane) for lane in lanes)
    label_w = min(max(label_w, 4), 36)
    for lane in lanes:
        marks = [" "] * width
        density = [0] * width
        for ev in events:
            if ev["lane_key"] != lane:
                continue
            idx = min(int((ev["fleet_t"] - t_min) / span * width),
                      width - 1)
            kind = ev.get("ev")
            if kind in ("chunk", "level", "progress", "ops",
                        "pool_util"):
                density[idx] += 1
            else:
                mark = INTERVENTIONS.get(kind)
                if mark and marks[idx] == " ":
                    marks[idx] = mark
        row = []
        for i in range(width):
            if marks[i] != " ":
                row.append(marks[i])
            elif density[i] >= 4:
                row.append("#")
            elif density[i] >= 2:
                row.append(":")
            elif density[i] >= 1:
                row.append(".")
            else:
                row.append(" ")
        out.write(f"{lane[:label_w]:<{label_w}} |{''.join(row)}|\n")
    # the merged intervention list, fleet-relative
    inters = [e for e in events
              if e.get("ev") in INTERVENTIONS
              and e["ev"] not in ("compile", "discovery")]
    if inters:
        out.write("\ninterventions (fleet_t):\n")
        for ev in inters:
            detail = {k: v for k, v in ev.items()
                      if k not in ("t", "ev", "engine", "wall",
                                   "fleet_t", "lane_key", "src",
                                   "anchored", "run_id", "host",
                                   "rank")}
            out.write(f"  t={ev['fleet_t']:9.3f}  "
                      f"{ev['ev']:<14} [{ev['lane_key']}] {detail}\n")
    for ev in events:
        if ev.get("ev") == "discovery":
            out.write(f"\ndiscovered {ev.get('property')!r} on "
                      f"[{ev['lane_key']}] at t={ev['fleet_t']:.3f}\n")
    out.write("\n")


def job_traces(directory):
    """Locate a job directory's (or a service root's) trace artifacts
    by the canonical layout (``stateright_tpu.obs.artifact_paths``)."""
    found = []
    for name in ("service.jsonl", "trace.jsonl", "flight.jsonl"):
        path = os.path.join(directory, name)
        if os.path.isfile(path):
            found.append(path)
    # a service ROOT: include every job subdirectory's traces
    try:
        entries = sorted(os.listdir(directory))
    except OSError:
        entries = []
    for entry in entries:
        sub = os.path.join(directory, entry)
        if not os.path.isdir(sub):
            continue
        if not os.path.isfile(os.path.join(sub, "spec.json")):
            continue
        for name in ("trace.jsonl", "flight.jsonl"):
            path = os.path.join(sub, name)
            if os.path.isfile(path):
                found.append(path)
    return found


def main(argv):
    if not argv or argv[0] in ("-h", "--help"):
        print(__doc__)
        return 0
    validate = "--validate" in argv
    paths = [a for a in argv if not a.startswith("--")]
    if "--fleet" in argv:
        from stateright_tpu.obs import aggregate, validate_event
        if not paths:
            print("--fleet requires trace files or artifact "
                  "directories", file=sys.stderr)
            return 2
        sources = []
        for p in paths:
            if os.path.isdir(p):
                located = aggregate.collect_artifacts(p)
                if not located:
                    print(f"{p}: no trace artifacts found",
                          file=sys.stderr)
                    return 2
                sources.extend(located)
            else:
                sources.append(p)
        timeline = aggregate.merge(sources)
        if validate:
            # annotated events are supersets of the originals, and the
            # schema only pins REQUIRED fields — validate them directly
            for i, ev in enumerate(timeline.events):
                try:
                    validate_event(ev)
                except ValueError as exc:
                    print(f"fleet event {i}: {exc}", file=sys.stderr)
                    return 1
            print(f"fleet: {len(timeline.events)} events from "
                  f"{len(timeline.segments)} streams, schema OK",
                  file=sys.stderr)
        render_fleet(timeline)
        return 0
    if "--job" in argv:
        job_dirs = [paths.pop(paths.index(a))
                    for a in list(paths) if os.path.isdir(a)]
        if not job_dirs:
            print("--job requires a job directory", file=sys.stderr)
            return 2
        for d in job_dirs:
            located = job_traces(d)
            if not located:
                print(f"{d}: no trace artifacts found "
                      "(expected trace.jsonl / flight.jsonl / "
                      "service.jsonl)", file=sys.stderr)
                return 2
            print(f"# {d}: {len(located)} artifact(s)",
                  file=sys.stderr)
            paths.extend(located)
    for path in paths:
        events = load_events(path)
        if validate:
            from stateright_tpu.obs import validate_event
            for i, ev in enumerate(events):
                try:
                    validate_event(ev)
                except ValueError as exc:
                    print(f"{path}: event {i}: {exc}", file=sys.stderr)
                    return 1
            print(f"{path}: {len(events)} events, schema OK",
                  file=sys.stderr)
        report(events)
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
