"""Overlap-aware stall attribution: where did the wall time actually go?

Usage:
    python tools/stall_report.py TRACE.jsonl...
    python tools/stall_report.py --fleet DIR_OR_TRACES...

Consumes the ``span`` events emitted by the engines' dispatch/process
loops (``stateright_tpu/obs/spans.py``) and renders the ranked stall
table from the overlap-aware critical-path sweep: wall time split into
exclusively-attributed buckets that SUM TO WALL — ``device``/``pull``/
``exchange`` segments where only the device pipeline was busy,
``overlap`` where host work hid under an in-flight chunk (free, the
pipeline doing its job), ``host:<phase>`` where the host blocked an
idle device (the pipeline bubble), and ``idle`` dead air. The sweep
covers the whole run — engine set-up and the verdict read included
(``profile()['attribution']`` covers the chunk loop alone); the
biggest non-overlap row is the next perf target.

``--fleet`` merges any set of trace artifacts (directories expand via
``stateright_tpu.obs.aggregate.collect_artifacts``) onto one
wall-anchored timeline and reports per lane (per job / per rank) with
a merged summary; sharded runs get a per-shard imbalance column from
their ``chunk`` events' ``shard_new`` vectors.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _spans_mod():
    from stateright_tpu.obs import spans
    return spans


def attribution_from_events(events, wall=False):
    """``(attribution, imbalance)`` for one event stream — the shared
    consumer entry point (``perf_probe``/``prof_chunk`` shims and the
    tests call this instead of hand-parsing the trace)."""
    spans = _spans_mod()
    attr = spans.analyze(spans.spans_from_events(events, wall=wall))
    return attr, spans.shard_imbalance(events)


def summary_line(attr, imbalance=None, top=3):
    """One compact stall line (the live-console / perf-probe form):
    top buckets by share plus the bubble fraction."""
    spans = _spans_mod()
    if not attr or not attr.get("buckets"):
        return "stall: no spans"
    bits = [f"{name}={share:.0%}"
            for name, _secs, share in spans.ranked(attr)[:top]]
    bits.append(f"bubble={attr['bubble_frac']:.0%}")
    if imbalance is not None:
        bits.append(f"imbalance={imbalance['imbalance']:.2f}")
    return "stall: " + " ".join(bits)


def render(attr, imbalance=None, title=None, out=None):
    """The ranked stall table for one attribution (rows sum to wall)."""
    out = sys.stdout if out is None else out
    spans = _spans_mod()
    if title:
        print(f"# {title}", file=out)
    if not attr or not attr.get("spans"):
        print("  no span events (pre-span trace, or tracing was off)",
              file=out)
        return
    wall = attr["wall_s"]
    print(f"  wall {wall:.3f}s across {attr['spans']} spans "
          f"(span extent [{attr['t0']:.3f}, {attr['t1']:.3f}])",
          file=out)
    rows = spans.ranked(attr)
    name_w = max([len("bucket")] + [len(n) for n, _s, _f in rows])
    print(f"  {'bucket':<{name_w}}  {'seconds':>10}  {'share':>6}",
          file=out)
    total = 0.0
    for name, secs, share in rows:
        total += secs
        print(f"  {name:<{name_w}}  {secs:>10.4f}  {share:>6.1%}",
              file=out)
    print(f"  {'-' * name_w}  {'-' * 10}  {'-' * 6}", file=out)
    share = (total / wall) if wall > 0 else 0.0
    print(f"  {'sum':<{name_w}}  {total:>10.4f}  {share:>6.1%}",
          file=out)
    print(f"  bubble_frac={attr['bubble_frac']:.3f} "
          f"(host-blocking + idle share) "
          f"idle_s={attr['idle_s']:.4f} "
          f"overlap_s={attr['overlap_s']:.4f}", file=out)
    if imbalance is not None:
        print(f"  shard imbalance: max/mean="
              f"{imbalance['imbalance']:.2f} "
              f"per-shard new={imbalance['per_shard_new']}", file=out)


def render_fleet(timeline, out=None):
    """Per-lane stall tables + the merged fleet summary row set."""
    out = sys.stdout if out is None else out
    spans = _spans_mod()
    by_lane = {}
    for ev in timeline.events:
        by_lane.setdefault(ev.get("lane_key", "?"), []).append(ev)
    all_spans = []
    summary = []
    for lane in timeline.lanes():
        events = by_lane.get(lane, [])
        lane_spans = spans.spans_from_events(events, wall=True)
        all_spans.extend(lane_spans)
        attr = spans.analyze(lane_spans)
        imb = spans.shard_imbalance(events)
        if not attr["spans"]:
            continue
        ranked = spans.ranked(attr)
        top = f"{ranked[0][0]}={ranked[0][2]:.0%}" if ranked else "-"
        summary.append((lane, attr, imb, top))
        render(attr, imb, title=f"lane {lane}", out=out)
    if not summary:
        print("  no span events on the fleet timeline", file=out)
        return
    print("# fleet summary (per lane)", file=out)
    lane_w = max([len("lane")] + [len(s[0]) for s in summary])
    print(f"  {'lane':<{lane_w}}  {'wall_s':>8}  {'top stall':<18}"
          f"  {'bubble':>6}  {'imbal':>5}", file=out)
    for lane, attr, imb, top in summary:
        imb_s = f"{imb['imbalance']:.2f}" if imb is not None else "-"
        print(f"  {lane:<{lane_w}}  {attr['wall_s']:>8.3f}  "
              f"{top:<18}  {attr['bubble_frac']:>6.1%}  {imb_s:>5}",
              file=out)
    merged = spans.analyze(all_spans)
    render(merged, title="merged (wall-anchored, all lanes)", out=out)


def main(argv):
    if not argv or argv[0] in ("-h", "--help"):
        print(__doc__)
        return 0
    paths = [a for a in argv if not a.startswith("--")]
    if not paths:
        print("stall_report: no trace files given", file=sys.stderr)
        return 2
    if "--fleet" in argv:
        from stateright_tpu.obs import aggregate
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
        render_fleet(aggregate.merge(sources))
        return 0
    from trace_report import load_events
    for path in paths:
        if not os.path.isfile(path):
            print(f"{path}: not a file", file=sys.stderr)
            return 2
        events = load_events(path)
        attr, imb = attribution_from_events(events)
        render(attr, imb, title=f"stall attribution: {path}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
